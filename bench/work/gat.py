"""Work of one GAT training epoch (forward and backward, no recompute), on
the same ``shape`` as :mod:`gcn`.

A layer of ``H`` heads of width ``F`` (``HF = H * F`` columns; the heads
of ``bench/reference/gat.py``: the hidden layers split their width into
``HEADS`` heads, the output layer runs ``OUT_HEADS`` heads of the class
count) transforms first, ``z = h W``, then
aggregates ``z`` under attention.  Every layer runs its aggregation
backward, layer 0 too: ``z`` depends on ``W``.

The aggregation is the edge softmax and the weighted sums, both under the
``aggregate`` scope.  FLOPs, per layer:

- the scores ``a_src . z_k`` and ``a_dst . z_k`` of every vertex: forward
  ``4 n HF``; backward ``8 n HF`` (the gradients of ``z`` and of the two
  vectors);
- per edge and head, forward 7 (the add of the two scores, LeakyReLU, the
  maximum, the shift, exp, the sum, the divide) and backward 7 (the
  divide's three, exp's multiply, LeakyReLU's multiply, the adds into the
  two scores);
- the weighted sums: forward ``2 E HF``; backward ``4 E HF`` (the
  gradients of ``z`` and of the coefficients).

Least bytes, in float32, as two passes (the softmax, then the sums), each
reading its inputs and writing its outputs once; ``R`` rows are read (inner
plus halo), ``n`` written.  Forward: the softmax reads ``z`` and each
edge's source and destination (8 bytes) and writes the coefficients (``4H``
bytes an edge); the sums read ``z``, the coefficients and the ids and write the
output rows.  Backward: the sums read the output gradient, ``z``, the
coefficients and the ids and write the gradients of ``z`` and of the
coefficients; the softmax reads the coefficients, their gradient, the ids
and ``z`` and writes ``z``'s gradient.  Per-vertex tables of ``H`` floats
(scores, maxima, sums) are left out.

GAT's aggregation is its attention, so ``attention_flops`` and
``attention_bytes`` are ``spmm_flops`` and ``spmm_bytes``:
``attention_roofline`` reads the first pair, and ``flops`` adds the dense
work to the second as :mod:`gcn` does.  Dense work counts the transform of
every vertex once: forward, weight gradient, and the input gradient of
layers 1 and up.  Element-wise work (bias, skip, ELU, head average,
softmax of the loss, Adam) is not counted.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_reference_gat",
    Path(__file__).parents[1] / "reference" / "gat.py")
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

F32 = 4


def attention(shape: dict, heads: int, hf: int) -> tuple[int, int]:
    """FLOPs and least bytes of one layer's aggregation, forward and
    backward, for ``heads`` heads of ``hf`` columns in all."""
    e, n, r = shape["edges"], shape["nodes"], shape["rows_in"]
    flops = 12 * n * hf + 14 * e * heads + 6 * e * hf
    rows = (r + (r + n) + (n + 2 * r) + 2 * r) * hf * F32
    edges = e * (2 * (8 + 4 * heads) + 2 * (8 + 8 * heads))
    return flops, rows + edges


def work(shape: dict) -> dict:
    dims, n = shape["dims"], shape["nodes"]
    agg_f = agg_b = dense = 0
    for li, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        heads, width = reference.heads_of(dout, li == len(dims) - 2)
        hf = heads * width
        f, b = attention(shape, heads, hf)
        agg_f, agg_b = agg_f + f, agg_b + b
        mm = 2 * n * din * hf
        dense += 2 * mm                      # forward + weight gradient
        if li > 0:
            dense += mm                      # input gradient
    return {"spmm_flops": agg_f, "spmm_bytes": agg_b, "dense_flops": dense,
            "flops": agg_f + dense, "attention_flops": agg_f,
            "attention_bytes": agg_b}
