"""Work of one GraphSAGE-mean training epoch (forward and backward, no
recompute), on the same ``shape`` as :mod:`gcn`.

Each layer aggregates its input rows as GCN does, divides by the weighted
in-degree, and applies two matmuls, one to the vertex's own row and one to
the aggregate.  The degree is a sum of the edge weights: one add per edge,
reading each edge's destination and weight once (8 bytes) and writing one
float per output row.  It is counted with the aggregation, forward only.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_work_gcn", Path(__file__).with_name("gcn.py"))
gcn = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gcn)


def work(shape: dict) -> dict:
    dims, n, e = shape["dims"], shape["nodes"], shape["edges"]
    agg_f = agg_b = dense = 0
    for li, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        f, b = gcn.spmm(shape, din)
        agg_f += f + e
        agg_b += b + e * 8 + n * gcn.F32
        mm = 2 * n * din * dout
        dense += 2 * 2 * mm                  # two matmuls, forward + weight grad
        if li > 0:
            f, b = gcn.spmm(shape, din, backward=True)
            agg_f, agg_b = agg_f + f, agg_b + b
            dense += 2 * mm                  # input gradient of both
    return {"spmm_flops": agg_f, "spmm_bytes": agg_b, "dense_flops": dense,
            "flops": agg_f + dense}
