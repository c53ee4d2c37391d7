"""Work of one GCN training epoch (forward and backward, no recompute).

``shape`` holds what the partitioned graph needs, summed over partitions:
``nodes`` (inner vertices, each counted once), ``edges`` (aggregation
edges kept, self loops included), ``rows_in`` (inner plus halo rows that
the aggregation reads) and ``dims`` (layer widths, input first).

Aggregation of width ``d`` is one multiply-add per edge and feature.  Its
least bytes, in float32, read each input row once, the edge's source,
destination and weight once (12 bytes), and write each output row once.
The backward aggregation runs for layers 1 and up only: layer 0 reads
the input features, which take no gradient.  Dense work counts the
matmuls: forward, weight gradient, and the input gradient of layers 1 and
up.  Element-wise work (bias, ReLU, softmax, Adam) is not counted.
"""
from __future__ import annotations

F32 = 4
EDGE_BYTES = 12


def spmm(shape: dict, d: int, backward: bool = False) -> tuple[int, int]:
    """FLOPs and least bytes of one aggregation of width ``d``."""
    e, n, r = shape["edges"], shape["nodes"], shape["rows_in"]
    flops = 2 * e * d
    read_rows, write_rows = (n, r) if backward else (r, n)
    return flops, (read_rows + write_rows) * d * F32 + e * EDGE_BYTES


def work(shape: dict) -> dict:
    dims, n = shape["dims"], shape["nodes"]
    agg_f = agg_b = dense = 0
    for li, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        f, b = spmm(shape, din)
        agg_f, agg_b = agg_f + f, agg_b + b
        mm = 2 * n * din * dout
        dense += 2 * mm                      # forward + weight gradient
        if li > 0:
            f, b = spmm(shape, din, backward=True)
            agg_f, agg_b = agg_f + f, agg_b + b
            dense += mm                      # input gradient
    return {"spmm_flops": agg_f, "spmm_bytes": agg_b, "dense_flops": dense,
            "flops": agg_f + dense}
