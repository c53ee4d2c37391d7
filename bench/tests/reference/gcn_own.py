"""The GCN of ``bench/reference/gcn.py`` written as a layer that
aggregates itself (``OWN_AGGREGATION``), op for op as the harness
aggregates for it: the same weights, the same readings."""
from __future__ import annotations

import jax
import jax.numpy as jnp

OWN_AGGREGATION = True


def init(key, dims: list[int]) -> list[dict]:
    params = []
    for din, dout in zip(dims[:-1], dims[1:]):
        key, k = jax.random.split(key)
        lim = (6.0 / (din + dout)) ** 0.5
        params.append({"w": jax.random.uniform(k, (din, dout), jnp.float32,
                                               -lim, lim),
                       "b": jnp.zeros((dout,), jnp.float32)})
    return params


def layer(p: dict, h, h_stale, g):
    rows = g.src_rows(h, h_stale)
    agg = jax.ops.segment_sum(rows * g.w[:, None], g.dst,
                              num_segments=g.num_nodes)
    z = agg @ p["w"] + p["b"]
    return z if g.last else jax.nn.relu(z)
