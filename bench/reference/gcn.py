"""Plain GCN layer (Kipf & Welling 2017): ``z = (A_hat h) W + b``, with
``A_hat`` the symmetric-normalised adjacency with self loops.  ReLU
follows every layer but the last."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEEDS_DEGREE = False


def init(key, dims: list[int]) -> list[dict]:
    """Glorot-uniform weights, zero biases, float32."""
    params = []
    for din, dout in zip(dims[:-1], dims[1:]):
        key, k = jax.random.split(key)
        lim = (6.0 / (din + dout)) ** 0.5
        params.append({"w": jax.random.uniform(k, (din, dout), jnp.float32,
                                               -lim, lim),
                       "b": jnp.zeros((dout,), jnp.float32)})
    return params


def layer(p: dict, h_self, agg, degree):
    """Pre-activation of one layer from the aggregated rows ``agg``."""
    del h_self, degree
    return agg @ p["w"] + p["b"]
