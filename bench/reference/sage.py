"""GraphSAGE-mean layer (Hamilton et al. 2017) as the configuration states
it: ``z = h_v W_self + (agg_v / max(deg_v, 1)) W_neigh + b``, where
``agg_v`` sums the neighbours' rows weighted by the symmetric-normalised
edge weights (self loop included) and ``deg_v`` sums those weights.  The
published aggregator is the plain mean over neighbours; the weighting and
the clamp are this system's, stated in the configuration file.  ReLU
follows every layer but the last."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEEDS_DEGREE = True


def init(key, dims: list[int]) -> list[dict]:
    """Glorot-uniform weights, zero biases, float32."""
    params = []
    for din, dout in zip(dims[:-1], dims[1:]):
        key, k1, k2 = jax.random.split(key, 3)
        lim = (6.0 / (din + dout)) ** 0.5
        params.append({
            "w_self": jax.random.uniform(k1, (din, dout), jnp.float32,
                                         -lim, lim),
            "w_neigh": jax.random.uniform(k2, (din, dout), jnp.float32,
                                          -lim, lim),
            "b": jnp.zeros((dout,), jnp.float32)})
    return params


def layer(p: dict, h_self, agg, degree):
    """Pre-activation of one layer from the aggregated rows ``agg``."""
    mean = agg / jnp.maximum(degree, 1.0)[:, None]
    return h_self @ p["w_self"] + mean @ p["w_neigh"] + p["b"]
