"""One attention head per layer, weights from the layer's own
activations: ``z = h W``, logits ``LeakyReLU(a_dst . z_v + a_src . z_u)``
over the edges ``u -> v``, softmax over each vertex's in-edges, and the
weighted sum of ``z_u``; ELU follows every layer but the last.  A source
read from a cached tier gives its stale ``z_u`` to both the logit and the
sum."""
from __future__ import annotations

import jax
import jax.numpy as jnp

OWN_AGGREGATION = True
SLOPE = 0.2


def init(key, dims: list[int]) -> list[dict]:
    params = []
    for din, dout in zip(dims[:-1], dims[1:]):
        key, k1, k2, k3 = jax.random.split(key, 4)
        lim = (6.0 / (din + dout)) ** 0.5
        params.append({
            "w": jax.random.uniform(k1, (din, dout), jnp.float32, -lim, lim),
            "a_src": jax.random.normal(k2, (dout,), jnp.float32),
            "a_dst": jax.random.normal(k3, (dout,), jnp.float32)})
    return params


def layer(p: dict, h, h_stale, g):
    z = h @ p["w"]
    z_stale = None if h_stale is None else h_stale @ p["w"]
    z_src = g.src_rows(z, z_stale)                               # [E, d]
    logit = jax.nn.leaky_relu(z_src @ p["a_src"]
                              + (z @ p["a_dst"])[g.dst], SLOPE)
    top = jax.ops.segment_max(logit, g.dst, num_segments=g.num_nodes)
    e = jnp.exp(logit - top[g.dst])
    alpha = e / jax.ops.segment_sum(e, g.dst,
                                    num_segments=g.num_nodes)[g.dst]
    out = jax.ops.segment_sum(alpha[:, None] * z_src, g.dst,
                              num_segments=g.num_nodes)
    return out if g.last else jax.nn.elu(out)
