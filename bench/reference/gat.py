"""Plain GAT (Velickovic et al., "Graph Attention Networks", ICLR 2018,
arXiv:1710.10903, section 3.3, the inductive model) as the configuration
states it.  A layer of ``K`` heads maps ``h`` to ``z = h W`` and, per head
``k`` and edge ``u -> v``,

    e_uv = LeakyReLU_0.2(a_src[k] . z_k[u] + a_dst[k] . z_k[v])
    alpha_uv = softmax of e_uv over the in-edges of v
    out_k[v] = sum_u alpha_uv z_k[u]

A hidden layer concatenates its ``HEADS`` heads, adds a bias per column,
adds its input where the widths match (the identity skip across the
middle layer) and applies ELU; the output layer averages its
``OUT_HEADS`` heads and adds a bias.  The neighbourhood is the graph's
in-edges, self loops included; the edge weights are not read.  A source
read from a cached tier gives its stale ``z_k[u]`` to both its logit and
its message.

Each head's weighted sum runs under ``jax.checkpoint``, so the backward
recomputes the gathered source rows instead of keeping them: at Flickr's
size one head's ``[E, 256]`` rows are 1.4 GB.  On a cached step those rows
come in one gather from the fresh and stale rows stacked.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

OWN_AGGREGATION = True
HEADS = 4            # heads of a hidden layer, concatenated
OUT_HEADS = 6        # heads of the output layer, averaged
SLOPE = 0.2


def heads_of(dout: int, last: bool) -> tuple[int, int]:
    """``(heads, width of a head)`` of a layer of output width ``dout``."""
    return (OUT_HEADS, dout) if last else (HEADS, dout // HEADS)


def init(key, dims: list[int]) -> list[dict]:
    """Glorot-uniform ``W`` and attention vectors (each as an ``[F, 1]``
    kernel), zero biases, float32."""
    params = []
    for li, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        k, f = heads_of(dout, li == len(dims) - 2)
        key, k1, k2, k3 = jax.random.split(key, 4)
        lim_w = (6.0 / (din + k * f)) ** 0.5
        lim_a = (6.0 / (f + 1)) ** 0.5
        params.append({
            "w": jax.random.uniform(k1, (din, k * f), jnp.float32,
                                    -lim_w, lim_w),
            "a_src": jax.random.uniform(k2, (k, f), jnp.float32,
                                        -lim_a, lim_a),
            "a_dst": jax.random.uniform(k3, (k, f), jnp.float32,
                                        -lim_a, lim_a),
            "b": jnp.zeros((dout,), jnp.float32)})
    return params


def layer(p: dict, h, h_stale, g):
    heads, width = p["a_src"].shape
    z = h @ p["w"]
    z_stale = None if h_stale is None else h_stale @ p["w"]
    outs = []
    for k in range(heads):
        cols = slice(k * width, (k + 1) * width)
        z_k = z[:, cols]
        zs_k = None if z_stale is None else z_stale[:, cols]
        score = g.src_rows(z_k @ p["a_src"][k],
                           None if zs_k is None else zs_k @ p["a_src"][k])
        logit = jax.nn.leaky_relu(score + (z_k @ p["a_dst"][k])[g.dst],
                                  SLOPE)
        # the maximum is a shift the softmax cancels: no gradient
        top = jax.lax.stop_gradient(jax.ops.segment_max(
            logit, g.dst, num_segments=g.num_nodes))
        e = jnp.exp(logit - top[g.dst])
        alpha = e / jax.ops.segment_sum(e, g.dst,
                                        num_segments=g.num_nodes)[g.dst]

        def weighted(z_k, zs_k, alpha):
            if zs_k is None:
                rows = z_k[g.src]
            else:
                # g.src_rows in one gather: the fresh rows, then the stale
                rows = jnp.concatenate([z_k, zs_k])[
                    g.src + g.num_nodes * g.stale.astype(g.src.dtype)]
            return jax.ops.segment_sum(alpha[:, None] * rows, g.dst,
                                       num_segments=g.num_nodes)
        outs.append(jax.checkpoint(weighted)(z_k, zs_k, alpha))
    if g.last:
        return sum(outs[1:], outs[0]) / heads + p["b"]
    out = jnp.concatenate(outs, axis=-1) + p["b"]
    if h.shape[1] == out.shape[1]:
        out = out + h
    return jax.nn.elu(out)
