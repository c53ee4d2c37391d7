"""A plain float32 GAT (Velickovic et al., "Graph Attention Networks",
ICLR 2018, section 3.3), from the paper's equations and nothing of the
program: per head ``k`` and edge ``u -> v``,
``e_uv = LeakyReLU_0.2(a_src[k] . z_k[u] + a_dst[k] . z_k[v])`` with
``z = h W``, a softmax of ``e_uv`` over the in-edges of ``v``, and
``out_k[v] = sum_u alpha_uv z_k[u]``.  Hidden layers concatenate their
heads, add a bias, add their input where the widths match and apply ELU;
the output layer averages its heads and adds a bias.

On a cached step an edge whose source sits in a cache tier reads that
source's stale layer input (``stale`` per edge, ``stale_inputs`` per layer
from 1 up), for its logit and its message alike."""
import jax
import jax.numpy as jnp


def forward(params, x, src, dst, num_nodes, stale=None, stale_inputs=None):
    """Logits of every vertex and the inputs of layers 1 and up."""
    h, inputs = x, []
    for li, p in enumerate(params):
        last = li == len(params) - 1
        if li > 0:
            inputs.append(h)
        heads, width = p["a_src"].shape
        z = h @ p["w"]
        z_old = (stale_inputs[li - 1] @ p["w"]
                 if li > 0 and stale_inputs is not None else None)
        outs = []
        for k in range(heads):
            cols = slice(k * width, (k + 1) * width)
            z_src = z[src, cols]
            if z_old is not None:
                z_src = jnp.where(stale[:, None], z_old[src, cols], z_src)
            e = jax.nn.leaky_relu(z_src @ p["a_src"][k]
                                  + (z[:, cols] @ p["a_dst"][k])[dst], 0.2)
            e = e - jax.ops.segment_max(e, dst, num_segments=num_nodes)[dst]
            alpha = jnp.exp(e) / jax.ops.segment_sum(
                jnp.exp(e), dst, num_segments=num_nodes)[dst]
            outs.append(jax.ops.segment_sum(alpha[:, None] * z_src, dst,
                                            num_segments=num_nodes))
        if last:
            h = sum(outs[1:], outs[0]) / heads + p["b"]
        else:
            out = jnp.concatenate(outs, axis=1) + p["b"]
            if out.shape[1] == h.shape[1]:
                out = out + h
            h = jax.nn.elu(out)
    return h, inputs


def loss(params, x, src, dst, labels, mask, stale=None, stale_inputs=None):
    """Mean softmax cross-entropy over the vertices where ``mask`` is 1."""
    logits, _ = forward(params, x, src, dst, x.shape[0], stale, stale_inputs)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.sum(mask)
