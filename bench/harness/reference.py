"""The plain reference: full-graph training in float32 ``jax.numpy`` at the
highest matmul precision, on the graph the partitions hold.

It imports nothing of the program.  Its inputs are the dataset (features,
labels, train mask, the normalised graph's edge list) and the partitions:
which vertices each holds and which edges it kept after pruning.  Which
halo rows a partition reads from its cache on a cached step it works out
by CaPGNN's rules (:func:`cache_tiers`), not from the program's plan.  It
draws its own weights (``bench/reference/<model>.py``), computes its own
edge weights from the graph's degrees, and runs its own Adam.

A step reads the halo rows of cached tiers as they were at the last step
that refreshed them (layers 1 and up; layer 0 reads the static features).

A model module (``bench/reference/<model>.py``) gives ``init(key, dims)``
and one of two kinds of ``layer``.  By default the reference aggregates:
``layer(p, h_self, agg, degree)`` maps the edge-weighted sum of the source
rows to the pre-activation, and ReLU follows every layer but the last.  A
module that sets ``OWN_AGGREGATION = True`` aggregates itself, for weights
that come from the layer's own activations:
``layer(p, h, h_stale, g) -> h_out``, with :class:`EdgeView` ``g`` and its
own activation (none where ``g.last``); ``h_stale`` holds the layer's
inputs as the cached tiers keep them, ``None`` on layer 0 and on steps
that refresh.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


@dataclasses.dataclass
class Graph:
    """Global edge list of the pruned graph (numpy, host side)."""
    num_nodes: int
    src: np.ndarray        # [E] int32
    dst: np.ndarray        # [E] int32
    weight: np.ndarray     # [E] float32, D^-1/2 (A+I) D^-1/2 of the dataset
    stale: np.ndarray      # [E] bool, source read from a cache tier
    features: np.ndarray   # [N, F] float32
    labels: np.ndarray     # [N] int32
    train_mask: np.ndarray  # [N] float32


def build_graph(data_src, data_dst, features, labels, train_mask,
                parts) -> Graph:
    """``data_src/dst``: the dataset's edge list (self loops included).
    ``parts``: per partition ``(inner_ids, halo_ids, local_src, local_dst,
    cached_halo_pos)`` in the partition's local numbering.  Checks that
    every vertex is inner to exactly one partition, that every kept edge
    ends at an inner vertex and exists in the dataset."""
    n = features.shape[0]
    owner = np.full(n, -1, np.int64)
    srcs, dsts, stale = [], [], []
    for p, (inner, halo, lsrc, ldst, cached_pos) in enumerate(parts):
        if (owner[inner] >= 0).any():
            raise ValueError(f"partition {p} repeats an inner vertex")
        owner[inner] = p
        gids = np.concatenate([inner, halo]).astype(np.int64)
        ni = inner.shape[0]
        if (ldst >= ni).any():
            raise ValueError(f"partition {p} keeps an edge into a halo row")
        cached = np.zeros(halo.shape[0] + 1, bool)
        cached[np.asarray(cached_pos, np.int64)] = True
        hpos = np.where(lsrc >= ni, lsrc - ni, halo.shape[0])
        srcs.append(gids[lsrc])
        dsts.append(gids[ldst])
        stale.append(cached[hpos])
    if (owner < 0).any():
        raise ValueError("a vertex is inner to no partition")
    src, dst = np.concatenate(srcs), np.concatenate(dsts)
    data_keys = np.asarray(data_src, np.int64) * n + np.asarray(data_dst)
    if not np.isin(src * n + dst, data_keys).all():
        raise ValueError("a kept edge is not an edge of the dataset")
    deg_out = np.bincount(np.asarray(data_src), minlength=n).astype(np.float64)
    deg_in = np.bincount(np.asarray(data_dst), minlength=n).astype(np.float64)
    w = (np.maximum(deg_out, 1.0)[src] ** -0.5
         * np.maximum(deg_in, 1.0)[dst] ** -0.5).astype(np.float32)
    return Graph(num_nodes=n, src=src.astype(np.int32),
                 dst=dst.astype(np.int32), weight=w,
                 stale=np.concatenate(stale), features=features,
                 labels=labels.astype(np.int32),
                 train_mask=train_mask.astype(np.float32))


def cache_tiers(halos, n_inner, n_edges, dims, mem_gib, cpu_cache_gib,
                jaca: bool) -> list:
    """The halo positions each partition reads from a cache tier on a
    cached step, by CaPGNN's rules (paper Alg. 1 and Eq. 2), worked out
    from the partitions' halo sets: a worker's local cache holds what its
    memory leaves (``mem_gib`` less 512 MiB and its resident rows and
    edges, at float32 rows of ``dims``), filled by overlap ratio R(v), the
    number of partitions whose halo holds v, highest first; the shared
    tier holds the union's highest-R rows that ``cpu_cache_gib`` less
    1 GiB leaves.  Without JACA nothing is cached."""
    if not jaca:
        return [np.zeros(0, np.int64) for _ in halos]
    mib = 1024.0 ** 2
    row = 4.0 * sum(dims)
    overlap = {}
    for h in halos:
        for v in h.tolist():
            overlap[v] = overlap.get(v, 0) + 1
    union = np.array(sorted(overlap), np.int64)
    r_union = np.array([overlap[v] for v in union.tolist()])
    c_cpu = int(min(max(0.0, cpu_cache_gib * 1024.0 - 1024.0) * mib // row,
                    union.size))
    shared = set(union[np.argsort(-r_union, kind="stable")][:c_cpu].tolist())
    out = []
    for h, ni, ne, mem in zip(halos, n_inner, n_edges, mem_gib):
        avail = max(0.0, mem * 1024.0 - 512.0) * mib
        avail = max(0.0, avail - ((ni + h.size) * row + 8.0 * ne))
        c_local = int(min(avail // row, h.size))
        rank = np.argsort(-np.array([overlap[v] for v in h.tolist()]),
                          kind="stable")
        rest = rank[c_local:]
        in_shared = np.array([int(h[p]) in shared for p in rest], bool)
        out.append(np.sort(np.concatenate([rank[:c_local],
                                           rest[in_shared]])))
    return out


@dataclasses.dataclass(frozen=True)
class EdgeView:
    """The graph as a layer that aggregates itself reads it, inside the
    traced step: ``src``, ``dst`` and ``w`` per edge, ``num_nodes``, and
    whether the layer is the last."""
    src: jax.Array
    dst: jax.Array
    w: jax.Array
    stale: jax.Array      # [E] bool, the source is read from a cache tier
    num_nodes: int
    last: bool

    def src_rows(self, x, x_stale=None):
        """Each edge's source row of the per-node tensor ``x``, taken from
        ``x_stale`` (the same tensor computed from the stale inputs) where
        the edge reads a cached tier."""
        rows = x[self.src]
        if x_stale is None:
            return rows
        stale = self.stale.reshape(self.stale.shape + (1,) * (x.ndim - 1))
        return jnp.where(stale, x_stale[self.src], rows)


def _adam(params, grads, state, lr):
    m, v, t = state
    t = t + 1
    m = jax.tree.map(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, m, grads)
    v = jax.tree.map(lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g,
                     v, grads)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS),
        params, m, v)
    return params, (m, v, t)


def device_graph(g: Graph, dtype=jnp.float32) -> dict:
    """The graph's arrays on the device, passed to the jitted steps as
    arguments (captured, they would be compiled in as constants)."""
    d = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst),
         "w": jnp.asarray(g.weight, dtype), "stale": jnp.asarray(g.stale),
         "x": jnp.asarray(g.features, dtype),
         "labels": jnp.asarray(g.labels), "mask": jnp.asarray(g.train_mask)}
    n = g.num_nodes
    agg = jax.jit(lambda d: jax.ops.segment_sum(
        d["x"][d["src"]] * d["w"][:, None], d["dst"], num_segments=n))
    d["agg0"] = agg(d)     # layer 0 reads the static features only
    d["degree"] = jax.jit(lambda d: jax.ops.segment_sum(
        d["w"], d["dst"], num_segments=n))(d)
    return d


def make_step(model, num_nodes: int, lr: float, dtype=jnp.float32,
              precision: str = "highest"):
    """``step(params, state, stale_acts, data, use_stale) -> (params,
    state, loss, grads, acts)``; ``acts`` are the inputs of layers 1 and
    up, as a refresh would cache them.  ``dtype`` is the compute type of
    the forward and backward pass and ``precision`` that of its matmuls;
    the float32 master weights and Adam state stay float32.  The reference
    is float32 at ``highest``; the other settings are controls."""
    own = getattr(model, "OWN_AGGREGATION", False)

    def aggregate(d, h, h_stale=None):
        rows = h[d["src"]]
        if h_stale is not None:
            rows = jnp.where(d["stale"][:, None], h_stale[d["src"]], rows)
        return jax.ops.segment_sum(rows * d["w"][:, None], d["dst"],
                                   num_segments=num_nodes)

    def loss_fn(params, stale_acts, d, use_stale):
        params = jax.tree.map(lambda a: a.astype(dtype), params)
        h, acts = d["x"], []
        for li, p in enumerate(params):
            if li > 0:
                acts.append(h)
            h_stale = stale_acts[li - 1] if li > 0 and use_stale else None
            if own:
                h = model.layer(p, h, h_stale, EdgeView(
                    src=d["src"], dst=d["dst"], w=d["w"], stale=d["stale"],
                    num_nodes=num_nodes, last=li == len(params) - 1))
                continue
            agg = d["agg0"] if li == 0 else aggregate(d, h, h_stale)
            h = model.layer(p, h, agg, d["degree"])
            if li < len(params) - 1:
                h = jax.nn.relu(h)
        logp = jax.nn.log_softmax(h.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, d["labels"][:, None], -1)[:, 0]
        mask = d["mask"]
        loss = jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
        return loss, [jax.lax.stop_gradient(a) for a in acts]

    def step(params, state, stale_acts, d, use_stale: bool):
        with jax.default_matmul_precision(precision):
            (loss, acts), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, stale_acts, d, use_stale)
            grads = jax.tree.map(lambda a: a.astype(jnp.float32), grads)
            params, state = _adam(params, grads, state, lr)
        return params, state, loss, grads, acts

    return jax.jit(step, static_argnums=(4,))


def run(step, data: dict, params0, kinds: list[str]) -> dict:
    """Follow the schedule ``kinds`` (``refresh``/``cached``/``pipelined``)
    from ``params0`` with ``step`` (:func:`make_step`) over ``data``
    (:func:`device_graph`); return the readings the comparison needs: the
    loss of every step, the leaf norms of the first gradient, and the leaf
    norms of the change of the parameters over all steps."""
    params = params0
    zeros = jax.tree.map(jnp.zeros_like, params0)
    state = (zeros, zeros, jnp.zeros((), jnp.float32))
    stale_acts = [jnp.zeros((data["x"].shape[0], 1), data["x"].dtype)]
    losses, grad_norms = [], None
    for kind in kinds:
        use_stale = kind != "refresh"
        params, state, loss, grads, acts = step(params, state, stale_acts,
                                                data, use_stale)
        if kind != "cached":
            stale_acts = acts
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
    delta = jax.tree.map(lambda a, b: a - b, params, params0)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": leaf_norms(delta)}


def leaf_norms(tree) -> list[float]:
    return [float(jnp.linalg.norm(jnp.ravel(a).astype(jnp.float32)))
            for a in jax.tree.leaves(tree)]
