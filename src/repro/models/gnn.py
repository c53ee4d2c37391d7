"""GNN models (GCN, GraphSAGE, GAT, GIN) over pluggable aggregation backends.

Layer contract (partition-parallel form): a layer maps
``h_local = concat([h_inner, h_halo])  [n_local, d_in]`` to new inner
embeddings ``[n_inner, d_out]`` via an :class:`Adjacency` whose rows are the
partition's inner vertices and whose columns are local ids.  On a single
worker with no partitioning, n_halo = 0 and this reduces to the textbook
model — that equivalence is what the correctness tests assert.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn import glorot, zeros_init
from repro.graph.graph import Graph
from repro.obs.annotations import device_scope

__all__ = ["Adjacency", "DenseAdj", "EdgeListAdj", "EllAdj", "HybridAdj",
           "BACKENDS", "GNNConfig", "init_gnn", "gnn_forward",
           "make_local_adj", "cross_entropy_loss", "bce_loss", "accuracy"]

BACKENDS = ("edges", "dense", "ell", "hybrid")


# ---------------------------------------------------------------------------
# Aggregation backends
# ---------------------------------------------------------------------------

class Adjacency:
    """Abstract aggregation operator: rows = inner vertices, cols = local.

    Every backend provides ``spmm`` and ``degree``; ``spmm_at`` (per-edge
    values, the GAT edge-softmax path) is a capability — backends that can't
    express it raise a :class:`NotImplementedError` naming themselves and
    the ``backend="edges"`` fallback.
    """

    n_rows: int
    n_cols: int

    def spmm(self, h: jnp.ndarray) -> jnp.ndarray:   # [n_cols, d] -> [n_rows, d]
        raise NotImplementedError

    def degree(self) -> jnp.ndarray:
        """Weighted in-degree per inner row.

        Default: ``spmm`` against a ones column — exact for every backend
        since padding entries carry zero weight.  Backends with a cheaper
        closed form override this.
        """
        return self.spmm(jnp.ones((self.n_cols, 1), jnp.float32))[:, 0]

    def spmm_at(self, e_vals: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
        """SpMM with externally supplied per-edge values (GAT attention)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support per-edge-value "
            "aggregation (spmm_at); GAT's edge softmax needs flat edge ids "
            "— build the adjacency with backend='edges'.")


@dataclasses.dataclass(frozen=True)
class DenseAdj(Adjacency):
    """Dense normalized adjacency (tests / tiny graphs)."""
    mat: jnp.ndarray   # [n_rows, n_cols]

    @property
    def n_rows(self):
        return self.mat.shape[0]

    @property
    def n_cols(self):
        return self.mat.shape[1]

    def spmm(self, h):
        return self.mat @ h


@dataclasses.dataclass(frozen=True)
class EdgeListAdj(Adjacency):
    """COO edge list + segment-sum aggregation (jnp reference backend)."""
    src: jnp.ndarray      # [m] local col ids
    dst: jnp.ndarray      # [m] inner row ids
    weight: jnp.ndarray   # [m]
    n_rows_: int
    n_cols_: int

    @property
    def n_rows(self):
        return self.n_rows_

    @property
    def n_cols(self):
        return self.n_cols_

    def spmm(self, h):
        msgs = h[self.src] * self.weight[:, None]
        return jax.ops.segment_sum(msgs, self.dst, num_segments=self.n_rows_)

    def spmm_at(self, e_vals, h):
        msgs = h[self.src] * e_vals[:, None]
        return jax.ops.segment_sum(msgs, self.dst, num_segments=self.n_rows_)

    def degree(self):
        # weighted in-degree — consistent with the spmm(ones) fallback of the
        # dense/ELL backends and with the stacked worker layer (SAGE mean is
        # the ew-weighted mean on the normalized graph).
        return jax.ops.segment_sum(self.weight, self.dst,
                                   num_segments=self.n_rows_)


@dataclasses.dataclass(frozen=True)
class EllAdj(Adjacency):
    """Blocked-ELL adjacency backed by the Pallas SpMM kernel."""
    cols: jnp.ndarray     # [n_rows, max_deg] local col ids (padded)
    vals: jnp.ndarray     # [n_rows, max_deg] weights (0 at padding)
    n_cols_: int

    @property
    def n_rows(self):
        return self.cols.shape[0]

    @property
    def n_cols(self):
        return self.n_cols_

    def spmm(self, h):
        from repro.kernels.ops import ell_spmm
        return ell_spmm(self.cols, self.vals, h)

    def spmm_at(self, e_vals, h):
        """SpMM with ELL-shaped per-edge values ``[n_rows, max_deg]``.

        Padding slots (``vals == 0``) are masked out, so callers may pass
        unmasked attention scores in the same ELL layout.
        """
        from repro.kernels.ops import ell_spmm
        v = jnp.where(self.vals != 0, e_vals, 0.0)
        return ell_spmm(self.cols, v, h)


@dataclasses.dataclass(frozen=True)
class HybridAdj(Adjacency):
    """Hybrid blocked-ELL + COO-tail adjacency (Pallas kernel + segment-sum).

    The regular part is packed to the degree quantile; overflow edges of
    heavy rows live in a COO tail aggregated by segment-sum.  Padded tail
    entries carry ``tail_dst == n_rows`` and are dropped by the scatter, so
    the tail arrays may be padded to a static width (mesh shards).
    """
    cols: jnp.ndarray      # [n_rows, max_deg] local col ids (padded)
    vals: jnp.ndarray      # [n_rows, max_deg] weights (0 at padding)
    tail_src: jnp.ndarray  # [mt] local col ids
    tail_dst: jnp.ndarray  # [mt] inner row ids (n_rows = padding)
    tail_w: jnp.ndarray    # [mt] weights (0 at padding)
    n_cols_: int

    @property
    def n_rows(self):
        return self.cols.shape[0]

    @property
    def n_cols(self):
        return self.n_cols_

    def spmm(self, h):
        from repro.kernels.ops import hybrid_spmm
        return hybrid_spmm(self.cols, self.vals, self.tail_src,
                           self.tail_dst, self.tail_w, h)


def make_local_adj(local_graph: Graph, n_inner: int, backend: str = "edges"
                   ) -> Adjacency:
    """Build an Adjacency for a partition-local graph (rows = inner)."""
    src, dst = local_graph.edges()
    keep = dst < n_inner
    src, dst = src[keep], dst[keep]
    w = (local_graph.edge_weight[keep] if local_graph.edge_weight is not None
         else np.ones(src.shape[0], np.float32))
    n_cols = local_graph.num_nodes
    if backend == "dense":
        mat = np.zeros((n_inner, n_cols), np.float32)
        mat[dst, src] = w
        return DenseAdj(jnp.asarray(mat))
    if backend == "edges":
        return EdgeListAdj(jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
                           jnp.asarray(w, jnp.float32), n_inner, n_cols)
    if backend == "ell":
        from repro.kernels.ops import ell_pack
        cols, vals = ell_pack(src, dst, w, n_inner)
        return EllAdj(jnp.asarray(cols), jnp.asarray(vals), n_cols)
    if backend == "hybrid":
        from repro.kernels.ops import ell_pack_hybrid
        cols, vals, ts, td, tw = ell_pack_hybrid(src, dst, w, n_inner)
        return HybridAdj(jnp.asarray(cols), jnp.asarray(vals),
                         jnp.asarray(ts), jnp.asarray(td), jnp.asarray(tw),
                         n_cols)
    raise ValueError(f"unknown aggregation backend {backend!r}; "
                     f"expected one of {BACKENDS}")


# ---------------------------------------------------------------------------
# Models
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GNNConfig:
    model: str = "gcn"            # gcn | sage | gat | gin
    in_dim: int = 64
    hidden_dim: int = 256         # paper: 256
    out_dim: int = 16
    num_layers: int = 3           # paper: 3
    num_heads: int = 4            # GAT
    residual: bool = False

    @property
    def layer_dims(self) -> list[tuple[int, int]]:
        dims = [self.in_dim] + [self.hidden_dim] * (self.num_layers - 1) + [self.out_dim]
        return list(zip(dims[:-1], dims[1:]))

    @property
    def feat_dims(self) -> list[int]:
        """Per-tier cached row widths: input features + each layer output."""
        return [self.in_dim] + [self.hidden_dim] * (self.num_layers - 1) + [self.out_dim]


def init_gnn(key, cfg: GNNConfig) -> list[dict]:
    params = []
    for li, (din, dout) in enumerate(cfg.layer_dims):
        key, k1, k2, k3 = jax.random.split(key, 4)
        if cfg.model == "gcn":
            p = {"w": glorot(k1, (din, dout)), "b": zeros_init(k2, (dout,))}
        elif cfg.model == "sage":
            p = {"w_self": glorot(k1, (din, dout)),
                 "w_neigh": glorot(k2, (din, dout)),
                 "b": zeros_init(k3, (dout,))}
        elif cfg.model == "gat":
            h = cfg.num_heads
            dh = max(1, dout // h)
            p = {"w": glorot(k1, (din, h * dh)),
                 "a_src": glorot(k2, (h, dh)),
                 "a_dst": glorot(k3, (h, dh)),
                 "proj": glorot(key, (h * dh, dout))}
        elif cfg.model == "gin":
            p = {"w1": glorot(k1, (din, dout)), "b1": zeros_init(k2, (dout,)),
                 "w2": glorot(k3, (dout, dout)), "b2": zeros_init(key, (dout,)),
                 "eps": jnp.zeros(())}
        else:
            raise ValueError(cfg.model)
        params.append(p)
    return params


def _layer_apply(cfg: GNNConfig, p: dict, adj: Adjacency,
                 h_local: jnp.ndarray, n_inner: int, is_last: bool) -> jnp.ndarray:
    """One layer, its work under the two scopes of a layer
    (:data:`repro.obs.annotations.LAYER_PARTS`): ``aggregate``, the
    adjacency product with SAGE's degree normalisation and GAT's edge
    softmax, and ``transform``, the dense work and the activation."""
    if cfg.model == "gcn":
        with device_scope("aggregate"):
            agg = adj.spmm(h_local)
        with device_scope("transform"):
            z = agg @ p["w"] + p["b"]
    elif cfg.model == "sage":
        with device_scope("aggregate"):
            agg = adj.spmm(h_local)
            agg = agg / jnp.maximum(adj.degree()[:, None], 1.0)
        with device_scope("transform"):
            z = h_local[:n_inner] @ p["w_self"] + agg @ p["w_neigh"] + p["b"]
    elif cfg.model == "gat":
        if not isinstance(adj, EdgeListAdj):
            raise NotImplementedError(
                f"GAT's edge softmax needs flat edge ids, which the "
                f"{type(adj).__name__} backend does not expose — build the "
                "adjacency/runtime with backend='edges' for GAT.")
        with device_scope("transform"):
            h_heads = (h_local @ p["w"]).reshape(h_local.shape[0],
                                                 p["a_src"].shape[0], -1)
        with device_scope("aggregate"):
            e_src = jnp.einsum("nhd,hd->nh", h_heads, p["a_src"])
            e_dst = jnp.einsum("nhd,hd->nh", h_heads, p["a_dst"])
            logits = jax.nn.leaky_relu(e_src[adj.src] + e_dst[adj.dst], 0.2)
            # segment softmax over incoming edges of each inner vertex
            seg_max = jax.ops.segment_max(logits, adj.dst,
                                          num_segments=adj.n_rows)
            ex = jnp.exp(logits - seg_max[adj.dst])
            denom = jax.ops.segment_sum(ex, adj.dst, num_segments=adj.n_rows)
            att = ex / jnp.maximum(denom[adj.dst], 1e-9)
            outs = []
            for hh in range(att.shape[1]):
                outs.append(adj.spmm_at(att[:, hh], h_heads[:, hh, :]))
            agg = jnp.concatenate(outs, axis=-1)
        with device_scope("transform"):
            z = agg @ p["proj"]
    elif cfg.model == "gin":
        with device_scope("aggregate"):
            agg = adj.spmm(h_local)
        with device_scope("transform"):
            z = (1.0 + p["eps"]) * h_local[:n_inner] + agg
            z = jax.nn.relu(z @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
    else:
        raise ValueError(cfg.model)
    if not is_last:
        with device_scope("transform"):
            z = jax.nn.relu(z)
    return z


def gnn_forward(cfg: GNNConfig, params: list[dict], adj: Adjacency,
                h_inner: jnp.ndarray,
                halo_embeds: Sequence[jnp.ndarray] | None) -> jnp.ndarray:
    """Partition-local forward.

    ``halo_embeds[l]`` are the halo embeddings consumed by layer ``l``
    (layer 0: halo input features; layer l>0: remote layer-(l) inputs).
    ``None`` means no halo (single-worker full graph).
    Returns inner-vertex logits.
    """
    n_inner = h_inner.shape[0]
    h = h_inner
    for li, p in enumerate(params):
        if halo_embeds is not None:
            h_local = jnp.concatenate([h, halo_embeds[li]], axis=0)
        else:
            h_local = h
        h = _layer_apply(cfg, p, adj, h_local, n_inner,
                         is_last=(li == len(params) - 1))
    return h


# ---------------------------------------------------------------------------
# Losses / metrics
# ---------------------------------------------------------------------------

def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       mask: jnp.ndarray | None = None) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), -1)[:, 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def bce_loss(logits: jnp.ndarray, targets: jnp.ndarray,
             mask: jnp.ndarray | None = None) -> jnp.ndarray:
    per = jnp.maximum(logits, 0) - logits * targets + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    per = per.mean(-1)
    if mask is not None:
        return jnp.sum(per * mask) / jnp.maximum(mask.sum(), 1.0)
    return per.mean()


def accuracy(logits: jnp.ndarray, labels: jnp.ndarray,
             mask: jnp.ndarray | None = None) -> jnp.ndarray:
    correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    if mask is not None:
        return jnp.sum(correct * mask) / jnp.maximum(mask.sum(), 1.0)
    return correct.mean()
