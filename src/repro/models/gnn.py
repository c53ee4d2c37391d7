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
        """``e_vals [m]`` weighs every column of an edge's message;
        head-major ``e_vals [k, m]`` weighs each of ``h``'s ``k`` column
        blocks (GAT's heads side by side) by its own row."""
        if e_vals.ndim == 2:
            scale = jnp.repeat(e_vals.T, h.shape[1] // e_vals.shape[0],
                               axis=1)
        else:
            scale = e_vals[:, None]
        msgs = h[self.src] * scale
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
    num_heads: int = 4            # GAT: heads of a hidden layer
    out_heads: int = 6            # GAT: heads of the output layer
    residual: bool = False

    @property
    def layer_dims(self) -> list[tuple[int, int]]:
        dims = [self.in_dim] + [self.hidden_dim] * (self.num_layers - 1) + [self.out_dim]
        return list(zip(dims[:-1], dims[1:]))

    @property
    def feat_dims(self) -> list[int]:
        """Per-tier cached row widths: input features + each layer output."""
        return [self.in_dim] + [self.hidden_dim] * (self.num_layers - 1) + [self.out_dim]

    def gat_heads(self, layer: int) -> tuple[int, int]:
        """GAT: ``(heads, width of a head)`` of layer ``layer``.  A hidden
        layer splits ``hidden_dim`` into ``num_heads`` heads, concatenated;
        the output layer runs ``out_heads`` heads of ``out_dim``, averaged
        (Velickovic et al. 2018, section 3.3)."""
        if layer == self.num_layers - 1:
            return self.out_heads, self.out_dim
        if self.hidden_dim % self.num_heads:
            raise ValueError(f"GAT: num_heads={self.num_heads} does not "
                             f"divide hidden_dim={self.hidden_dim}, the "
                             "concatenated width of a hidden layer")
        return self.num_heads, self.hidden_dim // self.num_heads

    @property
    def attention_heads(self) -> int:
        """Attention heads summed over the layers: GAT computes one edge
        softmax per edge and head of each layer.  0 for the other
        models."""
        if self.model != "gat":
            return 0
        return sum(self.gat_heads(li)[0] for li in range(self.num_layers))


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
            h, f = cfg.gat_heads(li)
            # each head's attention vector is Glorot-uniform as an [f, 1]
            # kernel; the bias is one per output column
            p = {"w": glorot(k1, (din, h * f)),
                 "a_src": glorot(k2, (h, f, 1))[..., 0],
                 "a_dst": glorot(k3, (h, f, 1))[..., 0],
                 "b": zeros_init(key, (dout,))}
        elif cfg.model == "gin":
            p = {"w1": glorot(k1, (din, dout)), "b1": zeros_init(k2, (dout,)),
                 "w2": glorot(k3, (dout, dout)), "b2": zeros_init(key, (dout,)),
                 "eps": jnp.zeros(())}
        else:
            raise ValueError(cfg.model)
        params.append(p)
    return params


def _edge_softmax(z: jnp.ndarray, a_src: jnp.ndarray, a_dst: jnp.ndarray,
                  adj: EdgeListAdj) -> jnp.ndarray:
    """GAT's attention coefficients, head-major ``[heads, E]``: per head
    ``h`` and edge ``u -> v``, ``e = LeakyReLU_0.2(a_src[h] . z_h[u] +
    a_dst[h] . z_h[v])``, normalised by a softmax over ``v``'s in-edges.
    ``z`` holds the heads side by side, ``[rows, heads * width]``.  The
    per-vertex maximum is a shift the softmax cancels, so no gradient
    flows through it.  A padding edge (destination ``n_rows``, as the
    mesh shards' edge rectangle holds) reads a neutral maximum and sum:
    its coefficient stays finite, and the sums drop it."""
    heads, width = a_src.shape
    alpha = []
    for h in range(heads):
        z_h = z[:, h * width:(h + 1) * width]
        logit = jax.nn.leaky_relu((z_h @ a_src[h])[adj.src]
                                  + (z_h[:adj.n_rows] @ a_dst[h])[adj.dst],
                                  0.2)
        top = jax.lax.stop_gradient(jax.ops.segment_max(
            logit, adj.dst, num_segments=adj.n_rows))
        ex = jnp.exp(logit - top.at[adj.dst].get(mode="fill",
                                                 fill_value=0.0))
        den = jax.ops.segment_sum(ex, adj.dst, num_segments=adj.n_rows)
        alpha.append(ex / den.at[adj.dst].get(mode="fill", fill_value=1.0))
    return jnp.stack(alpha)


def head_sum(adj: EdgeListAdj, alpha: jnp.ndarray,
             z: jnp.ndarray) -> jnp.ndarray:
    """``adj.spmm_at(alpha, z)``, the attention-weighted sum of heads side
    by side, whose backward recomputes the gathered source rows from the
    saved ``alpha`` and ``z``: kept, one head's ``[E, width]`` rows would
    outweigh the layer."""
    return jax.checkpoint(adj.spmm_at)(alpha, z)


def _layer_apply(cfg: GNNConfig, p: dict, adj: Adjacency,
                 h_local: jnp.ndarray, n_inner: int, is_last: bool) -> jnp.ndarray:
    """One layer, its work under the two scopes of a layer
    (:data:`repro.obs.annotations.LAYER_PARTS`): ``aggregate``, the
    adjacency product with SAGE's degree normalisation and GAT's edge
    softmax (its own ``edge_softmax`` scope inside ``aggregate``), and
    ``transform``, the dense work and the activation.

    GAT (Velickovic et al. 2018): ``z = h W``, the edge softmax per head,
    each head's coefficient-weighted sum of source rows, then a bias per
    output column.  A hidden layer concatenates its heads, adds its input
    where the widths match (the identity skip) and applies ELU; the output
    layer averages its heads."""
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
        heads, width = p["a_src"].shape
        with device_scope("transform"):
            z = h_local @ p["w"]                      # [rows, heads * width]
        with device_scope("aggregate"):
            with device_scope("edge_softmax"):
                alpha = _edge_softmax(z, p["a_src"], p["a_dst"], adj)
            if is_last:
                # the narrow output heads gathered as one [rows, H*C] row
                agg = head_sum(adj, alpha, z)
            else:
                agg = jnp.concatenate(
                    [head_sum(adj, alpha[h], z[:, h * width:(h + 1) * width])
                     for h in range(heads)], axis=-1)
        with device_scope("transform"):
            if is_last:
                z = agg[:, :width]
                for h in range(1, heads):
                    z = z + agg[:, h * width:(h + 1) * width]
                z = z / heads + p["b"]
            else:
                z = agg + p["b"]
                if h_local.shape[1] == z.shape[1]:
                    z = z + h_local[:n_inner]         # identity skip
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
            z = jax.nn.elu(z) if cfg.model == "gat" else jax.nn.relu(z)
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
