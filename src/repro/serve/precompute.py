"""Partitioned layer-wise full-graph inference → per-layer embedding tables.

Offline half of the serving subsystem: run the trained model once over the
whole graph through the *same* partition-parallel machinery as training
(`ExchangePlan` tiers, `StackedParts` layout, any aggregation ``backend``),
and scatter every layer's stacked ``[P, NI, d]`` activations back to global
``[N, d]`` tables.  The online engine (`repro.serve.engine`) then answers
node queries by row lookup instead of neighbourhood aggregation — the
standard layer-wise inference trick (one full-graph pass costs the same as
a single refresh training step, then each query is O(1)).

``tables[l]`` holds the *input* of layer ``l`` for ``l < L`` (layer 0 = the
raw input features, layers ``1..L-1`` = post-activation hidden states) and
``tables[L]`` the final logits.  The intermediate layers are what the
engine's ``fresh=k`` mode consumes as frontier boundary values when it
recomputes a k-hop neighbourhood for updated nodes.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.dist.capgnn_sim import (_build_global, _glob_dict, _pull,
                                   _read_global, _scatter, _tier_dict,
                                   make_adj_builder, stacked_rows)
from repro.dist.exchange import ExchangePlan, StackedParts
from repro.graph.partition import PartitionSet
from repro.models.gnn import GNNConfig, _layer_apply

__all__ = ["EmbeddingStore", "precompute_embeddings", "save_store",
           "load_store"]

_META_NAME = "store_meta.json"


@dataclasses.dataclass
class EmbeddingStore:
    """Per-layer global embedding tables of one precompute pass.

    ``tables`` has ``num_layers + 1`` entries; entry ``l`` is ``[N, d_l]``
    with ``d_l = cfg.feat_dims[l]`` (input features, hidden states, logits).
    """
    cfg: GNNConfig
    backend: str
    tables: list[np.ndarray]

    @property
    def num_nodes(self) -> int:
        return int(self.tables[0].shape[0])

    @property
    def logits(self) -> np.ndarray:
        return self.tables[-1]

    @property
    def dims(self) -> list[int]:
        return [int(t.shape[1]) for t in self.tables]


def precompute_embeddings(cfg: GNNConfig, ps: PartitionSet, sp: StackedParts,
                          xplan: ExchangePlan, params,
                          backend: str = "edges") -> EmbeddingStore:
    """One fresh partition-parallel forward pass, keeping every layer.

    Numerically identical to ``SimRuntime.forward_fresh`` (same tier pulls,
    same block-diagonal stacked layer apply, same backend packs), so the
    final table equals the training runtime's fresh logits — asserted by
    the serving parity tests.
    """
    p, ni, nh = sp.num_parts, sp.n_inner_max, sp.n_halo_max
    layers = cfg.num_layers
    feats = jnp.asarray(sp.feats)
    halo_feats = jnp.asarray(sp.halo_feats)
    adj_leaves, build_adj = make_adj_builder(sp, backend, stacked=True)
    un_d = _tier_dict(xplan.uncached)
    loc_d = _tier_dict(xplan.local)
    glob_d = _glob_dict(xplan.glob)

    def layer_all(lp, h, halo, is_last, adj_lv):
        out = _layer_apply(cfg, lp, build_adj(adj_lv), stacked_rows(h, halo),
                           p * ni, is_last)
        return out.reshape(p, ni, -1)

    # the stacked inputs and tier programs are arguments: captured, they
    # would be baked into the executable as constants
    @jax.jit
    def run(params, feats, halo_feats, adj_lv, un_d, loc_d, glob_d):
        h = feats
        outs = [h]
        for li, lp in enumerate(params):
            if li == 0:
                halo = halo_feats
            else:
                d = h.shape[-1]
                halo = jnp.zeros((p, nh, d), h.dtype)
                halo = _scatter(halo, un_d["recv_halo_pos"], _pull(un_d, h),
                                un_d["recv_valid"])
                halo = _scatter(halo, loc_d["recv_halo_pos"], _pull(loc_d, h),
                                loc_d["recv_valid"])
                halo = _read_global(glob_d, _build_global(glob_d, h), halo)
            h = layer_all(lp, h, halo, (li == layers - 1), adj_lv)
            outs.append(h)
        return outs

    outs = [np.asarray(o) for o in run(params, feats, halo_feats, adj_leaves,
                                       un_d, loc_d, glob_d)]
    n = ps.graph.num_nodes
    tables = []
    for o in outs:
        table = np.zeros((n, o.shape[-1]), np.float32)
        for i, part in enumerate(ps.parts):
            table[part.inner_nodes] = o[i, : part.n_inner]
        tables.append(table)
    return EmbeddingStore(cfg=cfg, backend=backend, tables=tables)


# ---------------------------------------------------------------------------
# Persistence (rides on repro.checkpoint: atomic npz + json meta)
# ---------------------------------------------------------------------------

def save_store(store_dir: str, store: EmbeddingStore, step: int = 0) -> str:
    """Persist the tables via :mod:`repro.checkpoint` plus a meta sidecar
    describing the model config, so :func:`load_store` is self-contained."""
    path = save_checkpoint(store_dir, step, store.tables)
    meta = {"backend": store.backend,
            "num_nodes": store.num_nodes,
            "dims": store.dims,
            "cfg": dataclasses.asdict(store.cfg)}
    meta_path = os.path.join(store_dir, _META_NAME)
    with open(meta_path + ".tmp", "w") as f:
        json.dump(meta, f)
    os.replace(meta_path + ".tmp", meta_path)
    return path


def load_store(store_dir: str, step: int | None = None) -> EmbeddingStore:
    with open(os.path.join(store_dir, _META_NAME)) as f:
        meta = json.load(f)
    if step is None:
        step = latest_step(store_dir)
        if step is None:
            raise FileNotFoundError(f"no embedding checkpoint in {store_dir}")
    like = [np.zeros((meta["num_nodes"], d), np.float32)
            for d in meta["dims"]]
    tables = [np.asarray(t) for t in load_checkpoint(store_dir, step, like)]
    return EmbeddingStore(cfg=GNNConfig(**meta["cfg"]),
                          backend=meta["backend"], tables=tables)
