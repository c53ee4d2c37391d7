"""Single-device stacked oracle for the CaPGNN partition-parallel runtime.

Every partition's state lives in one padded ``[P, ...]`` array and the
per-worker computation is a ``vmap`` over the leading axis; the inter-worker
exchange is ordinary gather/scatter index arithmetic over the stacked inner
matrix.  Because the arithmetic is identical to what `capgnn_spmd` lowers
through ``shard_map`` collectives, this runtime doubles as the numerical
oracle for the SPMD parity tests — and, with ``refresh_every=1``, as an
exact reimplementation of single-worker full-graph training (the tier-1
correctness anchor).

Three step flavours (paper §4.2/§4.3):

- ``step_refresh``   — all three tiers pulled fresh; caches rewritten.
- ``step_cached``    — local/global tiers read stale from the caches; only
  the uncached tier is exchanged.  Caches unchanged.
- ``step_pipelined`` — same numerics as ``step_cached`` (consumes the same
  stale tiers) but *additionally* emits this step's fresh cache rows, the
  way the pipeline overlaps the refresh transfer with compute.  On the
  single-device oracle that is a numerics statement only; the SPMD
  runtime's ``transport="p2p"`` implements the overlap for real
  (double-buffered ``ppermute`` rings interleaved with the layer loop —
  see :mod:`repro.dist.capgnn_spmd`).

The jitted steps take the exchange index arrays as traced *arguments*
(a read plan and an emit plan — identical except on a plan-transition
step), so online cache adaptation (``SimRuntime.set_plan`` /
``step_transition`` with a capacity-padded slot-stable layout) swaps a
re-ranked plan into a running step without retracing.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.staleness import StalenessController
from repro.faults.guard import GuardConfig, TrainGuard
from repro.faults.plan import NULL_FAULTS
from repro.models.gnn import (EdgeListAdj, EllAdj, GNNConfig, HybridAdj,
                              _layer_apply, accuracy, cross_entropy_loss,
                              init_gnn)
from repro.obs.annotations import device_scope
from repro.obs.tracer import NULL_TRACER, StepCounters, device_peak_bytes
from repro.optim import Optimizer

from .exchange import ExchangePlan, ExchangeTier, GlobalTier, StackedParts
# halo_dtype_info moved to host_store (the staged h2d path casts with the
# same rules as the wire); re-exported here for backward compatibility
from .host_store import HostFeatureStore, halo_dtype_info
from .spec import TrainSpec, halo_dtype_name, warn_loose_kwargs

__all__ = ["make_sim_runtime", "SimRuntime", "init_caches", "train_capgnn",
           "TrainReport", "RUNTIME_BACKENDS", "check_backend",
           "make_adj_builder", "halo_dtype_info", "exchange_arrays",
           "RUNTIME_FEATURES"]

# where the input features live: stacked on device, or host-resident with
# per-step staged fetch of the non-locally-cached halo rows (out-of-core)
RUNTIME_FEATURES = ("device", "host")


# ---------------------------------------------------------------------------
# Tier primitives (shared by the property tests and both runtimes)
# ---------------------------------------------------------------------------

def _tier_dict(t: ExchangeTier) -> dict:
    return {
        "send_row": jnp.asarray(t.send_row, jnp.int32),
        "recv_src_part": jnp.asarray(t.recv_src_part, jnp.int32),
        "recv_src_slot": jnp.asarray(t.recv_src_slot, jnp.int32),
        "recv_halo_pos": jnp.asarray(t.recv_halo_pos, jnp.int32),
        "recv_valid": jnp.asarray(t.recv_valid),
    }


def _glob_dict(g: GlobalTier) -> dict:
    return {
        "send_row": jnp.asarray(g.send_row, jnp.int32),
        "src_part": jnp.asarray(g.src_part, jnp.int32),
        "src_slot": jnp.asarray(g.src_slot, jnp.int32),
        "read_pos": jnp.asarray(g.read_pos, jnp.int32),
        "read_buf_idx": jnp.asarray(g.read_buf_idx, jnp.int32),
        "read_valid": jnp.asarray(g.read_valid),
        "buf_valid": jnp.asarray(g.buf_valid),
    }


def exchange_arrays(xplan: ExchangePlan, include_host: bool = False) -> dict:
    """Device pytree of one plan's tier index arrays + valid masks.

    The jitted steps take this pytree as a *traced argument* (not a baked
    constant), so swapping in another plan's arrays — same shapes under a
    capacity-padded layout — re-plans the running step without retracing.
    ``include_host`` adds the layer-0 host-tier scatter program consumed
    by the ``features="host"`` runtimes.
    """
    out = {"un": _tier_dict(xplan.uncached),
           "loc": _tier_dict(xplan.local),
           "gl": _glob_dict(xplan.glob)}
    if include_host:
        if xplan.host is None:
            raise ValueError("features='host' needs a plan with a host "
                             "tier (rebuild via build_exchange_plan)")
        out["host"] = {"feat_pos": jnp.asarray(xplan.host.feat_pos,
                                               jnp.int32),
                       "feat_valid": jnp.asarray(xplan.host.feat_valid)}
    return out


def _pull(td: dict, h: jnp.ndarray, halo_dtype=None) -> jnp.ndarray:
    """Gather one tier's rows from the stacked inner matrix ``h [P,NI,d]``.

    Owners pack their send buffers, consumers address the payload by
    (src_part, src_slot).  Invalid (padding) rows are zeroed so they can be
    cached or compared without carrying garbage.  ``halo_dtype`` casts the
    packed payload before "transport" and dequantises the addressed rows
    back to ``h.dtype`` (the compressed-wire numerics the SPMD runtime
    applies for real).  Returns ``[P, R, d]``.
    """
    p = h.shape[0]
    payload = h[jnp.arange(p)[:, None], td["send_row"]]          # [P, S, d]
    if halo_dtype is not None:
        payload = payload.astype(halo_dtype)
    rows = payload[td["recv_src_part"], td["recv_src_slot"]]     # [P, R, d]
    rows = rows.astype(h.dtype)
    return jnp.where(td["recv_valid"][..., None], rows, 0.0)


def _scatter(halo: jnp.ndarray, pos: jnp.ndarray, rows: jnp.ndarray,
             valid: jnp.ndarray) -> jnp.ndarray:
    """Scatter tier rows into the halo buffer ``[P, NH, d]`` at ``pos``;
    invalid entries are routed out of bounds and dropped."""
    nh = halo.shape[1]
    pos_eff = jnp.where(valid, pos, nh)
    pidx = jnp.arange(halo.shape[0])[:, None]
    return halo.at[pidx, pos_eff].set(rows, mode="drop")


def _build_global(gd: dict, h: jnp.ndarray, halo_dtype=None) -> jnp.ndarray:
    """Fill the deduplicated global buffer ``[G, d]`` from owners' rows.
    The buffer is stored dequantised (compute dtype); with ``halo_dtype``
    the owners' payload is cast before transport, so the buffer carries
    exactly the rows a compressed wire delivers.  Capacity-padding slots
    (``buf_valid`` false) are zeroed so caches/drift stats never carry
    garbage."""
    p = h.shape[0]
    payload = h[jnp.arange(p)[:, None], gd["send_row"]]          # [P, S, d]
    if halo_dtype is not None:
        payload = payload.astype(halo_dtype)
    rows = payload[gd["src_part"], gd["src_slot"]].astype(h.dtype)  # [G, d]
    if "buf_valid" in gd:
        rows = jnp.where(gd["buf_valid"][:, None], rows, 0.0)
    return rows


def _read_global(gd: dict, buf: jnp.ndarray, halo: jnp.ndarray) -> jnp.ndarray:
    """Serve each worker's global-tier halo positions from the buffer."""
    rows = buf[gd["read_buf_idx"]]                               # [P, RG, d]
    return _scatter(halo, gd["read_pos"], rows, gd["read_valid"])


# ---------------------------------------------------------------------------
# Aggregation backends (shared with the SPMD runtime)
# ---------------------------------------------------------------------------

RUNTIME_BACKENDS = ("edges", "ell", "hybrid")


def check_backend(sp: StackedParts, backend: str) -> None:
    """Validate a runtime backend choice against the stacked layout."""
    if backend not in RUNTIME_BACKENDS:
        raise ValueError(f"unknown aggregation backend {backend!r}; "
                         f"expected one of {RUNTIME_BACKENDS}")
    if backend != "edges" and (sp.ell is None or sp.ell.backend != backend):
        have = sp.ell.backend if sp.ell is not None else None
        raise ValueError(
            f"backend={backend!r} needs a matching stacked aggregation pack "
            f"(found {have!r}); rebuild the stacked layout with "
            f"stack_partitions(ps, task, backend={backend!r})")


def make_adj_builder(sp: StackedParts, backend: str, stacked: bool = False):
    """Return ``(pack_leaves, build)``: ``build(pack_leaves)`` constructs
    the :class:`~repro.models.gnn.Adjacency` (inside a trace, where the
    leaves arrive as arguments).

    By default ``pack_leaves`` holds per-partition ``[P, ...]`` arrays to
    shard over a mesh, and ``build`` takes one partition's slices.  With
    ``stacked=True`` the P partitions form one block-diagonal adjacency
    over the flattened stacked rows — ``P*NI`` inner rows, then ``P*NH``
    halo rows (:func:`stacked_rows`) — so a device holding every
    partition runs one unbatched SpMM: a vmapped segment-sum lowers to a
    batched scatter whose TPU compile time grows with the edge count.

    Every backend aggregates over the identical edge set (the packs are
    built from the same remapped edge lists at stack time), so swapping the
    backend changes kernel shape only — logits, gradients, and the exchange
    byte accounting are backend-invariant.
    """
    check_backend(sp, backend)
    p, ni, nh = sp.num_parts, sp.n_inner_max, sp.n_halo_max
    n_rows, n_cols = (p * ni, p * (ni + nh)) if stacked else (ni, ni + nh)

    def cols(c):
        if not stacked:
            return jnp.asarray(c)
        part = np.arange(p).reshape((p,) + (1,) * (c.ndim - 1))
        flat = np.where(c < ni, part * ni + c, p * ni + part * nh + c - ni)
        return jnp.asarray(flat.reshape((-1,) + c.shape[2:]), jnp.int32)

    def rows(r):
        # padding rows (== NI) stay out of range, so the scatter drops them
        if not stacked:
            return jnp.asarray(r)
        part = np.arange(p)[:, None]
        return jnp.asarray(np.where(r < ni, part * ni + r, n_rows).reshape(-1),
                           jnp.int32)

    def vals(v):
        v = np.asarray(v)
        return jnp.asarray(v.reshape((-1,) + v.shape[2:]) if stacked else v)

    if backend == "edges":
        leaves = {"src": cols(sp.e_src), "dst": rows(sp.e_dst),
                  "w": vals(sp.e_w)}

        def build(lv):
            return EdgeListAdj(lv["src"], lv["dst"], lv["w"], n_rows, n_cols)
    elif backend == "ell":
        leaves = {"cols": cols(sp.ell.cols), "vals": vals(sp.ell.vals)}

        def build(lv):
            return EllAdj(lv["cols"], lv["vals"], n_cols)
    else:  # hybrid
        leaves = {"cols": cols(sp.ell.cols), "vals": vals(sp.ell.vals),
                  "tail_src": cols(sp.ell.tail_src),
                  "tail_dst": rows(sp.ell.tail_dst),
                  "tail_w": vals(sp.ell.tail_w)}

        def build(lv):
            return HybridAdj(lv["cols"], lv["vals"], lv["tail_src"],
                             lv["tail_dst"], lv["tail_w"], n_cols)
    return leaves, build


def stacked_rows(h: jnp.ndarray, halo: jnp.ndarray) -> jnp.ndarray:
    """``[P, NI, d]`` inner + ``[P, NH, d]`` halo -> the ``[P*NI + P*NH,
    d]`` row space of a ``stacked=True`` adjacency."""
    d = h.shape[-1]
    return jnp.concatenate([h.reshape(-1, d), halo.reshape(-1, d)], axis=0)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_caches(cfg: GNNConfig, xplan: ExchangePlan, num_parts: int,
                features: str = "device") -> dict:
    """Zero-filled stale tiers, one entry per cached exchange layer.

    Entry ``l-1`` holds the halo inputs of layer ``l`` (layers ``1..L-1``);
    layer 0 consumes the static input features, which never go stale.

    With ``features="host"`` the global tier is *host-resident* (it lives
    in the runtime's :class:`~repro.dist.host_store.HostFeatureStore` and
    is staged per step), so the device cache pytree carries only the
    local tier.
    """
    dims = cfg.feat_dims[1: cfg.num_layers]
    r_local = int(np.asarray(xplan.local.recv_halo_pos).shape[1])
    g = xplan.glob.buf_size
    return {
        "local": [jnp.zeros((num_parts, r_local, d), jnp.float32)
                  for d in dims],
        "global": ([] if features == "host" else
                   [jnp.zeros((g, d), jnp.float32) for d in dims]),
    }


# ---------------------------------------------------------------------------
# Runtime
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SimRuntime:
    cfg: GNNConfig
    xplan: ExchangePlan
    comm_dims: list        # per-exchange-layer feature dims (byte accounting)
    forward_fresh: Callable
    step_refresh: Callable
    step_cached: Callable
    step_pipelined: Callable
    evaluate: Callable
    caches0: dict
    backend: str = "edges"
    halo_dtype_bytes: int = 4   # actual wire width per halo payload entry
    # feature residency: "device" (stacked on device) or "host"
    # (out-of-core: host store + per-step staged fetch)
    features: str = "device"
    host_store: HostFeatureStore | None = dataclasses.field(default=None,
                                                            repr=False)
    # online adaptation plumbing: the jitted step impls take the exchange
    # arrays of the (read, emit) plans as traced arguments; `_state` holds
    # the currently-installed plan's arrays.
    jit_steps: dict | None = dataclasses.field(default=None, repr=False)
    _state: dict | None = dataclasses.field(default=None, repr=False)
    # the stacked layout this runtime was built over — kept for padded-row
    # accounting under uneven (resource-aware) partitions
    stacked: StackedParts | None = dataclasses.field(default=None, repr=False)
    # the TrainSpec this runtime was configured from (always set — the
    # loose-kwarg shim synthesises one), recorded into TrainReport.spec
    spec: TrainSpec | None = dataclasses.field(default=None, repr=False)
    # the stacked inputs every jitted step takes as its last argument
    data: dict | None = dataclasses.field(default=None, repr=False)

    def padding_stats(self) -> dict:
        """Valid vs padded stacked-row counts (see
        :meth:`repro.dist.StackedParts.padding_stats`)."""
        return self.stacked.padding_stats() if self.stacked else {}

    def set_tracer(self, tracer) -> None:
        """Attach a :class:`repro.obs.Tracer`: the plain-Python stepper
        wrappers record their staging sub-spans (``l0_stage``,
        ``h2d_prefetch``, ``writeback``) on it and the host store its
        ``h2d_put`` dispatches.  Default is the shared no-op tracer —
        detaching is ``set_tracer(NULL_TRACER)``."""
        if self._state is not None:
            self._state["tracer"] = tracer
        if self.host_store is not None:
            self.host_store.set_tracer(tracer)

    def set_fault_guard(self, guard) -> None:
        """Attach a :class:`repro.faults.FetchGuard`: the host-mode
        staging wrappers route through its retry/degrade/stale-reuse
        paths.  ``None`` (the default) keeps the original unguarded
        staging code byte-for-byte.  No-op in device-feature mode."""
        if self._state is not None:
            self._state["fetch_guard"] = guard
            if guard is not None and "l0loc" in self._state:
                # the resident layer-0 local rows are the natural stale
                # fallback for a failed re-stage at the next plan install
                guard.last_good.setdefault("l0loc", self._state["l0loc"])

    def set_plan(self, xplan: ExchangePlan) -> None:
        """Install a re-ranked plan.  Under a capacity-padded (slot-stable)
        layout the jitted steps keep their compiled executables — only the
        index data changes.  The caches' *content* still reflects the old
        tiering, so the next step must be a refresh (or have been emitted
        by :meth:`step_transition`).  In ``features="host"`` mode this
        also flushes the staged-fetch ring and restages the layer-0 local
        cache for the new plan."""
        self.xplan = xplan
        hook = (self._state or {}).get("_set_plan")
        if hook is not None:
            hook(xplan)
        else:
            self._state["xarr"] = exchange_arrays(xplan)

    def step_transition(self, params, opt_state, caches,
                        new_xplan: ExchangePlan):
        """Pipelined plan switch: consume the *current* plan's stale tiers
        (and its uncached exchange) while prefetching the **new** plan's
        tier rows in the refresh windows; the emitted caches are laid out
        for ``new_xplan``, which becomes the installed plan.  In host
        mode the stale global tier is staged on the *old* plan's layout,
        the emitted buffers are written back under the new plan's
        membership, and the layer-0 staging ring is flushed (its
        prefetches carry old-plan rows — they are discarded unaccounted,
        never served)."""
        hook = (self._state or {}).get("_transition")
        if hook is not None:
            out = hook(params, opt_state, caches, new_xplan)
        else:
            xe = exchange_arrays(new_xplan)
            out = self.jit_steps["pipelined"](params, opt_state, caches,
                                              self._state["xarr"], xe,
                                              self.data)
            self._state["xarr"] = xe
        self.xplan = new_xplan
        return out

    def lower_step(self, name: str, params, opt_state, caches):
        """Lower one jitted step flavour (``"refresh" | "cached" |
        "pipelined"``) with the installed plan's exchange arrays — for HLO
        inspection/cost tooling."""
        xa = self._state["xarr"]
        if self.features == "host":
            hd = self._state["_dummy_hostd"](name)
            return self.jit_steps[name].lower(params, opt_state, caches,
                                              hd, self._state["l0loc"],
                                              xa, xa, self.data)
        return self.jit_steps[name].lower(params, opt_state, caches, xa, xa,
                                          self.data)


def make_sim_runtime(cfg: GNNConfig, sp: StackedParts, xplan: ExchangePlan,
                     opt: Optimizer, exchange_layer0: bool = True,
                     backend: str = "edges", halo_dtype=None,
                     donate: bool = True,
                     features: str = "device",
                     host_store: HostFeatureStore | None = None,
                     prefetch_depth: int = 2,
                     spec: TrainSpec | None = None) -> SimRuntime:
    """Build the jitted stacked-oracle runtime.

    ``spec`` (a :class:`repro.dist.TrainSpec`) is the configuration
    surface; when passed it overrides every loose configuration kwarg
    below.  The loose kwargs remain as a deprecated shim that forwards
    into a synthesised spec (one ``DeprecationWarning`` per call — see
    the README migration note).  ``host_store`` stays a real argument
    either way: it is a resource, not a choice.

    ``exchange_layer0=False`` models pre-replicated input features (they are
    static, so a deployment ships them once): layer 0 drops out of the byte
    accounting, while the numerics are unchanged.

    ``backend`` picks the per-partition aggregation operator: ``"edges"``
    (segment-sum reference), ``"ell"`` (Pallas blocked-ELL SpMM) or
    ``"hybrid"`` (Pallas ELL + COO overflow tail).  The non-edge backends
    need the stacked pack from ``stack_partitions(..., backend=...)``; the
    exchange plan, caches and byte accounting are backend-invariant.

    ``halo_dtype="bf16"`` casts every tier's payload before the exchange
    and dequantises on scatter, halving the accounted wire bytes
    (``halo_dtype_bytes`` is threaded into ``train_capgnn``'s accounting).
    In host mode the same cast compresses the PCIe staging payloads.

    ``donate=True`` (default) donates ``(params, opt_state, caches)`` into
    the jitted steps, so the optimizer and cache buffers are updated
    in place in steady state instead of being copied.  Callers must then
    treat the arguments of a step call as consumed — re-use the *returned*
    state (pass ``donate=False`` for branch-and-compare experiments that
    deliberately re-run a step from the same state).

    ``features="host"`` is the out-of-core mode: the halo feature table
    never lives on device.  Layer 0's local-tier rows are staged once per
    plan (``l0loc``, the genuinely device-cached JACA local tier); the
    uncached+global layer-0 rows ride a double-buffered
    :class:`~repro.dist.host_store.HostFeatureStore` staging ring whose
    next fetch is ``device_put``-in-flight while the current step runs;
    the per-exchange-layer global buffers live host-side between steps
    (written back on refresh, staged h2d for the stale reads).  The plan
    must carry a host tier (``build_exchange_plan`` always emits one).
    ``host_store`` injects a pre-built store (shared with a serve engine);
    by default one is built over ``sp.halo_feats``.
    """
    if spec is None:
        warn_loose_kwargs("make_sim_runtime")
        spec = TrainSpec(strategy="halo_1d", backend=backend,
                         features=features,
                         halo_dtype=halo_dtype_name(halo_dtype),
                         exchange_layer0=exchange_layer0, donate=donate,
                         prefetch_depth=prefetch_depth)
    # the spec is authoritative from here on — identical construction for
    # both entry paths (the shim-equivalence tests pin this)
    exchange_layer0 = spec.exchange_layer0
    backend = spec.backend
    halo_dtype = spec.halo_dtype
    donate = spec.donate
    features = spec.features
    prefetch_depth = spec.prefetch_depth
    p, ni, nh = sp.num_parts, sp.n_inner_max, sp.n_halo_max
    hdt, hd_bytes = halo_dtype_info(halo_dtype)
    layers = cfg.num_layers
    if features not in RUNTIME_FEATURES:
        raise ValueError(f"unknown features mode {features!r}; "
                         f"expected one of {RUNTIME_FEATURES}")
    host_mode = features == "host"

    feats = jnp.asarray(sp.feats)
    if host_mode:
        store = host_store if host_store is not None else HostFeatureStore(
            sp.halo_feats, halo_dtype=halo_dtype,
            prefetch_depth=prefetch_depth)
        halo_feats = None      # the halo table never touches device memory
    else:
        store = None
        halo_feats = jnp.asarray(sp.halo_feats)
    labels = jnp.asarray(sp.labels).reshape(-1)
    masks = {k: jnp.asarray(m).reshape(-1)
             for k, m in (("train", sp.train_mask), ("val", sp.val_mask),
                          ("test", sp.test_mask))}
    adj_leaves, build_adj = make_adj_builder(sp, backend, stacked=True)
    # the stacked inputs travel into every jitted function as an argument:
    # captured, they would be baked into each executable as constants
    data = {"feats": feats, "labels": labels, "masks": masks,
            "adj": adj_leaves}
    if not host_mode:
        data["halo_feats"] = halo_feats

    def layer_all(lp, h, halo, is_last, adj_lv):
        out = _layer_apply(cfg, lp, build_adj(adj_lv),
                           stacked_rows(h, halo), p * ni, is_last)
        return out.reshape(p, ni, -1)

    def forward(params, caches, xr, xe, use_stale: bool, data,
                hostd=None, l0loc=None):
        """``xr`` is the installed (read) plan: stale caches are scattered
        at its positions and its uncached tier is exchanged.  ``xe`` is the
        emit plan whose tier rows are pulled fresh — identical to ``xr``
        except on a plan-transition step, where the fresh pulls prefetch
        the *next* plan's rows.

        In host mode the layer-0 halo is assembled on device from two
        staged payloads instead of a resident table: ``l0loc`` (the
        per-plan device-cached local tier) scattered at the local tier's
        positions, and ``hostd["l0"]`` (this step's double-buffered host
        fetch) scattered at the host tier's positions (uncached ∪ global
        membership).  Stale global reads come from ``hostd["gl"]`` — the
        staged host-resident buffers — rather than a device cache."""
        h = data["feats"]
        fresh = {"local": [], "global": []}
        for li, lp in enumerate(params):
            if li == 0:
                if host_mode:
                    with device_scope("cache_read"):
                        halo = jnp.zeros((p, nh, h.shape[-1]), h.dtype)
                        halo = _scatter(halo, xr["loc"]["recv_halo_pos"],
                                        l0loc.astype(h.dtype),
                                        xr["loc"]["recv_valid"])
                        halo = _scatter(halo, xr["host"]["feat_pos"],
                                        hostd["l0"].astype(h.dtype),
                                        xr["host"]["feat_valid"])
                else:
                    halo = data["halo_feats"]
            else:
                d = h.shape[-1]
                with device_scope("cache_read"):
                    halo = jnp.zeros((p, nh, d), h.dtype)
                with device_scope("tier_pull_uncached"):
                    halo = _scatter(halo, xr["un"]["recv_halo_pos"],
                                    _pull(xr["un"], h, hdt),
                                    xr["un"]["recv_valid"])
                with device_scope("tier_pull_refresh"):
                    loc_fresh = _pull(xe["loc"], h, hdt)
                    buf_fresh = _build_global(xe["gl"], h, hdt)
                with device_scope("cache_read"):
                    if use_stale:
                        loc_use, loc_t = caches["local"][li - 1], xr["loc"]
                        if host_mode:
                            buf_use = hostd["gl"][li - 1].astype(h.dtype)
                        else:
                            buf_use = caches["global"][li - 1]
                        gl_t = xr["gl"]
                    else:
                        loc_use, loc_t = loc_fresh, xe["loc"]
                        buf_use, gl_t = buf_fresh, xe["gl"]
                    halo = _scatter(halo, loc_t["recv_halo_pos"], loc_use,
                                    loc_t["recv_valid"])
                    halo = _read_global(gl_t, buf_use, halo)
                fresh["local"].append(loc_fresh)
                fresh["global"].append(buf_fresh)
            with device_scope(f"layer{li}"):
                h = layer_all(lp, h, halo, (li == layers - 1), data["adj"])
        return h, fresh

    def loss_fn(params, caches, xr, xe, use_stale: bool, data,
                hostd=None, l0loc=None):
        logits, fresh = forward(params, caches, xr, xe, use_stale, data,
                                hostd, l0loc)
        with device_scope("loss"):
            flat = logits.reshape(-1, logits.shape[-1])
            loss = cross_entropy_loss(flat, data["labels"],
                                      data["masks"]["train"])
        return loss, (flat, fresh)

    def _metrics_and_caches(loss, flat, fresh, caches, stale_gl,
                            use_stale: bool, emit_fresh: bool, data):
        with device_scope("loss"):
            metrics = {"loss": loss,
                       "acc": accuracy(flat, data["labels"],
                                       data["masks"]["train"])}
        with device_scope("cache_update"):
            # Drift compares fresh rows against the stale source of this step.
            # In host mode that source is the staged host buffer (``stale_gl``
            # from hostd) — on a host *refresh* there is no staged stale
            # global at all, so the drift keys are simply not emitted.
            if emit_fresh and (use_stale or not host_mode):
                pairs = list(zip(fresh["local"] + fresh["global"],
                                 caches["local"] + stale_gl))
                drifts = [jnp.max(jnp.abs(a - b)) for a, b in pairs
                          if a.size]
                metrics["drift"] = (jnp.max(jnp.stack(drifts)) if drifts
                                    else jnp.zeros(()))
                # per-row drift stats for the drift-aware planner policy
                # (max over layers and feature dim; meaningful when xr == xe)
                n_ex = len(fresh["local"])
                if n_ex:
                    loc_rows = [jnp.max(jnp.abs(a - b), axis=-1)
                                for a, b in pairs[:n_ex]]
                    gl_rows = [jnp.max(jnp.abs(a - b), axis=-1)
                               for a, b in pairs[n_ex:]]
                    metrics["drift_local_rows"] = jnp.max(
                        jnp.stack(loc_rows), axis=0)          # [P, Rloc]
                    metrics["drift_global_rows"] = jnp.max(
                        jnp.stack(gl_rows), axis=0)           # [G]
        if host_mode:
            out_caches = {"local": (fresh["local"] if emit_fresh
                                    else caches["local"]),
                          "global": []}
        else:
            out_caches = fresh if emit_fresh else caches
        return metrics, out_caches

    def make_step(use_stale: bool, emit_fresh: bool):
        if host_mode:
            def step(params, opt_state, caches, hostd, l0loc, xr, xe, data):
                (loss, (flat, fresh)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, caches, xr, xe,
                                           use_stale, data, hostd, l0loc)
                with device_scope("optimizer"):
                    new_params, new_state = opt.update(grads, opt_state,
                                                       params)
                with device_scope("cache_update"):
                    stale_gl = ([g.astype(jnp.float32) for g in hostd["gl"]]
                                if use_stale else [])
                metrics, out_caches = _metrics_and_caches(
                    loss, flat, fresh, caches, stale_gl,
                    use_stale, emit_fresh, data)
                if emit_fresh:
                    # emitted global buffers go back to the host store
                    # (d2h writeback by the caller), not into device caches
                    return (new_params, new_state, out_caches,
                            fresh["global"], metrics)
                return new_params, new_state, out_caches, metrics
            # the staged hostd payloads are single-use but their shapes
            # never match a step output, so donating them would only warn;
            # their buffers free when the wrapper drops the last reference
            return jax.jit(step,
                           donate_argnums=(0, 1, 2) if donate else ())

        def step(params, opt_state, caches, xr, xe, data):
            (loss, (flat, fresh)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, caches, xr, xe, use_stale,
                                       data)
            with device_scope("optimizer"):
                new_params, new_state = opt.update(grads, opt_state, params)
            metrics, out_caches = _metrics_and_caches(
                loss, flat, fresh, caches, caches["global"],
                use_stale, emit_fresh, data)
            return new_params, new_state, out_caches, metrics
        # steady-state steps rewrite (params, opt_state, caches) in place;
        # the exchange arrays (xr, xe) are NOT donated — they are reused
        # across steps and swapped wholesale by set_plan/step_transition
        return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())

    caches0 = init_caches(cfg, xplan, p, features=features)

    if host_mode:
        def _fwd_fresh(params, hostd, l0loc, xr, data):
            logits, _ = forward(params, caches0, xr, xr, False, data,
                                hostd, l0loc)
            return logits
    else:
        def _fwd_fresh(params, xr, data):
            logits, _ = forward(params, caches0, xr, xr, False, data)
            return logits

    jit_steps = {"refresh": make_step(False, True),
                 "cached": make_step(True, False),
                 "pipelined": make_step(True, True),
                 "forward": jax.jit(_fwd_fresh)}
    state = {"xarr": exchange_arrays(xplan, include_host=host_mode),
             "tracer": NULL_TRACER}

    def wrap(name):
        def stepper(params, opt_state, caches):
            xa = state["xarr"]
            return jit_steps[name](params, opt_state, caches, xa, xa, data)
        return stepper

    if host_mode:
        n_ex = layers - 1
        ex_dims = list(cfg.feat_dims[1:layers])
        parts_idx = np.arange(p)[:, None]
        staged_dtype = hdt if hdt is not None else jnp.float32

        def _host_np(xp: ExchangePlan) -> dict:
            """Host-side gather programs of one plan (plain numpy — these
            index the host table, they never ride into the jitted step)."""
            return {"feat_pos": np.asarray(xp.host.feat_pos, np.int64),
                    "feat_valid": np.asarray(xp.host.feat_valid, bool),
                    "loc_pos": np.asarray(xp.local.recv_halo_pos, np.int64),
                    "loc_valid": np.asarray(xp.local.recv_valid, bool),
                    "gl_rows": int(xp.glob.n_unique)}

        def _stage_l0loc():
            """(Re)stage the layer-0 local-tier rows — the device-cached
            slice of the host table.  One accounted fetch per plan install,
            then resident until the next re-plan."""
            hn = state["hostnp"]

            def stage():
                return store.stage_rows((parts_idx, hn["loc_pos"]),
                                        valid=hn["loc_valid"])
            g = state.get("fetch_guard")
            if g is None:
                sf = stage()
                store.account_fetch(sf)
                state["l0loc"] = sf.array
            else:
                state["l0loc"] = g.fetch_sync(stage, store, "l0loc")

        def _stage_l0():
            hn = state["hostnp"]
            return store.stage_rows((parts_idx, hn["feat_pos"]),
                                    valid=hn["feat_valid"])

        def _take_l0():
            """Pop the oldest in-flight layer-0 fetch (or stage one cold)
            and account it — accounting happens at consumption, so flushed
            prefetches never count.  With a fault guard attached the cold
            path retries with backoff and past the budget serves the
            previous step's rows (stale reuse)."""
            ring = state["l0_ring"]
            g = state.get("fetch_guard")
            if g is None:
                sf = ring.popleft() if ring else _stage_l0()
                store.account_fetch(sf)
                return sf.array
            if ring:
                return g.consume(ring.popleft(), store, "l0")
            return g.fetch_sync(_stage_l0, store, "l0")

        def _prefetch_l0():
            """Refill the double buffer: keep the *next* step's host rows
            ``device_put``-in-flight while the current step computes.
            Under an active fault guard a failed or slow fetch suspends
            the refill — consumption degrades to synchronous staging."""
            ring = state["l0_ring"]
            g = state.get("fetch_guard")
            if g is not None and not g.prefetch_ok():
                return
            while len(ring) < max(1, store.prefetch_depth - 1):
                if g is None:
                    ring.append(_stage_l0())
                else:
                    sf = g.try_stage(_stage_l0)
                    if sf is None:
                        return
                    ring.append(sf)

        def _take_gl():
            g = state.get("fetch_guard")
            out = []
            for li in range(n_ex):
                if g is None:
                    sf = store.stage_buf(li)
                    store.account_fetch(sf)
                    out.append(sf.array)
                else:
                    out.append(g.fetch_sync(
                        lambda li=li: store.stage_buf(li), store, f"gl{li}"))
            return out

        def _writeback(host_out):
            for li, buf in enumerate(host_out):
                store.write_buf(li, buf, state["hostnp"]["gl_rows"])

        state["hostnp"] = _host_np(xplan)
        state["l0_ring"] = deque()
        _stage_l0loc()
        for li, d in enumerate(ex_dims):
            store.init_buf(li, (xplan.glob.buf_size, d),
                           xplan.glob.n_unique)

        def wrap_host(name):
            use_gl = name in ("cached", "pipelined")
            emit = name in ("refresh", "pipelined")

            def stepper(params, opt_state, caches):
                tr = state["tracer"]
                with tr.span("l0_stage"):
                    hostd = {"l0": _take_l0()}
                    if use_gl:
                        hostd["gl"] = _take_gl()
                xa = state["xarr"]
                out = jit_steps[name](params, opt_state, caches, hostd,
                                      state["l0loc"], xa, xa, data)
                if emit:
                    new_p, new_s, out_caches, host_out, metrics = out
                    with tr.span("writeback"):
                        _writeback(host_out)
                    out = (new_p, new_s, out_caches, metrics)
                with tr.span("h2d_prefetch"):
                    _prefetch_l0()
                return out
            return stepper

        def _set_plan(xp: ExchangePlan):
            tr = state["tracer"]
            state["xarr"] = exchange_arrays(xp, include_host=True)
            state["hostnp"] = _host_np(xp)
            # old-plan prefetches are flushed *unaccounted* — they were
            # never consumed, so staged == consumed stays exact
            state["l0_ring"].clear()
            with tr.span("l0_stage"):
                _stage_l0loc()
            with tr.span("h2d_prefetch"):
                _prefetch_l0()
            # the host-resident global buffers keep their (old-tiering)
            # content; shapes are plan-invariant under the capacity-padded
            # layout and the next step after set_plan must be a refresh
        state["_set_plan"] = _set_plan

        def _transition(params, opt_state, caches, new_xp: ExchangePlan):
            tr = state["tracer"]
            # old plan's stale tiers are staged on the OLD layout...
            with tr.span("l0_stage"):
                hostd = {"l0": _take_l0(), "gl": _take_gl()}
            xr = state["xarr"]
            xe = exchange_arrays(new_xp, include_host=True)
            new_p, new_s, out_caches, host_out, metrics = (
                jit_steps["pipelined"](params, opt_state, caches, hostd,
                                       state["l0loc"], xr, xe, data))
            state["xarr"] = xe
            state["hostnp"] = _host_np(new_xp)
            # ...while the emitted buffers carry the NEW plan's membership
            with tr.span("writeback"):
                _writeback(host_out)
            state["l0_ring"].clear()
            with tr.span("l0_stage"):
                _stage_l0loc()
            with tr.span("h2d_prefetch"):
                _prefetch_l0()
            return new_p, new_s, out_caches, metrics
        state["_transition"] = _transition

        def _dummy_hostd(name: str) -> dict:
            """Zero payloads with the staged shapes/dtypes — for
            ``lower_step`` HLO inspection only."""
            w = state["hostnp"]["feat_pos"].shape[1]
            hd = {"l0": jnp.zeros((p, w, cfg.feat_dims[0]), staged_dtype)}
            if name in ("cached", "pipelined"):
                hd["gl"] = [jnp.zeros((xplan.glob.buf_size, d),
                                      staged_dtype) for d in ex_dims]
            return hd
        state["_dummy_hostd"] = _dummy_hostd

        def forward_fresh(params):
            sf = _stage_l0()
            store.account_fetch(sf)
            return jit_steps["forward"](params, {"l0": sf.array},
                                        state["l0loc"], state["xarr"], data)

        step_wrap = wrap_host
        _prefetch_l0()
    else:
        def forward_fresh(params):
            return jit_steps["forward"](params, state["xarr"], data)

        step_wrap = wrap

    def evaluate(params, split: str = "val"):
        flat = forward_fresh(params).reshape(-1, cfg.out_dim)
        m = masks[split]
        return (float(cross_entropy_loss(flat, labels, m)),
                float(accuracy(flat, labels, m)))

    comm_dims = list(cfg.feat_dims[:layers])
    if not exchange_layer0 or host_mode:
        # host mode: layer-0 rows arrive over PCIe from the host store
        # (accounted by the store), not over the inter-worker wire
        comm_dims = comm_dims[1:]

    return SimRuntime(cfg=cfg, xplan=xplan, comm_dims=comm_dims,
                      forward_fresh=forward_fresh,
                      step_refresh=step_wrap("refresh"),
                      step_cached=step_wrap("cached"),
                      step_pipelined=step_wrap("pipelined"),
                      evaluate=evaluate,
                      caches0=caches0, backend=backend,
                      halo_dtype_bytes=hd_bytes,
                      features=features, host_store=store,
                      jit_steps=jit_steps, _state=state, stacked=sp,
                      spec=spec, data=data)


# ---------------------------------------------------------------------------
# Training loop with exact byte accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainReport:
    losses: list
    val_acc: list
    comm_bytes: int
    comm_bytes_vanilla: int
    comm_reduction: float
    refresh_steps: int
    cached_steps: int
    wall_time_s: float
    replan_events: int = 0
    hit_rate: float | None = None    # planner-observed (adaptive runs only)
    final_opt_state: object = None   # for checkpoint/resume (launch.train)
    # out-of-core (features="host") traffic over the training loop, from
    # the store's consumption-driven counters; zero in device mode
    host_fetch_rows: int = 0
    host_fetch_bytes: int = 0
    host_writeback_bytes: int = 0
    # step 0 wall time (dominated by jit trace+compile), fenced separately
    # so ``wall_time_s`` above is steady-state only
    compile_s: float = 0.0
    # per step-kind {count, p50_ms, p99_ms, total_s} from the tracer's
    # depth-0 spans; None on untraced runs (timing them would add syncs)
    phase_stats: dict | None = None
    # fault-injection accounting (repro.faults): per-kind injected event
    # counts and the run's DefenseEvents totals; None on clean runs.
    # The fault-tolerance suite asserts the matched pairs are EQUAL —
    # fetch_drop==fetch_errors, fetch_delay==slow_fetches,
    # halo_corrupt==corruptions_detected, grad_nan==rollbacks,
    # mem_pressure==mem_backoffs.
    faults_injected: dict | None = None
    fault_events: dict | None = None
    # the serialised TrainSpec (spec.to_dict()) this run was configured
    # from, so every experiments/*.json records its exact configuration
    spec: dict | None = None


def _step_rows(x_read: ExchangePlan, x_emit: ExchangePlan,
               refresh: bool) -> int:
    """Exact per-layer wire rows of one step: the *read* plan's uncached
    tier moves every step; on a refresh the *emit* plan's cached tiers are
    (pre)fetched.  ``x_read is x_emit`` except on a plan-transition step."""
    n = x_read.uncached.n_rows
    if refresh:
        n += x_emit.local.n_rows + x_emit.glob.n_unique
    return n


def train_capgnn(cfg: GNNConfig, runtime, xplan: ExchangePlan,
                 num_parts: int, opt: Optimizer, epochs: int = 100,
                 eval_every: int = 0, controller: StalenessController | None = None,
                 pipeline: bool = False, seed: int = 0,
                 params0=None, opt_state0=None, planner=None,
                 tracer=None, faults=None, guard=None,
                 spec: TrainSpec | None = None) -> tuple[list, TrainReport]:
    """Full-batch CaPGNN training under the staleness schedule.

    ``spec`` (a :class:`repro.dist.TrainSpec`) supplies ``pipeline`` and
    ``seed`` and is recorded (serialised) into ``report.spec``; the loose
    ``pipeline``/``seed`` kwargs remain as a deprecated shim that forwards
    into a spec derived from the runtime's (one ``DeprecationWarning``).
    Object-valued collaborators (controller, planner, tracer, faults,
    guard, resume state) are resources, not spec fields — they stay
    explicit arguments on both paths.

    One step per epoch (full batch).  Per-step bytes are the plan's exact
    figures: a vanilla runtime would move every halo row at every layer of
    every step; CaPGNN moves only the uncached tier on cached steps and a
    deduplicated refresh on refresh steps.  With ``pipeline=True`` the
    scheduled refreshes (after warm-up) run as ``step_pipelined`` — the
    refresh payload rides along with the compute instead of a synchronous
    exchange phase; bytes are identical, latency is hidden.

    ``tracer`` (a :class:`repro.obs.Tracer`) records one depth-0 span per
    step — kind ``refresh``/``cached``/``pipelined``/``transition``, with
    the ``replan``/``l0_stage``/``writeback``/``h2d_prefetch``/``eval``
    sub-phases nested inside — plus one typed
    :class:`repro.obs.StepCounters` record per step whose totals equal
    this report's ``comm_bytes`` / ``host_fetch_*`` figures exactly (the
    per-step stream is the same accounting, before summation).  Traced
    steps are fenced (``block_until_ready``) so span durations measure
    completed device work; without a tracer no fence is added.

    Timing: step 0 is fenced separately — ``report.compile_s`` is the
    first step's wall time (dominated by jit trace+compile) and
    ``wall_time_s`` covers the remaining steady-state steps only, so
    throughput figures no longer conflate compilation with step time.

    ``planner`` (a :class:`repro.core.jaca.AdaptivePlanner`) switches on
    online cache adaptation: at the controller's re-plan boundaries
    (refresh steps, thinned by ``controller.replan_every``) the planner's
    live eviction state is materialised into a new plan and swapped into
    the runtime — via :meth:`~SimRuntime.step_transition` when pipelining
    (the transition step prefetches the *new* plan's rows inside the old
    plan's refresh windows) or ``set_plan`` + a plain refresh otherwise.
    The runtime must have been built against the planner's capacity-padded
    exchange layout so the swap never retraces; byte accounting follows
    the *active* plan(s) per step and stays exact across re-plan events.

    ``params0``/``opt_state0`` resume from checkpointed state instead of a
    fresh init (the staleness schedule restarts, whose first step is a
    refresh — required anyway since the caches start zero-filled).

    ``faults`` (a :class:`repro.faults.FaultPlan`) arms deterministic
    fault injection; ``guard`` (a :class:`repro.faults.GuardConfig`)
    configures the defenses — fetch retry/stale-reuse (via the runtime's
    ``set_fault_guard``), the divergence guard (per-step loss finiteness
    plus a fenced parameter sweep + snapshot every ``guard_every`` steps,
    rolling back and forcing a plain refresh on divergence), opt-in
    per-tier payload checksums (corruption forces a refresh of the
    affected tier), and memory-pressure capacity backoff (requires
    ``planner``).  With the default disabled plan and no guard, this loop
    is byte-for-byte the pre-faults code path: no extra sync points, no
    behavior change.  Guard-forced refreshes replace pipelined/transition
    steps with *plain* refreshes — a poisoned stale tier must never be
    consumed.  Injected and defended event counts land in the report
    (``faults_injected`` / ``fault_events``) and as per-step
    :class:`~repro.obs.StepCounters` fields.
    """
    if spec is None:
        warn_loose_kwargs("train_capgnn")
        base = getattr(runtime, "spec", None)
        spec = (base.replace(pipeline=pipeline, seed=seed)
                if base is not None
                else TrainSpec(pipeline=pipeline, seed=seed))
    else:
        pipeline = spec.pipeline
        seed = spec.seed
    if controller is None:
        controller = StalenessController(refresh_every=xplan.refresh_every)
    params = params0 if params0 is not None else init_gnn(
        jax.random.PRNGKey(seed), cfg)
    opt_state = opt_state0 if opt_state0 is not None else opt.init(params)
    caches = init_caches(cfg, xplan, num_parts,
                         features=getattr(runtime, "features", "device"))
    store = getattr(runtime, "host_store", None)
    store_snap = store.snapshot() if store is not None else None
    dims = getattr(runtime, "comm_dims", list(cfg.feat_dims[:cfg.num_layers]))
    # actual wire width of one halo payload entry (2 under halo_dtype=bf16);
    # the vanilla baseline ships the same payload dtype, so the reduction
    # isolates the caching effect.
    dtype_bytes = getattr(runtime, "halo_dtype_bytes", 4)

    tr = tracer if tracer is not None else NULL_TRACER
    if tr.enabled and hasattr(runtime, "set_tracer"):
        runtime.set_tracer(tr)

    fa = faults if faults is not None else NULL_FAULTS
    if fa.enabled and fa.has("mem_pressure") and planner is None:
        raise ValueError(
            "mem_pressure faults need an AdaptivePlanner: the backoff "
            "defense shrinks capacity and replans through it")
    gd = None
    ev_snap = inj_snap = None
    if fa.enabled or guard is not None:
        gd = TrainGuard(guard if guard is not None else GuardConfig(),
                        store=store)
        if hasattr(runtime, "set_fault_guard"):
            runtime.set_fault_guard(gd.fetch_guard)
        if fa.enabled and store is not None:
            store.set_faults(fa)
        if gd.cfg.guard_every > 0:
            gd.snapshot(-1, params, opt_state)   # rollback floor
        gd.seal(caches)                          # checksum baseline
        ev_snap = gd.events.as_dict()
        inj_snap = fa.total_injected()

    losses: list[float] = []
    val_acc: list[float] = []
    comm = 0
    vanilla = 0
    refresh_steps = 0
    replan_events = 0
    x_active = xplan
    dim_bytes = sum(d * dtype_bytes for d in dims)
    rows_by_worker = None   # per-worker uncached recv rows (traced runs)
    step_snap = (store.snapshot()
                 if store is not None and tr.enabled else None)
    compile_s = 0.0
    pending_refresh = False   # guard-forced refresh for the NEXT step
    t0 = time.perf_counter()
    for e in range(epochs):
        force_refresh, pending_refresh = pending_refresh, False
        mem = False
        if fa.enabled:
            fa.begin_step(e)
            params = fa.corrupt_params(params)
            caches, _ = fa.corrupt_caches(caches, store)
            mem = fa.mem_pressure()
        if gd is not None and gd.cfg.checksums:
            with tr.span("integrity", step=e):
                corrupted = gd.verify(caches)
            if corrupted:
                force_refresh = True
        refresh = controller.should_refresh()
        replan = planner is not None and controller.should_replan()
        if mem:
            # memory-pressure backoff: shrink the cache capacity and
            # replan through the slot-stable machinery this very step
            with tr.span("mem_backoff", step=e):
                planner.shrink_capacity(gd.cfg.mem_backoff_factor)
            gd.events.mem_backoffs += 1
            replan = True
        if force_refresh:
            gd.events.forced_refreshes += 1
        refresh = refresh or force_refresh or mem
        # a guard-forced refresh must be a PLAIN refresh: pipelined /
        # transition flavours consume the stale tiers being quarantined
        if replan:
            kind = ("transition" if pipeline and not force_refresh
                    else "refresh")
        elif refresh and pipeline and controller.step > 0 and not force_refresh:
            kind = "pipelined"
        elif refresh:
            kind = "refresh"
        else:
            kind = "cached"
        with tr.step_span(kind, e):
            if replan:
                with tr.span("replan", step=e):
                    x_next = planner.exchange_plan(planner.replan())
                if pipeline and not force_refresh:
                    # transition step: consume/exchange on the old plan,
                    # prefetch the new plan's tier rows in the ring windows
                    params, opt_state, caches, m = runtime.step_transition(
                        params, opt_state, caches, x_next)
                    x_read, x_emit = x_active, x_next
                else:
                    runtime.set_plan(x_next)
                    params, opt_state, caches, m = runtime.step_refresh(
                        params, opt_state, caches)
                    x_read = x_emit = x_next
                refreshed_tiers = True
                x_active = x_next
                replan_events += 1
            else:
                if (refresh and pipeline and controller.step > 0
                        and not force_refresh):
                    step_fn = runtime.step_pipelined
                elif refresh:
                    step_fn = runtime.step_refresh
                else:
                    step_fn = runtime.step_cached
                params, opt_state, caches, m = step_fn(params, opt_state,
                                                       caches)
                x_read = x_emit = x_active
                refreshed_tiers = refresh
            step_rows = _step_rows(x_read, x_emit, refresh=refreshed_tiers)
            tr.fence(m["loss"])
        losses.append(float(m["loss"]))
        if e == 0:
            # fence step 0 separately: its wall time is dominated by jit
            # trace+compile and must not pollute the steady-state figure
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
        comm += step_rows * dim_bytes
        vanilla += xplan.total_halo * dim_bytes
        refresh_steps += int(refresh)
        # divergence guard: the loss is already a host float (free check
        # every step); the fenced parameter sweep + snapshot run on the
        # guard_every cadence.  Divergence rolls back to the last good
        # snapshot and forces the next step to be a plain refresh.
        diverged = False
        if gd is not None and gd.cfg.guard_every > 0:
            diverged = not np.isfinite(losses[-1])
            if not diverged and (e + 1) % gd.cfg.guard_every == 0:
                with tr.span("divergence_check", step=e):
                    diverged = not gd.params_finite(params)
                if not diverged:
                    gd.snapshot(e, params, opt_state)
            if diverged:
                with tr.span("rollback", step=e):
                    params, opt_state = gd.rollback(params, opt_state)
                pending_refresh = True
        # On a transition step the fresh rows are laid out for the NEW plan
        # while the compared caches hold the OLD plan's rows, so the drift
        # metrics compare different vertices — skip them entirely there
        # (and on diverged steps, whose drift is non-finite).
        drift = (float(m["drift"])
                 if "drift" in m and not replan and not diverged else None)
        if planner is not None:
            planner.observe_step(layers=max(1, len(dims)))
            if "drift_local_rows" in m and not replan and not diverged:
                planner.observe_drift(np.asarray(m["drift_local_rows"]),
                                      np.asarray(m["drift_global_rows"]))
        controller.observe(drift, refreshed=refresh)
        if eval_every and (e + 1) % eval_every == 0:
            with tr.span("eval", step=e):
                val_acc.append(runtime.evaluate(params, "val")[1])
        if tr.enabled:
            # counters are recorded at iteration end so the store deltas
            # (step + any eval fetches) attribute to this step exactly —
            # the per-step stream sums to the report totals
            sd = {}
            if store is not None:
                sd = store.delta(step_snap)
                step_snap = store.snapshot()
            if refreshed_tiers or rows_by_worker is None:
                rows_by_worker = [int(n) for n in np.asarray(
                    x_read.uncached.recv_valid).sum(axis=1)]
            extra = {}
            if gd is not None:
                # per-step defense/injection deltas: the stream sums to
                # the report's fault_events / faults_injected exactly
                extra = gd.events.delta(ev_snap)
                ev_snap = gd.events.as_dict()
                extra["faults_injected"] = fa.total_injected() - inj_snap
                inj_snap = fa.total_injected()
            tr.count(StepCounters(
                step=e, kind=kind,
                wire_rows_uncached=x_read.uncached.n_rows,
                wire_rows_local=(x_emit.local.n_rows
                                 if refreshed_tiers else 0),
                wire_rows_global=(x_emit.glob.n_unique
                                  if refreshed_tiers else 0),
                wire_bytes=step_rows * dim_bytes,
                wire_bytes_vanilla=xplan.total_halo * dim_bytes,
                cache_hit_rate=(None if refreshed_tiers else
                                1.0 - x_read.uncached.n_rows
                                / max(1, x_read.total_halo)),
                planner_hit_rate=(planner.hit_rate()
                                  if planner is not None else None),
                drift=drift,
                host_fetch_rows=int(sd.get("fetch_rows", 0)),
                host_fetch_bytes=int(sd.get("fetch_bytes", 0)),
                host_writeback_rows=int(sd.get("writeback_rows", 0)),
                host_writeback_bytes=int(sd.get("writeback_bytes", 0)),
                device_peak_bytes=device_peak_bytes(),
                wire_rows_by_worker=rows_by_worker, **extra))
        if gd is not None and gd.cfg.checksums:
            # seal the post-step tier payloads: the digests the next
            # consuming step must still observe
            with tr.span("integrity", step=e):
                gd.seal(caches)
    wall = time.perf_counter() - t0
    fa.end_run()

    # note: eval_every runs also consume accounted host fetches, so pin
    # eval_every=0 when asserting the plan-rows == staged-rows identity
    hostd = store.delta(store_snap) if store is not None else {}
    report = TrainReport(
        losses=losses, val_acc=val_acc, comm_bytes=comm,
        comm_bytes_vanilla=vanilla,
        comm_reduction=1.0 - comm / max(vanilla, 1),
        refresh_steps=refresh_steps, cached_steps=epochs - refresh_steps,
        wall_time_s=wall, replan_events=replan_events,
        hit_rate=planner.hit_rate() if planner is not None else None,
        final_opt_state=opt_state,
        host_fetch_rows=int(hostd.get("fetch_rows", 0)),
        host_fetch_bytes=int(hostd.get("fetch_bytes", 0)),
        host_writeback_bytes=int(hostd.get("writeback_bytes", 0)),
        compile_s=compile_s,
        faults_injected=dict(fa.injected) if fa.enabled else None,
        fault_events=gd.events.as_dict() if gd is not None else None,
        phase_stats=tr.phase_stats() if tr.enabled else None,
        spec=spec.to_dict())
    return params, report
