"""SPMD CaPGNN runtime: the stacked-oracle step functions lowered through
``shard_map`` over a device mesh, one partition per device.

Layout: every ``[P, ...]`` stacked array is sharded on its leading axis over
the mesh axis (or axis *tuple* — the §5.11-style multi-pod mesh shards the
partition dim over ``("pod", "data")``, linearised row-major, which is
exactly the order ``all_gather`` / the ``ppermute`` ring index over that
tuple reconstructs).  Parameters, optimizer state and the deduplicated
global-cache buffer are replicated.

Communication — two transports, selected by ``transport=``:

- ``"allgather"``: each tier's owners pack their (deduplicated) send rows
  into a dense payload and a single static-shape ``all_gather`` delivers
  every payload to every consumer; consumers address rows by
  ``(src_part, src_slot)``.  Simple, but wire volume is ~P x the paper's
  point-to-point accounting (replicas land on devices that never read
  them).
- ``"p2p"``: each owner re-packs its rows per destination
  (``peer_send_row``) and P-1 ``ppermute`` rotations ship block (i -> j)
  directly to j — static shapes, works on flat and multi-pod meshes, and
  each tier row crosses the wire exactly once per consumer, matching
  :meth:`~repro.dist.ExchangePlan.bytes_per_step` /
  :func:`repro.core.jaca.comm_bytes_per_step` exactly.  The global tier
  is a ring *broadcast* of the deduplicated buffer (it emulates the
  paper's CPU-shared cache: each unique row originates once).

On cached steps only the uncached tier moves — the JACA tiers replace that
traffic entirely.  ``step_pipelined`` consumes stale caches like
``step_cached`` but *additionally* refreshes them with a double-buffered
ring: the per-boundary refresh pulls are issued on the previous layer's
activations and advanced one rotation per layer while the SpMM computes,
finalising only after the last layer — nothing on the loss/grad critical
path waits for them (and no backward collectives are emitted for the
refreshed tiers), which is where the paper's pipeline hides the refresh
latency.  Loss and gradient reductions are ``psum`` over the same axis
tuple, so backprop through the exchange (``all_gather`` transpose /
inverse-permutation ``ppermute``) reproduces the oracle's exact
cross-partition gradient flow.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import pack_rows
from repro.models.gnn import GNNConfig, _layer_apply, accuracy, cross_entropy_loss
from repro.obs.annotations import device_scope
from repro.obs.tracer import NULL_TRACER
from repro.optim import Optimizer

from .capgnn_sim import (RUNTIME_FEATURES, halo_dtype_info, init_caches,
                         make_adj_builder)
from .exchange import ExchangePlan, StackedParts
from .host_store import HostFeatureStore
from .spec import TrainSpec, halo_dtype_name, warn_loose_kwargs

__all__ = ["make_spmd_runtime", "SpmdRuntime", "TRANSPORTS",
           "spmd_exchange_arrays"]

TRANSPORTS = ("allgather", "p2p")


def spmd_exchange_arrays(xplan: ExchangePlan, p2p: bool, mesh,
                         axis_names: tuple,
                         include_host: bool = False) -> dict:
    """One plan's exchange index arrays in the SPMD runtime's layout:
    ``"sh"`` leaves are ``[P, ...]`` and sharded over the partition axis,
    ``"rep"`` leaves (the global buffer's source addressing) replicated.
    The jitted steps take this pytree as a traced argument, so a
    capacity-padded re-plan swaps in without retracing.  ``include_host``
    adds the layer-0 host-tier scatter program (sharded like the other
    per-worker tiers) for the ``features="host"`` runtimes.  The leaves
    are placed once on ``mesh`` (sharded over ``axis_names``), so no step
    call reshards them."""

    def tier_arrays(t):
        d = {"send_row": t.send_row,
             "recv_src_part": t.recv_src_part,
             "recv_src_slot": t.recv_src_slot,
             "recv_halo_pos": t.recv_halo_pos,
             "recv_valid": t.recv_valid}
        if p2p:
            d.update(peer_send_row=t.peer_send_row,
                     peer_send_valid=t.peer_send_valid,
                     recv_peer_slot=t.recv_peer_slot)
        return d

    sh = {"un": tier_arrays(xplan.uncached),
          "loc": tier_arrays(xplan.local),
          "gl": {"send_row": xplan.glob.send_row,
                 "read_pos": xplan.glob.read_pos,
                 "read_buf_idx": xplan.glob.read_buf_idx,
                 "read_valid": xplan.glob.read_valid}}
    if include_host:
        if xplan.host is None:
            raise ValueError("features='host' needs a plan with a host "
                             "tier (rebuild via build_exchange_plan)")
        sh["host"] = {"feat_pos": xplan.host.feat_pos.astype(np.int32),
                      "feat_valid": xplan.host.feat_valid}
    rep = {"g_src_part": xplan.glob.src_part,
           "g_src_slot": xplan.glob.src_slot,
           "g_buf_valid": xplan.glob.buf_valid}
    return {"sh": jax.device_put(jax.tree.map(jnp.asarray, sh),
                                 NamedSharding(mesh, P(axis_names))),
            "rep": jax.device_put(jax.tree.map(jnp.asarray, rep),
                                  NamedSharding(mesh, P()))}


def _shift_perm(p: int, r: int) -> list:
    """Static permutation delivering device i's payload to (i + r) % p."""
    return [(s, (s + r) % p) for s in range(p)]


class _PeerRing:
    """P-1 ``ppermute`` rotations over a per-peer packed payload.

    ``payload[j]`` is the block this device ships to peer ``j``; after
    ``finish()``, ``blocks[o]`` holds the block peer ``o`` shipped to this
    device (own slot stays zero — a device never consumes its own halo
    rows).  Rotation ``r`` delivers block (i -> (i + r) % p) in one hop, so
    each row crosses the wire once per consumer.  The ring is advance-able
    one rotation at a time so the pipelined step can interleave rotations
    with layer compute in program order.
    """

    def __init__(self, payload: jnp.ndarray, i_dev, p: int, names):
        self.payload = payload                      # [P, B, d]
        self.i, self.p, self.names = i_dev, p, names
        self.blocks = jnp.zeros_like(payload)       # [P, B, d] by owner
        self.r = 0

    def advance(self, rotations: int = 1) -> "_PeerRing":
        for _ in range(rotations):
            if self.r >= self.p - 1:
                break
            self.r += 1
            send = jnp.take(self.payload, (self.i + self.r) % self.p, axis=0)
            recv = jax.lax.ppermute(send, self.names,
                                    _shift_perm(self.p, self.r))
            self.blocks = self.blocks.at[(self.i - self.r) % self.p].set(recv)
        return self

    def finish(self) -> jnp.ndarray:
        return self.advance(self.p).blocks


class _BufRing:
    """Ring broadcast of the deduplicated global-tier payload ``[SG, d]``:
    each owner's buffer originates once and circulates to all peers,
    accumulating the same ``[P, SG, d]`` an ``all_gather`` would build."""

    def __init__(self, payload: jnp.ndarray, i_dev, p: int, names):
        self.payload = payload
        self.i, self.p, self.names = i_dev, p, names
        acc = jnp.zeros((p,) + payload.shape, payload.dtype)
        self.acc = acc.at[i_dev].set(payload)
        self.r = 0

    def advance(self, rotations: int = 1) -> "_BufRing":
        for _ in range(rotations):
            if self.r >= self.p - 1:
                break
            self.r += 1
            recv = jax.lax.ppermute(self.payload, self.names,
                                    _shift_perm(self.p, self.r))
            self.acc = self.acc.at[(self.i - self.r) % self.p].set(recv)
        return self

    def finish(self) -> jnp.ndarray:
        return self.advance(self.p).acc


@dataclasses.dataclass
class SpmdRuntime:
    cfg: GNNConfig
    xplan: ExchangePlan
    mesh: object
    axis_names: tuple
    comm_dims: list
    forward_fresh: Callable
    step_refresh: Callable
    step_cached: Callable
    step_pipelined: Callable
    evaluate: Callable
    caches0: dict
    backend: str = "edges"
    transport: str = "allgather"
    halo_dtype_bytes: int = 4
    # feature residency — see :func:`repro.dist.make_sim_runtime`
    features: str = "device"
    host_store: HostFeatureStore | None = dataclasses.field(default=None,
                                                            repr=False)
    jit_steps: dict | None = dataclasses.field(default=None, repr=False)
    _state: dict | None = dataclasses.field(default=None, repr=False)
    # the stacked layout this runtime was built over — kept for padded-row
    # accounting under uneven (resource-aware) partitions
    stacked: StackedParts | None = dataclasses.field(default=None, repr=False)
    # the TrainSpec this runtime was configured from (always set — the
    # loose-kwarg shim synthesises one), recorded into TrainReport.spec
    spec: TrainSpec | None = dataclasses.field(default=None, repr=False)
    # the stacked per-partition inputs, placed one partition per device
    data: dict | None = dataclasses.field(default=None, repr=False)

    def padding_stats(self) -> dict:
        """Valid vs padded stacked-row counts (see
        :meth:`repro.dist.StackedParts.padding_stats`)."""
        return self.stacked.padding_stats() if self.stacked else {}

    def set_tracer(self, tracer) -> None:
        """Attach a :class:`repro.obs.Tracer` (see
        :meth:`repro.dist.SimRuntime.set_tracer`)."""
        if self._state is not None:
            self._state["tracer"] = tracer
        if self.host_store is not None:
            self.host_store.set_tracer(tracer)

    def set_fault_guard(self, guard) -> None:
        """Attach a :class:`repro.faults.FetchGuard` (see
        :meth:`repro.dist.SimRuntime.set_fault_guard`)."""
        if self._state is not None:
            self._state["fetch_guard"] = guard
            if guard is not None and "l0loc" in self._state:
                guard.last_good.setdefault("l0loc", self._state["l0loc"])

    def wire_rows(self, refresh: bool, padded: bool = False) -> dict:
        """Rows this runtime's transport moves in one layer exchange (see
        :meth:`repro.dist.ExchangePlan.transport_rows`)."""
        return self.xplan.transport_rows(self.transport, refresh,
                                         padded=padded)

    def set_plan(self, xplan: ExchangePlan) -> None:
        """Install a re-ranked plan (slot-stable capacity-padded layout:
        no retrace).  Cache content still follows the old tiering — the
        next step must refresh, or come from :meth:`step_transition`.
        Host mode additionally flushes the staging ring (unaccounted) and
        restages the layer-0 local tier for the new plan."""
        self.xplan = xplan
        hook = (self._state or {}).get("_set_plan")
        if hook is not None:
            hook(xplan)
        else:
            self._state["xarr"] = spmd_exchange_arrays(
                xplan, self.transport == "p2p", self.mesh, self.axis_names)

    def step_transition(self, params, opt_state, caches,
                        new_xplan: ExchangePlan):
        """Pipelined plan switch: stale consumption + uncached exchange
        run on the installed plan while the refresh rings prefetch the
        **new** plan's tier rows; the emitted caches are laid out for
        ``new_xplan``, which becomes the installed plan.  Host-mode
        semantics mirror :meth:`repro.dist.SimRuntime.step_transition`."""
        hook = (self._state or {}).get("_transition")
        if hook is not None:
            out = hook(params, opt_state, caches, new_xplan)
        else:
            xe = spmd_exchange_arrays(new_xplan, self.transport == "p2p",
                                      self.mesh, self.axis_names)
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


def make_spmd_runtime(cfg: GNNConfig, sp: StackedParts, xplan: ExchangePlan,
                      opt: Optimizer, mesh, axis: str | Sequence[str] = "data",
                      exchange_layer0: bool = True, backend: str = "edges",
                      transport: str = "allgather",
                      halo_dtype=None, donate: bool = True,
                      pallas_pack: bool = False, features: str = "device",
                      host_store: HostFeatureStore | None = None,
                      prefetch_depth: int = 2,
                      spec: TrainSpec | None = None) -> SpmdRuntime:
    """``backend`` mirrors :func:`make_sim_runtime`: the per-device local
    aggregation runs through the edge-list segment-sum, the Pallas
    blocked-ELL kernel, or the hybrid ELL+COO pack — the exchange
    collectives and byte accounting are identical across backends.

    ``transport`` picks the halo exchange lowering (see module docstring);
    ``"p2p"`` vs ``"allgather"`` logits and gradients agree to ~1e-5
    (asserted by ``tests/test_transport.py``).  ``halo_dtype="bf16"``
    casts every payload before the wire and dequantises on scatter.
    ``donate=True`` donates ``(params, opt_state, caches)`` into the
    jitted steps — re-use the returned state, not the arguments.
    ``pallas_pack=True`` routes the per-peer payload pack through the
    Pallas :func:`~repro.kernels.ops.gather_rows` kernel (TPU path).

    ``features="host"`` mirrors :func:`make_sim_runtime`'s out-of-core
    mode on the mesh: the halo table never ships to the devices — the
    layer-0 local tier is staged once per plan (sharded over the
    partition axis), the uncached+global layer-0 rows ride the store's
    double-buffered staging ring (the next step's ``device_put`` is in
    flight while the current step runs), and the per-layer global
    buffers are host-resident between steps (d2h writeback on refresh,
    replicated h2d stage for the stale reads).

    ``spec`` (a :class:`repro.dist.TrainSpec`) is the configuration
    surface; when passed it overrides every loose configuration kwarg
    (the deprecated shim forwards them into a synthesised spec with one
    ``DeprecationWarning`` — see the README migration note).  ``mesh``,
    ``axis`` and ``host_store`` stay real arguments: resources, not
    choices.
    """
    if spec is None:
        warn_loose_kwargs("make_spmd_runtime")
        spec = TrainSpec(strategy="halo_1d", backend=backend,
                         transport=transport, features=features,
                         halo_dtype=halo_dtype_name(halo_dtype),
                         exchange_layer0=exchange_layer0, donate=donate,
                         pallas_pack=pallas_pack,
                         prefetch_depth=prefetch_depth)
    exchange_layer0 = spec.exchange_layer0
    backend = spec.backend
    transport = spec.transport
    halo_dtype = spec.halo_dtype
    donate = spec.donate
    pallas_pack = spec.pallas_pack
    features = spec.features
    prefetch_depth = spec.prefetch_depth
    if transport not in TRANSPORTS:
        raise ValueError(f"unknown transport {transport!r}; "
                         f"expected one of {TRANSPORTS}")
    if features not in RUNTIME_FEATURES:
        raise ValueError(f"unknown features mode {features!r}; "
                         f"expected one of {RUNTIME_FEATURES}")
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    mesh_size = int(np.prod([mesh.shape[n] for n in names]))
    p, ni, nh = sp.num_parts, sp.n_inner_max, sp.n_halo_max
    if mesh_size != p:
        raise ValueError(f"mesh axes {names} have {mesh_size} devices but "
                         f"the plan has {p} partitions")
    layers = cfg.num_layers
    total_train = float(np.maximum(sp.train_mask.sum(), 1.0))
    adj_leaves, build_adj = make_adj_builder(sp, backend)
    hdt, hd_bytes = halo_dtype_info(halo_dtype)
    p2p = transport == "p2p"
    host_mode = features == "host"
    if host_mode:
        store = host_store if host_store is not None else HostFeatureStore(
            sp.halo_feats, halo_dtype=halo_dtype,
            prefetch_depth=prefetch_depth)
    else:
        store = None

    # Sharded batch: leading dim = partition.  The exchange index arrays
    # are NOT baked here — they travel as step arguments (xr/xe pytrees
    # from spmd_exchange_arrays) so online re-planning swaps them without
    # retracing.  In host mode the halo feature table stays host-side.
    data_sh = {
        "feats": sp.feats,
        "labels": sp.labels.astype(np.int32),
        "train_mask": sp.train_mask, "val_mask": sp.val_mask,
        "test_mask": sp.test_mask,
        "adj": adj_leaves,
    }
    if not host_mode:
        data_sh["halo_feats"] = sp.halo_feats
    # placed once, one partition per device: no step call reshards them
    data_sh = jax.device_put(jax.tree.map(jnp.asarray, data_sh),
                             NamedSharding(mesh, P(names)))

    caches_spec = {"local": P(names), "global": P()}
    # donated caches alias the step's sharded cache outputs only when the
    # compiled input is laid out the same way, so pin it
    caches_sh = {k: NamedSharding(mesh, v) for k, v in caches_spec.items()}
    xarr_spec = {"sh": P(names), "rep": P()}

    def _quant(x):
        return x.astype(hdt) if hdt is not None else x

    def _device_forward(params, caches, dsh, xr, xe, use_stale: bool,
                        defer_refresh: bool = False, hostd=None, l0loc=None):
        """Per-device forward. ``dsh``/``x*["sh"]`` leaves carry a leading
        dim of 1.

        ``xr`` is the installed (read) plan — stale cache consumption and
        the per-step uncached exchange run on it; ``xe`` is the emit plan
        whose tier rows the refresh pulls fetch.  They are the same arrays
        except on a plan-transition step, where the refresh
        prefetches the *next* plan's rows.

        ``defer_refresh`` (pipelined step, p2p transport): the local/global
        refresh pulls are issued as advance-able rings at their layer
        boundary, rotated once per layer while the SpMM computes, and
        finalised after the last layer — the layer math itself consumes
        the stale caches, so the rings never block it.

        In host mode the layer-0 halo is scattered from the staged
        payloads (``l0loc`` + ``hostd["l0"]``, sharded like the tiers)
        and stale global reads come from ``hostd["gl"]`` (replicated
        stage of the host-resident buffers) — mirroring the oracle.
        """
        def peer_ring(tier, h):
            payload = pack_rows(h, tier["peer_send_row"][0],
                                use_pallas=pallas_pack)          # [P, B, d]
            payload = jnp.where(tier["peer_send_valid"][0][..., None],
                                payload, 0.0)
            return _PeerRing(_quant(payload), jax.lax.axis_index(names), p,
                             names)

        def peer_collect(tier, blocks, dtype):
            rows = blocks[tier["recv_src_part"][0],
                          tier["recv_peer_slot"][0]].astype(dtype)
            return jnp.where(tier["recv_valid"][0][..., None], rows, 0.0)

        def pull(tier, h):
            """Fresh tier rows [R, d], transport run to completion."""
            if p2p:
                return peer_collect(tier, peer_ring(tier, h).finish(),
                                    h.dtype)
            payload = _quant(h[tier["send_row"][0]])              # [S, d]
            gathered = jax.lax.all_gather(payload, names)         # [P, S, d]
            rows = gathered[tier["recv_src_part"][0],
                            tier["recv_src_slot"][0]].astype(h.dtype)
            return jnp.where(tier["recv_valid"][0][..., None], rows, 0.0)

        def buf_ring(xa, h):
            return _BufRing(_quant(h[xa["sh"]["gl"]["send_row"][0]]),
                            jax.lax.axis_index(names), p, names)

        def buf_collect(xa, acc, dtype):
            rows = acc[xa["rep"]["g_src_part"],
                       xa["rep"]["g_src_slot"]].astype(dtype)
            return jnp.where(xa["rep"]["g_buf_valid"][:, None], rows, 0.0)

        def build_global(xa, h):
            if p2p:
                return buf_collect(xa, buf_ring(xa, h).finish(), h.dtype)
            payload = _quant(h[xa["sh"]["gl"]["send_row"][0]])    # [SG, d]
            gathered = jax.lax.all_gather(payload, names)         # [P, SG, d]
            return buf_collect(xa, gathered, h.dtype)

        def scatter(halo, pos, rows, valid):
            pos_eff = jnp.where(valid, pos, nh)
            return halo.at[pos_eff].set(rows, mode="drop")

        def read_global(gl, buf, halo):
            return scatter(halo, gl["read_pos"][0],
                           buf[gl["read_buf_idx"][0]], gl["read_valid"][0])

        with device_scope("cache_read"):
            h = dsh["feats"][0]                                   # [NI, F]
        fresh = {"local": [], "global": []}
        pending = []   # (dtype, local _PeerRing, global _BufRing)
        for li, lp in enumerate(params):
            if li == 0:
                with device_scope("cache_read"):
                    if host_mode:
                        halo = jnp.zeros((nh, h.shape[-1]), h.dtype)
                        loc_t = xr["sh"]["loc"]
                        halo = scatter(halo, loc_t["recv_halo_pos"][0],
                                       l0loc[0].astype(h.dtype),
                                       loc_t["recv_valid"][0])
                        ht = xr["sh"]["host"]
                        halo = scatter(halo, ht["feat_pos"][0],
                                       hostd["l0"][0].astype(h.dtype),
                                       ht["feat_valid"][0])
                    else:
                        halo = dsh["halo_feats"][0]               # [NH, F]
            else:
                d = h.shape[-1]
                with device_scope("cache_read"):
                    halo = jnp.zeros((nh, d), h.dtype)
                un = xr["sh"]["un"]
                with device_scope("tier_pull_uncached"):
                    halo = scatter(halo, un["recv_halo_pos"][0], pull(un, h),
                                   un["recv_valid"][0])
                if defer_refresh and p2p:
                    # issue this boundary's refresh rings on the EMIT plan;
                    # consume stale through the READ plan
                    with device_scope("refresh_ring_issue"):
                        pending.append((h.dtype,
                                        peer_ring(xe["sh"]["loc"], h),
                                        buf_ring(xe, h)))
                else:
                    with device_scope("tier_pull_refresh"):
                        loc_fresh = pull(xe["sh"]["loc"], h)
                        buf_fresh = build_global(xe, h)
                    with device_scope("cache_update"):
                        fresh["local"].append(loc_fresh[None])
                        fresh["global"].append(buf_fresh)
                tiers = xr["sh"] if use_stale else xe["sh"]
                with device_scope("cache_read"):
                    if use_stale:
                        loc_use = caches["local"][li - 1][0]
                        buf_use = (hostd["gl"][li - 1].astype(h.dtype)
                                   if host_mode else caches["global"][li - 1])
                    else:
                        loc_use, buf_use = loc_fresh, buf_fresh
                    halo = scatter(halo, tiers["loc"]["recv_halo_pos"][0],
                                   loc_use, tiers["loc"]["recv_valid"][0])
                    halo = read_global(tiers["gl"], buf_use, halo)
            with device_scope("cache_read"):
                h_local = jnp.concatenate([h, halo], axis=0)
            with device_scope(f"layer{li}"):
                adj = build_adj({k: v[0] for k, v in dsh["adj"].items()})
                h = _layer_apply(cfg, lp, adj, h_local, ni,
                                 is_last=(li == layers - 1))
            # one ring rotation per in-flight refresh, placed right after
            # the layer's SpMM in program order so XLA's latency-hiding
            # scheduler can run the sends under the compute
            with device_scope("refresh_ring_advance"):
                for _, lring, bring in pending:
                    lring.advance()
                    bring.advance()
        with device_scope("refresh_ring_finish"):
            for dtype, lring, bring in pending:
                fresh["local"].append(
                    peer_collect(xe["sh"]["loc"], lring.finish(),
                                 dtype)[None])
                fresh["global"].append(buf_collect(xe, bring.finish(),
                                                   dtype))
        return h, fresh

    def _device_loss(params, caches, dsh, xr, xe, use_stale: bool,
                     defer_refresh: bool, hostd=None, l0loc=None):
        """This device's share of the global mean loss.  The cross-device
        ``psum`` stays OUTSIDE the differentiated function: under
        ``shard_map`` the transpose of an in-loss ``psum`` is another
        ``psum``, so differentiating the summed loss and then psumming the
        grads double-counts by a factor P (the oracle-parity suite pins
        this with an sgd step, where adam's scale-invariant first step
        cannot mask it)."""
        logits, fresh = _device_forward(params, caches, dsh, xr, xe,
                                        use_stale, defer_refresh,
                                        hostd, l0loc)
        with device_scope("loss"):
            labels = dsh["labels"][0]
            mask = dsh["train_mask"][0]
            logp = jax.nn.log_softmax(logits, -1)
            nll = -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]
            loss = jnp.sum(nll * mask) / total_train
        return loss, (logits, fresh)

    def _make_step(use_stale: bool, emit_fresh: bool,
                   defer_refresh: bool = False):
        def device_step(params, opt_state, caches, dsh, xr, xe,
                        hostd=None, l0loc=None):
            (loss, (logits, fresh)), grads = jax.value_and_grad(
                _device_loss, has_aux=True)(params, caches, dsh, xr, xe,
                                            use_stale, defer_refresh,
                                            hostd, l0loc)
            with device_scope("loss"):
                labels = dsh["labels"][0]
                mask = dsh["train_mask"][0]
                correct = (jnp.argmax(logits, -1) == labels).astype(
                    jnp.float32)
                n_correct = jnp.sum(correct * mask)
            with device_scope("grad_sync"):
                loss = jax.lax.psum(loss, names)
                grads = jax.lax.psum(grads, names)
                acc = jax.lax.psum(n_correct, names) / total_train
            with device_scope("optimizer"):
                new_params, new_state = opt.update(grads, opt_state, params)
            metrics = {"loss": loss, "acc": acc}
            with device_scope("cache_update"):
                # host refresh has no staged stale global to drift against
                # — the keys are not emitted there (mirrors the oracle)
                if emit_fresh and (use_stale or not host_mode):
                    stale_gl = ([g.astype(jnp.float32) for g in hostd["gl"]]
                                if host_mode else caches["global"])
                    pairs = list(zip(fresh["local"] + fresh["global"],
                                     caches["local"] + stale_gl))
                    drifts = [jnp.max(jnp.abs(a - b)) for a, b in pairs
                              if a.size]
                    local_max = (jnp.max(jnp.stack(drifts)) if drifts
                                 else jnp.zeros(()))
                    metrics["drift"] = jax.lax.pmax(local_max, names)
                    n_ex = len(fresh["local"])
                    if n_ex:
                        # per-row drift stats for the drift-aware planner
                        metrics["drift_local_rows"] = jnp.max(jnp.stack(
                            [jnp.max(jnp.abs(a - b), axis=-1)
                             for a, b in pairs[:n_ex]]), axis=0)   # [1, Rloc]
                        metrics["drift_global_rows"] = jax.lax.pmax(
                            jnp.max(jnp.stack(
                                [jnp.max(jnp.abs(a - b), axis=-1)
                                 for a, b in pairs[n_ex:]]), axis=0), names)
            if host_mode:
                out_caches = {"local": (fresh["local"] if emit_fresh
                                        else caches["local"]),
                              "global": []}
            else:
                out_caches = fresh if emit_fresh else caches
            if host_mode and emit_fresh:
                # fresh global buffers return to the host store (d2h by
                # the wrapper), not into replicated device caches
                return (new_params, new_state, out_caches,
                        fresh["global"], metrics)
            return new_params, new_state, out_caches, metrics

        mspec = {"loss": P(), "acc": P()}
        emit_drift = emit_fresh and (use_stale or not host_mode)
        if emit_drift and layers > 1:
            mspec.update(drift=P(), drift_local_rows=P(names),
                         drift_global_rows=P())
        elif emit_drift:
            mspec["drift"] = P()
        host_caches_spec = {"local": P(names), "global": P()}
        if host_mode:
            hostd_spec = ({"l0": P(names), "gl": P()} if use_stale
                          else {"l0": P(names)})
            out_specs = (P(), P(), host_caches_spec, mspec)
            if emit_fresh:
                out_specs = (P(), P(), host_caches_spec, P(), mspec)
            sm = jax.shard_map(
                device_step, mesh=mesh,
                in_specs=(P(), P(), caches_spec, P(names), xarr_spec,
                          xarr_spec, hostd_spec, P(names)),
                out_specs=out_specs, check_vma=False)

            def step(params, opt_state, caches, hostd, l0loc, xr, xe, dsh):
                return sm(params, opt_state, caches, dsh, xr, xe,
                          hostd, l0loc)
            # the staged hostd payloads are single-use but never match an
            # output shape, so they are not donated (mirrors the oracle)
            return jax.jit(step, donate_argnums=(0, 1, 2) if donate else (),
                           in_shardings=(None, None, caches_sh) + (None,) * 5)

        sm = jax.shard_map(
            device_step, mesh=mesh,
            in_specs=(P(), P(), caches_spec, P(names), xarr_spec, xarr_spec),
            out_specs=(P(), P(), caches_spec, mspec),
            check_vma=False)

        def step(params, opt_state, caches, xr, xe, dsh):
            return sm(params, opt_state, caches, dsh, xr, xe)
        # steady-state steps rewrite (params, opt_state, caches) in place;
        # the exchange arrays (xr, xe) and the stacked inputs (dsh) are
        # reused across steps, not donated
        return jax.jit(step, donate_argnums=(0, 1, 2) if donate else (),
                       in_shardings=(None, None, caches_sh) + (None,) * 3)

    if host_mode:
        def _device_fwd_fresh(params, caches, dsh, xr, hostd, l0loc):
            logits, _ = _device_forward(params, caches, dsh, xr, xr, False,
                                        hostd=hostd, l0loc=l0loc)
            return logits[None]

        sm_fwd = jax.shard_map(_device_fwd_fresh, mesh=mesh,
                               in_specs=(P(), caches_spec, P(names),
                                         xarr_spec, {"l0": P(names)},
                                         P(names)),
                               out_specs=P(names), check_vma=False)
    else:
        def _device_fwd_fresh(params, caches, dsh, xr):
            logits, _ = _device_forward(params, caches, dsh, xr, xr, False)
            return logits[None]

        sm_fwd = jax.shard_map(_device_fwd_fresh, mesh=mesh,
                               in_specs=(P(), caches_spec, P(names),
                                         xarr_spec),
                               out_specs=P(names), check_vma=False)
    caches0 = jax.device_put(init_caches(cfg, xplan, p, features=features),
                             caches_sh)

    jit_steps = {"refresh": _make_step(False, True),
                 "cached": _make_step(True, False),
                 "pipelined": _make_step(True, True, defer_refresh=p2p)}
    if host_mode:
        jit_steps["forward"] = jax.jit(
            lambda params, hd, l0loc, xa, dsh, c0: sm_fwd(params, c0, dsh,
                                                          xa, hd, l0loc))
    else:
        jit_steps["forward"] = jax.jit(
            lambda params, xa, dsh, c0: sm_fwd(params, c0, dsh, xa))
    state = {"xarr": spmd_exchange_arrays(xplan, p2p, mesh, names,
                                          include_host=host_mode),
             "tracer": NULL_TRACER}

    def wrap(name):
        def stepper(params, opt_state, caches):
            xa = state["xarr"]
            return jit_steps[name](params, opt_state, caches, xa, xa,
                                   data_sh)
        return stepper

    if host_mode:
        n_ex = layers - 1
        ex_dims = list(cfg.feat_dims[1:layers])
        parts_idx = np.arange(p)[:, None]
        staged_dtype = hdt if hdt is not None else jnp.float32
        shard_parts = NamedSharding(mesh, P(names))
        shard_rep = NamedSharding(mesh, P())

        def _host_np(xp: ExchangePlan) -> dict:
            return {"feat_pos": np.asarray(xp.host.feat_pos, np.int64),
                    "feat_valid": np.asarray(xp.host.feat_valid, bool),
                    "loc_pos": np.asarray(xp.local.recv_halo_pos, np.int64),
                    "loc_valid": np.asarray(xp.local.recv_valid, bool),
                    "gl_rows": int(xp.glob.n_unique)}

        def _stage_l0loc():
            hn = state["hostnp"]

            def stage():
                return store.stage_rows((parts_idx, hn["loc_pos"]),
                                        valid=hn["loc_valid"],
                                        device=shard_parts)
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
                                    valid=hn["feat_valid"],
                                    device=shard_parts)

        def _take_l0():
            # fault-guard semantics mirror the sim runtime's _take_l0
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
                    sf = store.stage_buf(li, device=shard_rep)
                    store.account_fetch(sf)
                    out.append(sf.array)
                else:
                    out.append(g.fetch_sync(
                        lambda li=li: store.stage_buf(li, device=shard_rep),
                        store, f"gl{li}"))
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
                                      state["l0loc"], xa, xa, data_sh)
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
            state["xarr"] = spmd_exchange_arrays(xp, p2p, mesh, names,
                                                 include_host=True)
            state["hostnp"] = _host_np(xp)
            state["l0_ring"].clear()     # flushed, never accounted
            with tr.span("l0_stage"):
                _stage_l0loc()
            with tr.span("h2d_prefetch"):
                _prefetch_l0()
        state["_set_plan"] = _set_plan

        def _transition(params, opt_state, caches, new_xp: ExchangePlan):
            tr = state["tracer"]
            with tr.span("l0_stage"):
                hostd = {"l0": _take_l0(), "gl": _take_gl()}
            xr = state["xarr"]
            xe = spmd_exchange_arrays(new_xp, p2p, mesh, names,
                                      include_host=True)
            new_p, new_s, out_caches, host_out, metrics = (
                jit_steps["pipelined"](params, opt_state, caches, hostd,
                                       state["l0loc"], xr, xe, data_sh))
            state["xarr"] = xe
            state["hostnp"] = _host_np(new_xp)
            with tr.span("writeback"):
                _writeback(host_out)     # new plan's membership
            state["l0_ring"].clear()
            with tr.span("l0_stage"):
                _stage_l0loc()
            with tr.span("h2d_prefetch"):
                _prefetch_l0()
            return new_p, new_s, out_caches, metrics
        state["_transition"] = _transition

        def _dummy_hostd(name: str) -> dict:
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
                                        state["l0loc"], state["xarr"],
                                        data_sh, caches0)

        step_wrap = wrap_host
        _prefetch_l0()
    else:
        def forward_fresh(params):
            return jit_steps["forward"](params, state["xarr"], data_sh,
                                        caches0)

        step_wrap = wrap

    labels_flat = jnp.asarray(sp.labels.astype(np.int32)).reshape(-1)
    masks_flat = {"train": jnp.asarray(sp.train_mask).reshape(-1),
                  "val": jnp.asarray(sp.val_mask).reshape(-1),
                  "test": jnp.asarray(sp.test_mask).reshape(-1)}

    def evaluate(params, split: str = "val"):
        flat = forward_fresh(params).reshape(-1, cfg.out_dim)
        m = masks_flat[split]
        return (float(cross_entropy_loss(flat, labels_flat, m)),
                float(accuracy(flat, labels_flat, m)))

    comm_dims = list(cfg.feat_dims[:layers])
    if not exchange_layer0 or host_mode:
        # host mode: layer-0 rows arrive over PCIe from the host store
        # (accounted by the store), not over the inter-worker wire
        comm_dims = comm_dims[1:]

    return SpmdRuntime(cfg=cfg, xplan=xplan, mesh=mesh, axis_names=names,
                       comm_dims=comm_dims, forward_fresh=forward_fresh,
                       step_refresh=step_wrap("refresh"),
                       step_cached=step_wrap("cached"),
                       step_pipelined=step_wrap("pipelined"),
                       evaluate=evaluate, caches0=caches0, backend=backend,
                       transport=transport, halo_dtype_bytes=hd_bytes,
                       features=features, host_store=store,
                       jit_steps=jit_steps, _state=state, stacked=sp,
                       spec=spec, data=data_sh)
