"""``spmm_15d``: communication-avoiding 1.5D replicated-row block SpMM.

The halo model's wire volume tracks the partition cut, which grows with P
until nearly every boundary vertex is consumed remotely.  Tripathy,
Yelick & Buluç ("Reducing Communication in Graph Neural Network
Training", PAPERS.md) avoid that wall by trading memory for bandwidth:
replicate block rows of H over a replication axis of size ``c`` and
aggregate partial SpMM products with an allreduce, cutting the gathered
volume by ``c`` at the cost of an ``[NI, d]`` allreduce per layer.

Layout.  The graph is split into ``pr = P / c`` block rows (the ordinary
1D partitioner — RAPA/METIS reuse).  The ``P``-device mesh is the paper's
2D ``(P/c, c)`` grid with the block-row axis factored into two named
axes, ``("grp", "sub")`` of sizes ``(c, g = pr/c)`` (hence the classic
``P % c**2 == 0`` constraint), plus the replication axis ``("repl", c)``.
Device ``(a, s, j)`` holds block row ``i = a*g + s`` of H (replicated
over ``j``) and the edges of block row ``i`` whose *source* block belongs
to group ``j`` (blocks ``j*g .. j*g+g-1``), with source indices remapped
to ``(k % g) * NI + owner_row`` — positions in the gathered group buffer.

Per layer, each device:

1. ``ppermute`` over ``("grp", "repl")`` — the involution ``(a, j) ->
   (j, a)`` — after which device ``(a, s, j)`` holds block ``j*g + s``
   (skipped when ``c == 1``: the permutation is the identity);
2. ``all_gather`` over ``"sub"`` — now it holds all ``g`` blocks of
   group ``j``, exactly the rows its edge chunk reads (skipped when
   ``g == 1``);
3. local partial SpMM of its chunk (segment-sum, zero-weight padding);
4. ``psum`` over ``"repl"`` sums the ``c`` partial aggregations into the
   exact neighborhood sum for block row ``i`` (skipped when ``c == 1``),
   after which the (replicated) layer transform applies.

``c == 1`` degenerates to the dense 1D baseline (full-H ``all_gather``);
``c > 1`` gathers ``1/c`` of H per device.  Every step is
refresh-equivalent and exact — the JACA tiers, staleness and the host
store are ``halo_1d`` capabilities (see ``StrategyCaps``).

Gradients.  The loss contribution of each block row is computed on all
``c`` replicas, so the final-loss cotangent enters the last layer's
``psum`` *replicated* — under ``shard_map`` the transpose of ``psum`` is
another ``psum``, which over-counts that (and only that) boundary by
``c``; deeper psums receive per-replica *partial* cotangent shares, for
which the summing transpose is exactly right.  Net effect: every
parameter's all-device grad psum carries one uniform factor ``c`` — so
the step divides the psummed loss and grads by ``c`` and lands on the
oracle's exact mean-loss gradient (pinned to 1e-5 by
``tests/spmm15d_parity_script.py``).

Byte accounting.  ``forward_collective_bytes_per_device`` models the
result-shape bytes of exactly the collectives above, matching
:func:`repro.launch.hlo_cost.collective_bytes` over the lowered forward
HLO op-for-op (gated in ``benchmarks/comm_volume.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np

from .strategy import StrategyCaps, StrategyCapabilityError

__all__ = ["Spmm15dLayout", "Spmm15dRuntime", "Spmm15DStrategy",
           "build_spmm15d_layout", "make_spmm15d_mesh",
           "make_spmm15d_runtime", "train_spmm15d",
           "forward_collective_bytes_per_device", "SPMM_15D"]

AXES_15D = ("grp", "sub", "repl")


@dataclasses.dataclass(frozen=True)
class Spmm15dLayout:
    """Static 1.5D layout: the ``pr``-block stacking (reused from
    ``stack_partitions``) plus per-device edge chunks with gathered-buffer
    source indices.  Flat device order is row-major over
    ``(grp, sub, repl)`` — device ``i*c + j`` serves block row ``i``,
    replica ``j``."""
    c: int                      # replication factor
    g: int                      # blocks per group (= pr / c)
    pr: int                     # block rows (= P / c)
    ni: int                     # padded rows per block (sp.n_inner_max)
    sp: object                  # StackedParts over the pr block rows
    chunk_src: np.ndarray       # [P, ME] int32 into [0, g*ni)
    chunk_dst: np.ndarray       # [P, ME] int32 into [0, ni]; ni = padding
    chunk_w: np.ndarray         # [P, ME] float32; 0 at padding
    n_edges_dev: np.ndarray     # [P] real edges per device chunk

    @property
    def n_devices(self) -> int:
        return self.pr * self.c

    @property
    def block_of_dev(self) -> np.ndarray:
        return np.repeat(np.arange(self.pr), self.c)

    @property
    def edges_total(self) -> int:
        return int(self.n_edges_dev.sum())


def build_spmm15d_layout(ps, task, spec) -> Spmm15dLayout:
    """Compile the 1.5D layout from an ordinary ``pr``-way partition.

    ``ps.num_parts`` is the block-row count ``pr``; the run needs
    ``pr * c`` devices and ``pr % c == 0`` (i.e. ``P % c**2 == 0``)."""
    from .exchange import stack_partitions

    c = spec.replication
    pr = ps.num_parts
    if pr % c:
        raise StrategyCapabilityError(
            f"spmm_15d with replication c={c} needs the block-row count "
            f"divisible by c (P % c**2 == 0); got pr={pr} block rows — "
            f"use {pr * c} devices with pr a multiple of {c}")
    g = pr // c
    sp = stack_partitions(ps, task, backend="edges")
    ni = sp.n_inner_max

    n = ps.graph.num_nodes
    owner_row = np.full(n, -1, np.int64)
    for part in ps.parts:
        owner_row[part.inner_nodes] = np.arange(part.n_inner)
    owner_part = ps.assign.astype(np.int64)

    chunks: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    for pt in ps.parts:
        src, dst = pt.local_graph.edges()
        keep = dst < pt.n_inner
        src, dst = src[keep], dst[keep]
        w = (pt.local_graph.edge_weight[keep]
             if pt.local_graph.edge_weight is not None
             else np.ones(src.shape[0], np.float32))
        gid = np.empty(src.shape[0], np.int64)
        inner = src < pt.n_inner
        gid[inner] = pt.inner_nodes[src[inner]]
        gid[~inner] = pt.halo_nodes[src[~inner] - pt.n_inner]
        k = owner_part[gid]
        src15 = ((k % g) * ni + owner_row[gid]).astype(np.int32)
        grp = k // g
        for j in range(c):
            sel = grp == j
            chunks.append((src15[sel], dst[sel].astype(np.int32),
                           w[sel].astype(np.float32)))

    p_dev = pr * c
    me = max(1, max(s.shape[0] for s, _, _ in chunks))
    chunk_src = np.zeros((p_dev, me), np.int32)
    chunk_dst = np.full((p_dev, me), ni, np.int32)   # ni row => dropped
    chunk_w = np.zeros((p_dev, me), np.float32)
    for d, (s, t, w) in enumerate(chunks):
        m = s.shape[0]
        chunk_src[d, :m] = s
        chunk_dst[d, :m] = t
        chunk_w[d, :m] = w
    n_edges_dev = np.array([s.shape[0] for s, _, _ in chunks], np.int64)
    return Spmm15dLayout(c=c, g=g, pr=pr, ni=ni, sp=sp,
                         chunk_src=chunk_src, chunk_dst=chunk_dst,
                         chunk_w=chunk_w, n_edges_dev=n_edges_dev)


def forward_collective_bytes_per_device(layout: Spmm15dLayout, cfg,
                                        spec) -> int:
    """Modeled per-device result-shape bytes of the forward collectives —
    the quantity :func:`repro.launch.hlo_cost.collective_bytes` measures on
    the lowered forward HLO: per layer one ``collective-permute``
    (``[ni, d]``, wire dtype; c > 1), one ``all-gather`` (``[g*ni, d]``,
    wire dtype; g > 1) and one ``all-reduce`` (``[ni, d]``, f32; c > 1).
    With ``exchange_layer0=False`` layer 0's permute/gather drop out (the
    gathered input features are pre-replicated at build time) while its
    partial-aggregation psum remains."""
    wire = 2 if spec.halo_dtype == "bf16" else 4
    c, g, ni = layout.c, layout.g, layout.ni
    total = 0
    for li, d in enumerate(cfg.feat_dims[:cfg.num_layers]):
        ship = spec.exchange_layer0 or li > 0
        if c > 1 and ship:
            total += ni * d * wire              # ppermute(grp<->repl)
        if g > 1 and ship:
            total += g * ni * d * wire          # all_gather(sub)
        if c > 1:
            total += ni * d * 4                 # psum(repl), f32
    return total


def step_bytes_total(layout: Spmm15dLayout, cfg, spec) -> int:
    """Modeled all-device wire bytes of one (refresh-equivalent) step —
    the 1.5D side of the head-to-head accounting in
    ``benchmarks/comm_volume.py``."""
    return layout.n_devices * forward_collective_bytes_per_device(
        layout, cfg, spec)


def vanilla_bytes_total(layout: Spmm15dLayout, cfg, spec) -> int:
    """The dense 1D baseline on the same block partitioning: every device
    all-gathers every block of H each layer (CAGNET 1D; what ``c == 1``
    costs).  The report's ``comm_reduction`` therefore isolates the
    replication benefit."""
    wire = 2 if spec.halo_dtype == "bf16" else 4
    dims = [d for li, d in enumerate(cfg.feat_dims[:cfg.num_layers])
            if spec.exchange_layer0 or li > 0]
    per_dev = sum(layout.pr * layout.ni * d * wire for d in dims)
    return layout.n_devices * per_dev


def make_spmm15d_mesh(c: int, g: int):
    """The ``(grp, sub, repl)`` = ``(c, g, c)`` device mesh (row-major —
    the order :class:`Spmm15dLayout`'s flat device index assumes)."""
    import jax
    return jax.make_mesh((c, g, c), AXES_15D)


@dataclasses.dataclass
class Spmm15dRuntime:
    """Jitted 1.5D runtime.  All step flavours are the same exact step
    (no staleness axis); the names exist so generic tooling can poke it
    like the halo runtimes."""
    cfg: object
    layout: Spmm15dLayout
    mesh: object
    spec: object
    step: Callable                  # (params, opt_state) -> (p, s, metrics)
    forward_fresh: Callable         # params -> [P, NI, out] logits
    evaluate: Callable              # (params, split) -> (loss, acc)
    lower_step: Callable            # (params, opt_state) -> Lowered
    lower_forward: Callable         # params -> Lowered
    step_bytes: int                 # modeled all-device bytes per step
    vanilla_bytes: int              # dense-1D baseline bytes per step
    forward_bytes_per_device: int   # modeled forward HLO collective bytes

    # step-flavour aliases: every 1.5D step is exact
    @property
    def step_refresh(self):
        return self.step

    @property
    def step_cached(self):
        return self.step

    @property
    def step_pipelined(self):
        return self.step


def make_spmm15d_runtime(cfg, layout: Spmm15dLayout, opt, spec,
                         mesh=None) -> Spmm15dRuntime:
    """Build the jitted 1.5D step over ``mesh`` (built from the layout's
    ``(c, g, c)`` shape when omitted).  Requires ``layout.n_devices``
    visible devices; params/opt state are replicated and donated
    (``spec.donate``) so steady-state steps update in place."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.gnn import accuracy, cross_entropy_loss
    from .capgnn_sim import halo_dtype_info

    if cfg.model not in Spmm15DStrategy.caps.models:
        raise StrategyCapabilityError(
            f"spmm_15d implements models {Spmm15DStrategy.caps.models}, "
            f"not {cfg.model!r}; use strategy='halo_1d' for the others")
    c, g, pr, ni = layout.c, layout.g, layout.pr, layout.ni
    p_dev = layout.n_devices
    if mesh is None:
        if len(jax.devices()) < p_dev:
            raise StrategyCapabilityError(
                f"spmm_15d with pr={pr}, c={c} needs {p_dev} devices "
                f"({len(jax.devices())} visible) — force host devices "
                "via XLA_FLAGS=--xla_force_host_platform_device_count")
        mesh = make_spmm15d_mesh(c, g)
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if (tuple(mesh.axis_names) != AXES_15D
            or (shape["grp"], shape["sub"], shape["repl"]) != (c, g, c)):
        raise ValueError(f"spmm_15d needs a {AXES_15D} = ({c}, {g}, {c}) "
                         f"mesh, got axes {mesh.axis_names} of shape "
                         f"{mesh.devices.shape}")
    hdt, _ = halo_dtype_info(spec.halo_dtype)
    layers = cfg.num_layers
    sp = layout.sp
    rep = lambda x: np.repeat(np.asarray(x), c, axis=0)   # noqa: E731

    data = {"feats": rep(sp.feats),
            "labels": rep(sp.labels.astype(np.int32)),
            "train_mask": rep(sp.train_mask), "val_mask": rep(sp.val_mask),
            "test_mask": rep(sp.test_mask),
            "src": layout.chunk_src, "dst": layout.chunk_dst,
            "w": layout.chunk_w}
    if not spec.exchange_layer0:
        # pre-replicated inputs: each device ships with its group's
        # gathered layer-0 block instead of exchanging it per step
        f = sp.feats.shape[-1]
        hg0 = np.zeros((p_dev, g * ni, f), np.float32)
        for i in range(pr):
            for j in range(c):
                blocks = sp.feats[j * g:(j + 1) * g].reshape(g * ni, f)
                hg0[i * c + j] = blocks
        data["hg0"] = hg0
    # placed once, one block per device: no step call reshards them
    data = jax.device_put(jax.tree.map(jnp.asarray, data),
                          NamedSharding(mesh, P(AXES_15D)))

    total_train = float(np.maximum(sp.train_mask.sum(), 1.0))
    swap = [(a * c + j, j * c + a) for a in range(c) for j in range(c)]

    def _gather_group(h):
        """permute(grp<->repl) + all_gather(sub): [ni, d] -> [g*ni, d]
        holding every block of this device's source group."""
        hw = h.astype(hdt) if hdt is not None else h
        if c > 1:
            hw = jax.lax.ppermute(hw, ("grp", "repl"), swap)
        if g > 1:
            hw = jax.lax.all_gather(hw, "sub", tiled=True)
        return hw.astype(h.dtype)

    def _device_forward(params, dsh):
        src, dst, w = dsh["src"][0], dsh["dst"][0], dsh["w"][0]
        h = dsh["feats"][0]                                    # [ni, d]
        for li, lp in enumerate(params):
            if li == 0 and not spec.exchange_layer0:
                hg = dsh["hg0"][0]
            else:
                hg = _gather_group(h)
            msgs = hg[src] * w[:, None]
            agg = jax.ops.segment_sum(msgs, dst, num_segments=ni + 1)[:ni]
            if c > 1:
                agg = jax.lax.psum(agg, "repl")
            if cfg.model == "gcn":
                z = agg @ lp["w"] + lp["b"]
            else:                                              # gin
                z = (1.0 + lp["eps"]) * h + agg
                z = jax.nn.relu(z @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
            h = z if li == layers - 1 else jax.nn.relu(z)
        return h

    def _device_loss(params, dsh):
        """This device's share of the (c-fold replicated) loss sum.  The
        psum stays OUTSIDE the differentiated function — see the module
        docstring for why the all-axis grad psum carries one uniform
        factor c that the step divides back out."""
        logits = _device_forward(params, dsh)
        labels = dsh["labels"][0]
        mask = dsh["train_mask"][0]
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]
        return jnp.sum(nll * mask) / total_train, logits

    def _device_step(params, opt_state, dsh):
        (loss, logits), grads = jax.value_and_grad(
            _device_loss, has_aux=True)(params, dsh)
        loss = jax.lax.psum(loss, AXES_15D) / c
        grads = jax.tree.map(lambda gr: jax.lax.psum(gr, AXES_15D) / c,
                             grads)
        new_params, new_state = opt.update(grads, opt_state, params)
        labels = dsh["labels"][0]
        mask = dsh["train_mask"][0]
        correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        acc = jax.lax.psum(jnp.sum(correct * mask),
                           AXES_15D) / (c * total_train)
        return new_params, new_state, {"loss": loss, "acc": acc}

    names3 = AXES_15D
    sm_step = jax.shard_map(_device_step, mesh=mesh,
                            in_specs=(P(), P(), P(names3)),
                            out_specs=(P(), P(), {"loss": P(), "acc": P()}),
                            check_vma=False)
    sm_fwd = jax.shard_map(
        lambda params, dsh: _device_forward(params, dsh)[None],
        mesh=mesh, in_specs=(P(), P(names3)), out_specs=P(names3),
        check_vma=False)
    jit_step = jax.jit(lambda params, opt_state, dsh:
                       sm_step(params, opt_state, dsh),
                       donate_argnums=(0, 1) if spec.donate else ())
    jit_fwd = jax.jit(sm_fwd)

    def step(params, opt_state):
        return jit_step(params, opt_state, data)

    def forward_fresh(params):
        return jit_fwd(params, data)

    labels_flat = jnp.asarray(rep(sp.labels.astype(np.int32))).reshape(-1)
    masks_flat = {k: jnp.asarray(rep(m)).reshape(-1)
                  for k, m in (("train", sp.train_mask),
                               ("val", sp.val_mask),
                               ("test", sp.test_mask))}

    def evaluate(params, split: str = "val"):
        # rows are c-fold replicated; the masked means are unaffected
        flat = forward_fresh(params).reshape(-1, cfg.out_dim)
        m = masks_flat[split]
        return (float(cross_entropy_loss(flat, labels_flat, m)),
                float(accuracy(flat, labels_flat, m)))

    return Spmm15dRuntime(
        cfg=cfg, layout=layout, mesh=mesh, spec=spec, step=step,
        forward_fresh=forward_fresh, evaluate=evaluate,
        lower_step=lambda params, opt_state:
            jit_step.lower(params, opt_state, data),
        lower_forward=lambda params: jit_fwd.lower(params, data),
        step_bytes=step_bytes_total(layout, cfg, spec),
        vanilla_bytes=vanilla_bytes_total(layout, cfg, spec),
        forward_bytes_per_device=forward_collective_bytes_per_device(
            layout, cfg, spec))


def train_spmm15d(cfg, runtime: Spmm15dRuntime, opt, spec, epochs: int,
                  eval_every: int = 0, seed: int = 0, params0=None,
                  opt_state0=None):
    """The 1.5D training loop: every step is an exact refresh-equivalent
    step; byte accounting is the modeled figure (== HLO-measured, gated
    by the comm_volume suite).  Returns the same
    :class:`~repro.dist.capgnn_sim.TrainReport` shape as ``train_capgnn``
    (``comm_bytes_vanilla`` is the dense-1D baseline on the same
    blocks)."""
    import jax
    from repro.models.gnn import init_gnn
    from .capgnn_sim import TrainReport

    params = params0 if params0 is not None else init_gnn(
        jax.random.PRNGKey(seed), cfg)
    opt_state = opt_state0 if opt_state0 is not None else opt.init(params)
    losses: list[float] = []
    val_acc: list[float] = []
    compile_s = 0.0
    t0 = time.perf_counter()
    for e in range(epochs):
        params, opt_state, m = runtime.step(params, opt_state)
        losses.append(float(m["loss"]))
        if e == 0:
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
        if eval_every and (e + 1) % eval_every == 0:
            val_acc.append(runtime.evaluate(params, "val")[1])
    wall = time.perf_counter() - t0
    comm = runtime.step_bytes * epochs
    vanilla = runtime.vanilla_bytes * epochs
    report = TrainReport(
        losses=losses, val_acc=val_acc, comm_bytes=comm,
        comm_bytes_vanilla=vanilla,
        comm_reduction=1.0 - comm / max(vanilla, 1),
        refresh_steps=epochs, cached_steps=0, wall_time_s=wall,
        final_opt_state=opt_state, compile_s=compile_s,
        spec=spec.to_dict() if spec is not None else None)
    return params, report


class Spmm15DStrategy:
    """Registry entry for the 1.5D replicated-row SpMM model."""
    name = "spmm_15d"
    caps = StrategyCaps(jaca_tiers=False, pipeline=False,
                        host_features=False, adaptive_cache=False,
                        fault_guard=False, sim_runtime=False,
                        transports=("mesh_collectives",),
                        backends=("edges",),
                        models=("gcn", "gin"),
                        replicated=True)

    def validate_spec(self, spec) -> None:
        def deny(cond: bool, what: str):
            if cond:
                raise StrategyCapabilityError(
                    f"spmm_15d does not support {what} — that is halo_1d "
                    "machinery (see the strategy capability matrix in the "
                    "README); every spmm_15d step is refresh-equivalent "
                    "and exact")
        deny(spec.features != "device", f"features={spec.features!r}")
        deny(spec.pipeline, "pipeline=True (overlapped refresh)")
        deny(spec.cache_policy != "static",
             f"cache_policy={spec.cache_policy!r} (adaptive caching)")
        deny(spec.refresh_every != 1,
             f"refresh_every={spec.refresh_every} (bounded staleness)")
        deny(spec.backend != "edges", f"backend={spec.backend!r}")
        deny(bool(spec.faults) or spec.guard_every > 0 or spec.checksums
             or spec.fetch_retries is not None,
             "fault injection / guard defenses")
        deny(spec.pallas_pack, "pallas_pack (p2p peer packing)")

    def build_layout(self, ps, task, spec, **kw) -> Spmm15dLayout:
        return build_spmm15d_layout(ps, task, spec)

    def make_sim_runtime(self, cfg, layout, opt, spec, **kw):
        raise StrategyCapabilityError(
            "spmm_15d has no single-device sim runtime; parity checks "
            "run against the halo_1d sim oracle at refresh_every=1 "
            "(see tests/spmm15d_parity_script.py)")

    def make_spmd_runtime(self, cfg, layout, opt, spec, mesh=None, **kw):
        return make_spmm15d_runtime(cfg, layout, opt, spec, mesh=mesh)

    def train(self, cfg, runtime, layout, opt, spec, epochs, **kw):
        return train_spmm15d(cfg, runtime, opt, spec, epochs, **kw)

    def step_bytes(self, layout, cfg, spec) -> int:
        return step_bytes_total(layout, cfg, spec)

    def forward_collective_bytes(self, layout, cfg, spec,
                                 mesh_size=None) -> int:
        return forward_collective_bytes_per_device(layout, cfg, spec)


SPMM_15D = Spmm15DStrategy()
