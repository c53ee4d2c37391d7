"""Training launchers.

Two entry points:

- ``python -m repro.launch.train gnn ...``  — CaPGNN full-batch GNN
  training (the paper's workload): partitions, JACA plan, RAPA balance,
  staleness schedule, byte accounting.
- ``python -m repro.launch.train lm --arch <id> ...`` — token-LM training
  for the architecture-zoo configs (reduced or full), single host.

``gnn`` runs the partitions stacked on one device (``halo_1d``) or over a
device mesh (``--strategy spmm_15d``); ``chip_smoke.py`` drives it at
Flickr's published shape on a TPU.  The LM mesh path is exercised by
``repro.launch.dryrun``.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def run_gnn(args) -> dict:
    import jax
    from repro.core import (PROFILES, PAPER_GROUPS, make_group, cal_capacity,
                            build_cache_plan, do_partition, RapaConfig,
                            CacheCapacity, StalenessController,
                            AdaptivePlanner, capability_weights)
    from repro.data import make_task
    from repro.dist import (build_exchange_plan, stack_partitions,
                            make_sim_runtime, train_capgnn)
    from repro.graph import metis_partition, random_partition, build_partition
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.optim import adam

    from repro.dist.spec import TrainSpec
    from repro.dist.strategy import StrategyCapabilityError, get_strategy

    task = make_task(args.dataset, scale=args.scale, feat_dim=args.feat_dim,
                     seed=args.seed)
    g = task.graph
    p = args.parts

    # one constructor path for the whole config surface: CLI flags ->
    # TrainSpec (validated, including strategy capability checks)
    try:
        spec = TrainSpec.from_cli_args(args)
        strat = get_strategy(spec.strategy)
    except ValueError as e:       # includes StrategyCapabilityError
        raise SystemExit(str(e))
    is_15d = spec.strategy == "spmm_15d"
    c = spec.replication
    if is_15d and p % (c * c):
        raise SystemExit(
            f"spmm_15d needs --parts divisible by replication**2 "
            f"(P % c**2 == 0): got --parts={p} --replication={c}")
    # under spmm_15d --parts is the total device count P; the graph is
    # partitioned into the pr = P / c block rows
    n_parts = p // c if is_15d else p

    # device group first: with --uneven the profile shapes the partition
    # sizes (RAPA's resource-aware pre-partition), not just the pruning
    group = getattr(args, "group", "auto")
    if group == "auto":
        group = f"x{n_parts}" if f"x{n_parts}" in PAPER_GROUPS else "uniform"
    profiles = ([PROFILES["rtx3090"]] * n_parts if group == "uniform"
                else make_group(PAPER_GROUPS[group]))
    if len(profiles) != n_parts:
        raise SystemExit(f"device group {group!r} has {len(profiles)} "
                         f"devices but the run needs {n_parts} partitions")

    uneven = getattr(args, "uneven", True)
    weights = capability_weights(profiles) if uneven else None
    part_fn = {"metis": metis_partition, "random": random_partition}[args.partitioner]
    assign = part_fn(g, n_parts, seed=args.seed, weights=weights)
    ps = build_partition(g, assign, hops=1, parts=n_parts)
    if args.rapa:
        res = do_partition(ps, profiles, RapaConfig(feat_dim=args.feat_dim))
        ps = res.partition_set

    cfg = GNNConfig(model=args.model, in_dim=task.features.shape[1],
                    hidden_dim=args.hidden, out_dim=task.num_classes,
                    num_layers=args.layers)
    if is_15d:
        try:
            return _run_gnn_15d(args, spec, strat, task, ps, cfg, group,
                                uneven)
        except StrategyCapabilityError as e:
            raise SystemExit(str(e))
    if args.jaca:
        cap = cal_capacity(ps, cfg.feat_dims, profiles,
                           m_cpu_gib=args.cpu_cache_gib)
    else:
        cap = CacheCapacity(c_gpu=[0] * p, c_cpu=0)
    cache_policy = getattr(args, "cache_policy", "static")
    planner = None
    if cache_policy != "static":
        # online adaptation: the planner owns the initial plan AND the
        # slot-stable capacity padding, so the runtime's installed plan and
        # the planner's hit/drift accounting can never desync
        planner = AdaptivePlanner(ps, cap, refresh_every=args.refresh_every,
                                  policy=cache_policy, seed=args.seed)
        xplan = planner.exchange_plan()
    else:
        plan = build_cache_plan(ps, cap, refresh_every=args.refresh_every)
        xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task, backend=args.backend)
    opt = adam(args.lr)
    halo_dtype = spec.halo_dtype
    features = spec.features
    prefetch_depth = spec.prefetch_depth
    runtime = make_sim_runtime(cfg, sp, xplan, opt, spec=spec)
    ctl = StalenessController(refresh_every=args.refresh_every,
                              adaptive=args.adaptive_staleness,
                              replan_every=getattr(args, "replan_every", 1))

    # --resume: restore (params, opt_state, epoch) and run the remaining
    # epochs; --epochs is the *total* budget across runs.
    start_epoch, params0, opt_state0 = 0, None, None
    if args.resume and args.ckpt_dir:
        from repro.checkpoint import latest_step, load_checkpoint
        step = latest_step(args.ckpt_dir)
        if step is not None:
            like = init_gnn(jax.random.PRNGKey(args.seed), cfg)
            state = load_checkpoint(args.ckpt_dir, step,
                                    {"params": like,
                                     "opt_state": opt.init(like)})
            params0, opt_state0 = state["params"], state["opt_state"]
            start_epoch = step
    run_epochs = max(0, args.epochs - start_epoch)

    # fault injection + graceful degradation (repro.faults): a --faults
    # spec enables seeded injectors; any of the defense knobs builds a
    # TrainGuard even without injected faults (defense-only runs)
    faults_spec = getattr(args, "faults", "")
    guard_every = int(getattr(args, "guard_every", 0) or 0)
    fetch_retries = getattr(args, "fetch_retries", None)
    checksums = bool(getattr(args, "checksums", False))
    faults = guard = None
    if faults_spec:
        from repro.faults import FaultPlan
        faults = FaultPlan.parse(faults_spec, seed=args.seed)
    if (faults is not None or guard_every or checksums
            or fetch_retries is not None):
        from repro.faults import GuardConfig
        guard = GuardConfig(
            guard_every=guard_every,
            fetch_retries=(2 if fetch_retries is None
                           else int(fetch_retries)),
            checksums=checksums)

    device_trace_dir = getattr(args, "device_trace_dir", "")
    tracer = None
    if getattr(args, "trace", False) or device_trace_dir:
        # an enabled tracer's spans land in the device capture too
        from repro.obs import Tracer
        tracer = Tracer()
    from repro.obs import device_trace
    with device_trace(device_trace_dir):
        params, report = train_capgnn(cfg, runtime, xplan, p, opt,
                                      epochs=run_epochs, controller=ctl,
                                      spec=spec,
                                      params0=params0, opt_state0=opt_state0,
                                      planner=planner, tracer=tracer,
                                      faults=faults, guard=guard)
    _, test_acc = runtime.evaluate(params, "test")
    out = {
        "dataset": args.dataset, "model": args.model, "parts": p,
        "strategy": spec.strategy, "replication": spec.replication,
        "group": group, "uneven": bool(uneven),
        "inner_sizes": [pt.n_inner for pt in ps.parts],
        "stack_waste_frac": runtime.padding_stats().get("waste_frac"),
        "epochs": args.epochs, "resumed_from": start_epoch,
        "final_loss": report.losses[-1] if report.losses else None,
        "losses": report.losses,
        "halo_dtype": halo_dtype,
        "features": features, "prefetch_depth": prefetch_depth,
        "host_fetch_rows": report.host_fetch_rows,
        "host_fetch_bytes": report.host_fetch_bytes,
        "host_writeback_bytes": report.host_writeback_bytes,
        "cache_policy": cache_policy,
        "replan_events": report.replan_events,
        "planner_hit_rate": report.hit_rate,
        "test_acc": test_acc, "comm_bytes": report.comm_bytes,
        "comm_reduction_vs_vanilla": report.comm_reduction,
        "refresh_steps": report.refresh_steps,
        "cached_steps": report.cached_steps,
        # compile_s is the fenced step-0 time; wall_time_s is steady state
        "compile_s": round(report.compile_s, 3),
        "wall_time_s": round(report.wall_time_s, 2),
    }
    if report.fault_events is not None:
        out["faults"] = (faults.spec_string() if faults is not None else "")
        out["faults_injected"] = report.faults_injected
        out["fault_events"] = report.fault_events
    if getattr(args, "trace", False):
        paths = tracer.export(args.trace_dir, prefix="train")
        out["phase_stats"] = report.phase_stats
        out["trace_file"] = paths["trace"]
        out["metrics_file"] = paths["metrics"]
    print(json.dumps(out, indent=1))
    if args.ckpt_dir:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt_dir, start_epoch + run_epochs,
                        {"params": params,
                         "opt_state": report.final_opt_state})
    return out


def _run_gnn_15d(args, spec, strat, task, ps, cfg, group, uneven) -> dict:
    """The ``--strategy spmm_15d`` branch of ``run_gnn``: 1.5D replicated-
    row block SpMM over a real ``(grp, sub, repl)`` device mesh.  Needs
    ``--parts`` visible devices (force host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=P`` on CPU).
    Every step is exact (refresh-equivalent), so the staleness/caching
    flags do not apply — ``TrainSpec.from_cli_args`` normalises them away
    and the capability validation rejects explicit halo-only requests."""
    import jax
    from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
    from repro.models.gnn import init_gnn
    from repro.optim import adam

    p = args.parts
    if len(jax.devices()) < p:
        raise SystemExit(
            f"spmm_15d with --parts={p} needs {p} devices but only "
            f"{len(jax.devices())} are visible; on CPU force host devices "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count={p}")
    layout = strat.build_layout(ps, task, spec)
    opt = adam(args.lr)
    runtime = strat.make_spmd_runtime(cfg, layout, opt, spec)

    start_epoch, params0, opt_state0 = 0, None, None
    if args.resume and args.ckpt_dir:
        step = latest_step(args.ckpt_dir)
        if step is not None:
            like = init_gnn(jax.random.PRNGKey(args.seed), cfg)
            state = load_checkpoint(args.ckpt_dir, step,
                                    {"params": like,
                                     "opt_state": opt.init(like)})
            params0, opt_state0 = state["params"], state["opt_state"]
            start_epoch = step
    run_epochs = max(0, args.epochs - start_epoch)

    params, report = strat.train(cfg, runtime, layout, opt, spec,
                                 epochs=run_epochs, seed=args.seed,
                                 params0=params0, opt_state0=opt_state0)
    _, test_acc = runtime.evaluate(params, "test")
    out = {
        "dataset": args.dataset, "model": args.model, "parts": p,
        "strategy": spec.strategy, "replication": spec.replication,
        "block_rows": layout.pr, "group_size": layout.g,
        "group": group, "uneven": bool(uneven),
        "inner_sizes": [pt.n_inner for pt in ps.parts],
        "epochs": args.epochs, "resumed_from": start_epoch,
        "final_loss": report.losses[-1] if report.losses else None,
        "losses": report.losses,
        "halo_dtype": spec.halo_dtype,
        "test_acc": test_acc, "comm_bytes": report.comm_bytes,
        # vanilla = dense 1D full-H all-gather on the same block rows, so
        # the reduction isolates the replication benefit
        "comm_reduction_vs_vanilla": report.comm_reduction,
        "fwd_collective_bytes_per_device": runtime.forward_bytes_per_device,
        "refresh_steps": report.refresh_steps,
        "cached_steps": report.cached_steps,
        "compile_s": round(report.compile_s, 3),
        "wall_time_s": round(report.wall_time_s, 2),
    }
    print(json.dumps(out, indent=1))
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, start_epoch + run_epochs,
                        {"params": params,
                         "opt_state": report.final_opt_state})
    return out


def run_lm(args) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, get_reduced
    from repro.data import synthetic_token_batches
    from repro.models.transformer import init_model, train_step_fn
    from repro.optim import adamw

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    params = init_model(jax.random.PRNGKey(args.seed), cfg)
    opt = adamw(args.lr)
    opt_state = opt.init(params)
    start_step = 0
    if args.resume and args.ckpt_dir:
        from repro.checkpoint import latest_step, load_checkpoint
        s = latest_step(args.ckpt_dir)
        if s is not None:
            state = load_checkpoint(args.ckpt_dir, s,
                                    {"params": params, "opt_state": opt_state})
            params, opt_state = state["params"], state["opt_state"]
            start_step = s
    run_steps = max(0, args.steps - start_step)
    step = jax.jit(train_step_fn(cfg, opt))
    gen = synthetic_token_batches(cfg.vocab_size, args.seq_len, args.batch,
                                  seed=args.seed)
    for _ in range(start_step):   # resume the data stream where we left off
        next(gen)
    losses = []
    t0 = time.perf_counter()
    for i, host_batch in zip(range(run_steps), gen):
        batch = {"tokens": jnp.asarray(host_batch["tokens"]),
                 "labels": jnp.asarray(host_batch["labels"])}
        if cfg.vision_tokens:
            batch["patches"] = jnp.zeros(
                (args.batch, cfg.vision_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    wall = time.perf_counter() - t0
    out = {"arch": cfg.name, "steps": args.steps,
           "resumed_from": start_step,
           "loss_first": losses[0] if losses else None,
           "loss_last": losses[-1] if losses else None,
           "tokens_per_s":
           round(run_steps * args.batch * args.seq_len / max(wall, 1e-9), 1)}
    print(json.dumps(out, indent=1))
    if args.ckpt_dir:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt_dir, start_step + run_steps,
                        {"params": params, "opt_state": opt_state})
    return out


def build_parser() -> argparse.ArgumentParser:
    """The ``gnn`` / ``lm`` command line (``main`` parses ``sys.argv``)."""
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)

    g = sub.add_parser("gnn")
    g.add_argument("--dataset", default="flickr")
    g.add_argument("--scale", type=float, default=0.02)
    g.add_argument("--feat-dim", type=int, default=64)
    g.add_argument("--model", default="gcn",
                   choices=["gcn", "sage", "gat", "gin"])
    g.add_argument("--strategy", default="halo_1d",
                   choices=["halo_1d", "spmm_15d"],
                   help="distribution model (repro.dist.strategy): "
                        "'halo_1d' is the paper's 1D vertex partition + "
                        "halo exchange (JACA/staleness/host-store "
                        "capable); 'spmm_15d' is communication-avoiding "
                        "1.5D replicated-row block SpMM over a real "
                        "device mesh — --parts is then the total device "
                        "count P, partitioned into P/c block rows")
    g.add_argument("--replication", type=int, default=1,
                   help="1.5D row-replication factor c (spmm_15d only; "
                        "needs P %% c**2 == 0). c=1 degenerates to dense "
                        "1D all-gather")
    g.add_argument("--backend", default="edges",
                   choices=["edges", "ell", "hybrid"],
                   help="local aggregation backend (ell/hybrid run the "
                        "Pallas SpMM; interpret mode on CPU)")
    g.add_argument("--halo-dtype", default="f32", choices=["f32", "bf16"],
                   help="halo payload dtype on the wire: bf16 halves every "
                        "tier's exchange bytes (dequantised on scatter)")
    g.add_argument("--features", default="device",
                   choices=["device", "host"],
                   help="'host' keeps the halo feature/embedding table in a "
                        "host-resident store (out-of-core): layer-0 rows "
                        "arrive via double-buffered h2d prefetch, global-tier "
                        "buffers live on the host between steps")
    g.add_argument("--prefetch-depth", type=int, default=2,
                   help="host-store double-buffer depth (in-flight h2d "
                        "fetches; 2 = classic double buffering)")
    g.add_argument("--hidden", type=int, default=256)
    g.add_argument("--layers", type=int, default=3)
    g.add_argument("--parts", type=int, default=4)
    g.add_argument("--partitioner", default="metis",
                   choices=["metis", "random"])
    g.add_argument("--epochs", type=int, default=200)
    g.add_argument("--lr", type=float, default=0.01)
    g.add_argument("--jaca", action="store_true", default=True)
    g.add_argument("--no-jaca", dest="jaca", action="store_false")
    g.add_argument("--rapa", action="store_true", default=True)
    g.add_argument("--no-rapa", dest="rapa", action="store_false")
    g.add_argument("--uneven", action="store_true", default=True,
                   help="profile-weighted uneven partition sizes (RAPA "
                        "resource-aware pre-partition; weakest device gets "
                        "the smallest inner set)")
    g.add_argument("--even", dest="uneven", action="store_false",
                   help="uniform partition targets regardless of profile")
    from repro.core.device_profile import PAPER_GROUPS
    g.add_argument("--group", default="auto",
                   choices=["auto", "uniform"] + sorted(PAPER_GROUPS),
                   help="device group: a paper Table 4 group (x2..x8), "
                        "'uniform' (all rtx3090), or 'auto' (x<parts> if "
                        "defined, else uniform)")
    g.add_argument("--pipeline", action="store_true", default=True)
    g.add_argument("--no-pipeline", dest="pipeline", action="store_false")
    g.add_argument("--refresh-every", type=int, default=4)
    g.add_argument("--cache-policy", default="static",
                   choices=["static", "overlap", "lru", "fifo", "drift"],
                   help="online cache adaptation: 'static' freezes the "
                        "JACA overlap plan; the others re-rank tiers at "
                        "refresh boundaries (slot-stable swap, no retrace)")
    g.add_argument("--replan-every", type=int, default=1,
                   help="re-rank every k-th refresh (adaptive policies)")
    g.add_argument("--adaptive-staleness", action="store_true")
    g.add_argument("--cpu-cache-gib", type=float, default=4.0)
    g.add_argument("--trace", action="store_true",
                   help="enable the repro.obs tracer: per-step spans + "
                        "typed counters, exported as a Perfetto-loadable "
                        "Chrome trace and a JSONL metrics stream")
    g.add_argument("--trace-dir", default="experiments",
                   help="directory for trace_train.json / "
                        "metrics_train.jsonl (with --trace)")
    g.add_argument("--device-trace-dir", default="",
                   help="opt-in jax.profiler.trace capture directory for "
                        "device-side timelines (XPlane; open in "
                        "TensorBoard/Perfetto); turns the repro.obs "
                        "tracer on, so the capture holds its spans")
    g.add_argument("--faults", default="",
                   help="fault-injection spec, e.g. "
                        "'grad_nan@3;fetch_drop@2,5:rows=4' — clauses "
                        "kind@step,step[:key=val,...] joined by ';' "
                        "(kinds: fetch_drop fetch_delay halo_corrupt "
                        "grad_nan mem_pressure ckpt_truncate); seeded "
                        "by --seed, deterministic")
    g.add_argument("--guard-every", type=int, default=0,
                   help="divergence guard cadence: check param finiteness "
                        "and snapshot a rollback point every k steps "
                        "(0 = guard off; non-finite losses are checked "
                        "every step when on)")
    g.add_argument("--fetch-retries", type=int, default=None,
                   help="bounded retries for failed host-store fetches "
                        "before degrading to stale-tier reuse (enables "
                        "the fetch guard; default 2 when any fault/guard "
                        "flag is set)")
    g.add_argument("--checksums", action="store_true",
                   help="per-tier payload checksums on exchange/cache "
                        "buffers: verify before each step, force a plain "
                        "refresh of corrupted tiers (opt-in: adds a fenced "
                        "d2h digest per step)")
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--ckpt-dir", default="")
    g.add_argument("--resume", action="store_true",
                   help="restore (params, opt_state, epoch) from the latest "
                        "checkpoint in --ckpt-dir and train the remaining "
                        "epochs up to --epochs")
    g.set_defaults(fn=run_gnn)

    l = sub.add_parser("lm")
    l.add_argument("--arch", required=True)
    l.add_argument("--reduced", action="store_true", default=True)
    l.add_argument("--full", dest="reduced", action="store_false")
    l.add_argument("--steps", type=int, default=20)
    l.add_argument("--batch", type=int, default=4)
    l.add_argument("--seq-len", type=int, default=128)
    l.add_argument("--lr", type=float, default=3e-4)
    l.add_argument("--seed", type=int, default=0)
    l.add_argument("--ckpt-dir", default="")
    l.add_argument("--resume", action="store_true",
                   help="restore (params, opt_state, step) from the latest "
                        "checkpoint in --ckpt-dir and run the remaining "
                        "steps up to --steps")
    l.set_defaults(fn=run_lm)
    return ap


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = build_parser().parse_args()
    args.fn(args)


if __name__ == "__main__":
    main()
