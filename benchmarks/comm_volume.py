"""Paper Figs. 16-19: epoch time & communication volume vs cache capacity,
plus the overhead / benefit-to-overhead ratios of the caching machinery —
and the halo-transport sweep: modeled vs HLO-measured wire bytes and
pipelined vs unpipelined step time for ``transport="allgather" | "p2p"``.

Byte counts are exact (plan properties); wall time is CPU wall time of the
compiled stacked runtime.  The paper's check_cache/pick_cache bookkeeping
maps here to (a) the host-side plan build and (b) the cache scatter/gather
ops inside the step; (a) is measured directly, (b) rides in the step time.

The transport sweep needs a multi-device mesh, so it re-execs this module
in a subprocess with ``--xla_force_host_platform_device_count=4`` and
merges the child's JSON into ``experiments/comm_volume.json``.  The child
counts bytes and never times anything, so it runs with
``JAX_PLATFORMS=cpu``: on a chip host the parent holds the TPU.
``REPRO_BENCH_TINY=1`` shrinks both parts for CI smoke runs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.core import (CacheCapacity, StalenessController, build_cache_plan,
                        comm_bytes_per_step)
from repro.dist import (TrainSpec, build_exchange_plan, make_sim_runtime,
                        stack_partitions, train_capgnn)
from repro.graph import build_partition, metis_partition
from repro.models.gnn import GNNConfig
from repro.optim import adam
from ._util import BENCH_SCALE, DEFAULT_OUT, Timer, bench_task, save

EPOCHS = 12


def _one(task, ps, cap_frac: float, parts: int, refresh_every: int = 4,
         epochs: int = EPOCHS):
    max_halo = max(pt.n_halo for pt in ps.parts)
    cap = max(0, int(cap_frac * max_halo))
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=128, out_dim=task.num_classes, num_layers=3)
    with Timer() as t_plan:
        capc = CacheCapacity(c_gpu=[cap] * parts, c_cpu=cap * parts)
        plan = build_cache_plan(ps, capc, refresh_every=refresh_every)
        xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task)
    opt = adam(0.01)
    spec = TrainSpec(refresh_every=refresh_every)
    runtime = make_sim_runtime(cfg, sp, xplan, opt, spec=spec)
    ctl = StalenessController(refresh_every=refresh_every)
    with Timer() as t_train:
        _, rep = train_capgnn(cfg, runtime, xplan, parts, opt, epochs=epochs,
                              controller=ctl, eval_every=0, spec=spec)
    vol = comm_bytes_per_step(plan, cfg.hidden_dim,
                              dtype_bytes=runtime.halo_dtype_bytes)
    return {
        "cap_frac": cap_frac, "capacity": cap,
        "epoch_time_s": t_train.seconds / epochs,
        "plan_build_s": t_plan.seconds,
        "comm_bytes": rep.comm_bytes,
        "comm_bytes_vanilla": rep.comm_bytes_vanilla,
        "comm_reduction": rep.comm_reduction,
        "amortised_bytes_per_step": vol["amortised_bytes"],
    }


# ------------------------------------------------------- transport sweep

def _time_step(fn, params, opt, cfg, xplan, parts, repeats: int = 5,
               inner: int = 2) -> float:
    """Best-of-``repeats`` per-step seconds of a donated jitted step,
    chaining the returned state (steady-state loop)."""
    import jax
    import jax.numpy as jnp
    from repro.dist import init_caches

    pp = jax.tree.map(jnp.copy, params)
    oo = opt.init(pp)
    cc = init_caches(cfg, xplan, parts)
    pp, oo, cc, m = fn(pp, oo, cc)          # compile + warm-up
    jax.block_until_ready(m["loss"])
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            pp, oo, cc, m = fn(pp, oo, cc)
        jax.block_until_ready(m["loss"])
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def transport_sweep(tiny: bool, transports=("allgather", "p2p")) -> dict:
    """Runs in the forced-4-device child process: modeled vs HLO-measured
    wire bytes and pipelined vs unpipelined step time per transport on the
    flickr-scale benchmark config."""
    import jax
    jax.devices()           # lock the forced host device count first
    import jax.numpy as jnp
    from repro.core import PROFILES, cal_capacity
    from repro.data import make_task
    from repro.dist import TrainSpec, init_caches
    from repro.dist.capgnn_spmd import make_spmd_runtime
    from repro.launch.hlo_cost import collective_bytes
    from repro.models.gnn import init_gnn
    from repro.optim import adam as mk_adam

    parts = 4
    scale = BENCH_SCALE["flickr"] / (8 if tiny else 1)
    task = make_task("flickr", scale=scale, feat_dim=64)
    ps = build_partition(task.graph,
                         metis_partition(task.graph, parts, seed=0), hops=1)
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=128, out_dim=task.num_classes, num_layers=3)
    cap = cal_capacity(ps, cfg.feat_dims, [PROFILES["rtx3090"]] * parts)
    plan = build_cache_plan(ps, cap, refresh_every=4)
    xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task)
    opt = mk_adam(0.01)
    mesh = jax.make_mesh((parts,), ("data",))
    params = init_gnn(jax.random.PRNGKey(0), cfg)

    out = {"parts": parts, "num_nodes": int(task.graph.num_nodes),
           "tiny": bool(tiny), "transports": {}}
    for transport in transports:
        rt = make_spmd_runtime(cfg, sp, xplan, opt, mesh,
                               spec=TrainSpec(transport=transport))
        row = {}
        for refresh, key in ((False, "cached"), (True, "refresh")):
            row[f"modeled_{key}_bytes"] = sum(
                xplan.bytes_per_step(d, refresh=refresh,
                                     dtype_bytes=rt.halo_dtype_bytes)
                for d in rt.comm_dims)
            row[f"{key}_rows"] = rt.wire_rows(refresh)
            row[f"{key}_rows_padded"] = rt.wire_rows(refresh, padded=True)
        # HLO-measured per-device collective bytes of one compiled step
        # (includes static-shape padding and grad-transpose collectives)
        pp = jax.tree.map(jnp.copy, params)
        oo = opt.init(pp)
        cc = init_caches(cfg, xplan, parts)
        for name in ("cached", "refresh", "pipelined"):
            hlo = rt.lower_step(name, pp, oo, cc).compile().as_text()
            cb = collective_bytes(hlo)
            row[f"hlo_{name}_collective_bytes_per_device"] = cb["total"]
            row[f"hlo_{name}_collective_counts"] = cb["counts"]
        row["cached_ms"] = _time_step(rt.step_cached, params, opt, cfg,
                                      xplan, parts) * 1e3
        row["refresh_unpipelined_ms"] = _time_step(
            rt.step_refresh, params, opt, cfg, xplan, parts) * 1e3
        row["pipelined_ms"] = _time_step(rt.step_pipelined, params, opt,
                                         cfg, xplan, parts) * 1e3
        out["transports"][transport] = row

    if "p2p" in out["transports"]:
        p2p = out["transports"]["p2p"]
        refresh_rows = p2p["refresh_rows"]
        out["p2p_rows_match_plan"] = bool(
            refresh_rows["uncached"] == xplan.uncached.n_rows
            and refresh_rows["local"] == xplan.local.n_rows
            and refresh_rows["global"] == xplan.glob.n_unique)
        out["pipelined_leq_unpipelined_p2p"] = bool(
            p2p["pipelined_ms"] <= p2p["refresh_unpipelined_ms"])
        out["p2p_pipeline_speedup"] = (
            p2p["refresh_unpipelined_ms"] / max(p2p["pipelined_ms"], 1e-9))
        if "allgather" in out["transports"]:
            ag = out["transports"]["allgather"]
            out["p2p_vs_allgather_row_ratio"] = (
                refresh_rows["total"]
                / max(1, ag["refresh_rows"]["total"]))
    return out


# ------------------------------------------------------- strategy sweep

def strategy_sweep(tiny: bool) -> dict:
    """Runs in the forced-4-device child: the spmm_15d strategy measured
    for real — c=2 (pr=2) and c=1 (pr=4, the dense-1D degenerate) on the
    flickr-scale config — asserting the byte-accounting contract
    (modeled forward collective bytes == HLO-measured) and loss parity
    vs the halo_1d sim oracle at refresh_every=1."""
    import jax
    jax.devices()           # lock the forced host device count first
    import numpy as np
    from repro.core import PROFILES, cal_capacity
    from repro.data import make_task
    from repro.dist import TrainSpec, make_sim_runtime, train_capgnn
    from repro.dist.strategy_15d import (build_spmm15d_layout,
                                         make_spmm15d_runtime,
                                         train_spmm15d)
    from repro.launch.hlo_cost import collective_bytes
    from repro.models.gnn import init_gnn
    from repro.optim import adam as mk_adam

    devices = 4
    epochs = 4 if tiny else 8
    scale = BENCH_SCALE["flickr"] / (8 if tiny else 1)
    task = make_task("flickr", scale=scale, feat_dim=64)
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=128, out_dim=task.num_classes, num_layers=3)
    opt = mk_adam(0.01)
    out = {"devices": devices, "tiny": bool(tiny),
           "num_nodes": int(task.graph.num_nodes)}
    for c in (1, 2):
        pr = devices // c
        ps = build_partition(task.graph,
                             metis_partition(task.graph, pr, seed=0), hops=1)
        spec = TrainSpec(strategy="spmm_15d", replication=c, donate=False)
        layout = build_spmm15d_layout(ps, task, spec)
        rt = make_spmm15d_runtime(cfg, layout, opt, spec)
        params = init_gnn(jax.random.PRNGKey(0), cfg)
        hlo = rt.lower_forward(params).compile().as_text()
        measured = collective_bytes(hlo)["total"]
        row = {"block_rows": pr, "group_size": layout.g,
               "modeled_fwd_bytes_per_device": rt.forward_bytes_per_device,
               "hlo_fwd_bytes_per_device": measured,
               "hlo_matches_model": bool(
                   measured == rt.forward_bytes_per_device),
               "step_bytes_total": rt.step_bytes,
               "vanilla_bytes_total": rt.vanilla_bytes}
        assert row["hlo_matches_model"], (
            f"spmm_15d c={c}: modeled {rt.forward_bytes_per_device} != "
            f"HLO {measured} ({collective_bytes(hlo)['counts']})")
        if c == 2:
            # loss parity vs the halo_1d sim oracle at refresh_every=1
            # over the same pr-block partition
            cap = cal_capacity(ps, cfg.feat_dims, [PROFILES["rtx3090"]] * pr)
            plan = build_cache_plan(ps, cap, refresh_every=1)
            xplan = build_exchange_plan(ps, plan)
            sp = stack_partitions(ps, task)
            spec1d = TrainSpec(strategy="halo_1d", donate=False)
            sim = make_sim_runtime(cfg, sp, xplan, opt, spec=spec1d)
            _, rep_sim = train_capgnn(cfg, sim, xplan, pr, opt,
                                      epochs=epochs, spec=spec1d)
            _, rep_15 = train_spmm15d(cfg, rt, opt, spec, epochs=epochs)
            row["parity_max_err"] = float(np.abs(
                np.asarray(rep_sim.losses)
                - np.asarray(rep_15.losses)).max())
            row["step_ms"] = rep_15.wall_time_s / max(1, epochs - 1) * 1e3
        out[f"c{c}"] = row
    return out


def strategy_model_sweep(task, parts_list=(2, 4, 8, 16)) -> dict:
    """Pure byte-model head-to-head over P and c on one graph (no devices
    needed): the halo_1d exact-mode wire bytes (zero-capacity plan — every
    halo row every step, the cut-bounded figure) vs the spmm_15d model at
    every replication factor with P % c**2 == 0.  This is where the
    1D-vs-1.5D crossover trend lives: for group size g = P/c**2 > 1 the
    per-layer total is ~4*n*(P/c + 2c) bytes, so the c=2/c=1 ratio is
    1/2 + 4/P — decreasing in P, with c=2 winning outright by P=16 (at
    P=c**2 the gather axis is size 1 and drops, so small P sits near
    break-even modulo partition padding).  The halo figure tracks the
    partition cut instead and stays below both at these scales."""
    from repro.dist import TrainSpec
    from repro.dist.strategy_15d import build_spmm15d_layout, step_bytes_total

    g = task.graph
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=128, out_dim=task.num_classes, num_layers=3)
    dims = cfg.feat_dims[:cfg.num_layers]
    parts_cache: dict[int, object] = {}

    def parted(pr):
        if pr not in parts_cache:
            parts_cache[pr] = build_partition(
                g, metis_partition(g, pr, seed=0), hops=1)
        return parts_cache[pr]

    rows = {}
    for p in parts_list:
        ps = parted(p)
        plan0 = build_cache_plan(ps, CacheCapacity(c_gpu=[0] * p, c_cpu=0),
                                 refresh_every=1)
        xplan = build_exchange_plan(ps, plan0)
        halo = sum(xplan.bytes_per_step(d, refresh=True, dtype_bytes=4)
                   for d in dims)
        row = {"halo_exact_bytes": int(halo), "spmm15d": {}}
        for c in (1, 2, 4):
            if p % (c * c):
                continue
            spec = TrainSpec(strategy="spmm_15d", replication=c)
            layout = build_spmm15d_layout(parted(p // c), task, spec)
            row["spmm15d"][f"c{c}"] = int(step_bytes_total(layout, cfg, spec))
        rows[f"p{p}"] = row
    return rows


def _strategy_sweep_subprocess(tiny: bool) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"   # counts only; the parent holds the chip
    env["REPRO_BENCH_TINY"] = "1" if tiny else "0"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.comm_volume",
         "--strategy-sweep-child"],
        capture_output=True, text=True, timeout=3600, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError("strategy sweep child failed:\n"
                           + res.stdout[-2000:] + res.stderr[-2000:])
    return json.loads(res.stdout.splitlines()[-1])


def _transport_sweep_subprocess(tiny: bool,
                                transports=("allgather", "p2p")) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"   # counts only; the parent holds the chip
    env["REPRO_BENCH_TINY"] = "1" if tiny else "0"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.comm_volume",
         "--transport-sweep-child", "--transport", *transports],
        capture_output=True, text=True, timeout=3600, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError("transport sweep child failed:\n"
                           + res.stdout[-2000:] + res.stderr[-2000:])
    return json.loads(res.stdout.splitlines()[-1])


def run(out_dir: str = DEFAULT_OUT, tiny: bool | None = None,
        transports=("allgather", "p2p")) -> dict:
    if tiny is None:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
    if tiny:
        from repro.data import make_task
        task = make_task("reddit", scale=BENCH_SCALE["reddit"] / 4,
                         feat_dim=64)
        part_counts, fracs, epochs = (2, 4), (0.0, 0.3, 1.0), 4
    else:
        task = bench_task("reddit")
        part_counts, fracs, epochs = (2, 4), (0.0, 0.1, 0.3, 0.6, 1.0), EPOCHS
    g = task.graph
    sweeps = {}
    for parts in part_counts:
        ps = build_partition(g, metis_partition(g, parts, seed=0), hops=1)
        rows = [_one(task, ps, f, parts, epochs=epochs) for f in fracs]
        sweeps[f"{parts}p"] = rows

    # Fig. 19 ratios at the 4-partition full-capacity point
    base = sweeps["4p"][0]          # no cache
    best = sweeps["4p"][-1]         # full cache
    overhead_s = best["plan_build_s"] / epochs
    saved_s = base["epoch_time_s"] - best["epoch_time_s"]

    # strategy head-to-head: byte-model sweep over P and c (in-process),
    # plus the forced-4-device measured child (HLO == model + parity)
    sm = strategy_model_sweep(task)
    ratio = {p: (sm[f"p{p}"]["spmm15d"]["c2"]
                 / max(1, sm[f"p{p}"]["spmm15d"]["c1"]))
             for p in (4, 8, 16)}
    p16 = sm["p16"]["spmm15d"]
    ss = _strategy_sweep_subprocess(tiny)
    out = {
        "tiny": bool(tiny),
        "sweeps": sweeps,
        "strategy_model_sweep": sm,
        "strategy_sweep": ss,
        # byte-accounting contract, measured: modeled forward collective
        # bytes equal the HLO-measured figure for both c=1 and c=2
        "spmm15d_hlo_matches_model": bool(
            ss["c1"]["hlo_matches_model"] and ss["c2"]["hlo_matches_model"]),
        "spmm15d_parity_max_err": float(ss["c2"]["parity_max_err"]),
        # the 1D-vs-1.5D crossover trend: for g = P/c**2 > 1 the c=2/c=1
        # ratio falls as 1/2 + 4/P, so P=4/8 hover near break-even (the
        # model's partition padding wobbles them either side of 1.0) and
        # the P=16 tail is decisive: c=2 beats c=1, c=4 beats both.
        # Gated as exact ints + the tail bools + rtol'd ratios.
        "spmm15d_bytes_p4_c1": int(sm["p4"]["spmm15d"]["c1"]),
        "spmm15d_bytes_p4_c2": int(sm["p4"]["spmm15d"]["c2"]),
        "spmm15d_bytes_p16_c1": int(p16["c1"]),
        "spmm15d_bytes_p16_c2": int(p16["c2"]),
        "spmm15d_bytes_p16_c4": int(p16["c4"]),
        "halo_exact_bytes_p4": int(sm["p4"]["halo_exact_bytes"]),
        "spmm15d_ratio_c2_c1_p4": float(ratio[4]),
        "spmm15d_ratio_c2_c1_p8": float(ratio[8]),
        "spmm15d_ratio_c2_c1_p16": float(ratio[16]),
        "spmm15d_crossover_at_p16": bool(
            ratio[16] < min(ratio[4], ratio[8], 1.0)),
        "spmm15d_c2_beats_c1_at_p16": bool(ratio[16] < 1.0),
        "spmm15d_c4_best_at_p16": bool(
            p16["c4"] < p16["c2"] and p16["c4"] < p16["c1"]),
        # any non-zero cache beats no cache; the sweep is NOT monotone in
        # capacity because mid-size caches route more vertices through the
        # deduplicated global tier (one broadcast row per unique vertex)
        # while an all-local plan refreshes per-(vertex,consumer) pair —
        # the same "more cache is not always better" shape as paper Fig. 18.
        "cache_beats_no_cache": bool(all(
            r["comm_bytes"] < rows[0]["comm_bytes"]
            for rows in sweeps.values() for r in rows[1:])),
        "overhead_ratio": overhead_s / max(best["epoch_time_s"], 1e-9),
        "benefit_to_overhead": saved_s / max(overhead_s, 1e-9),
        "max_comm_reduction": max(r["comm_reduction"]
                                  for rows in sweeps.values() for r in rows),
        "transport_sweep": _transport_sweep_subprocess(tiny, transports),
    }
    save(out_dir, "comm_volume", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--transport-sweep-child", action="store_true",
                    help="internal: run only the transport sweep in this "
                         "(forced multi-device) process, JSON on stdout")
    ap.add_argument("--strategy-sweep-child", action="store_true",
                    help="internal: run only the spmm_15d strategy sweep "
                         "in this (forced multi-device) process, JSON on "
                         "stdout")
    ap.add_argument("--transport", nargs="*",
                    default=["allgather", "p2p"],
                    choices=["allgather", "p2p"],
                    help="which halo transports the sweep times/records")
    # parse_known_args: tolerate the benchmarks.run orchestrator's flags
    args, _ = ap.parse_known_args(argv)
    if args.transport_sweep_child:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
        print(json.dumps(transport_sweep(tiny, tuple(args.transport))))
        return
    if args.strategy_sweep_child:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
        print(json.dumps(strategy_sweep(tiny)))
        return
    out = run(transports=tuple(args.transport))
    print(f"comm_volume: cache beats no cache = {out['cache_beats_no_cache']},"
          f" max reduction = {out['max_comm_reduction']:.1%}")
    for k, rows in out["sweeps"].items():
        line = ", ".join(f"{r['cap_frac']:.1f}:{r['comm_reduction']:.0%}"
                         for r in rows)
        print(f"  {k}: reduction by cap frac {line}")
    print(f"  overhead ratio {out['overhead_ratio']:.4f}, "
          f"benefit/overhead {out['benefit_to_overhead']:.1f}")
    ts = out["transport_sweep"]
    for t, row in ts["transports"].items():
        print(f"  transport {t:9s}: refresh rows "
              f"{row['refresh_rows']['total']:7d} "
              f"(padded {row['refresh_rows_padded']['total']:7d}), "
              f"hlo refresh coll {row['hlo_refresh_collective_bytes_per_device']:.2e} B/dev, "
              f"cached {row['cached_ms']:.1f} ms, "
              f"refresh {row['refresh_unpipelined_ms']:.1f} ms, "
              f"pipelined {row['pipelined_ms']:.1f} ms")
    if "p2p_rows_match_plan" in ts:
        print(f"  p2p rows match plan = {ts['p2p_rows_match_plan']}, "
              f"p2p/allgather rows = "
              f"{ts.get('p2p_vs_allgather_row_ratio', float('nan')):.2f}, "
              f"pipelined<=unpipelined(p2p) = "
              f"{ts['pipelined_leq_unpipelined_p2p']}"
              f" (speedup {ts['p2p_pipeline_speedup']:.2f}x)")
    # strategy head-to-head: the 1D-vs-1.5D crossover as P grows
    print(f"  spmm_15d: HLO == model = {out['spmm15d_hlo_matches_model']}, "
          f"parity vs halo_1d oracle = "
          f"{out['spmm15d_parity_max_err']:.2e}")
    for p, row in out["strategy_model_sweep"].items():
        ks = ", ".join(f"{c}={b:.2e}" for c, b in row["spmm15d"].items())
        print(f"  strategy {p:4s}: halo exact {row['halo_exact_bytes']:.2e} B"
              f" | spmm15d {ks}")
    print(f"  crossover: c2/c1 ratio "
          f"P4 {out['spmm15d_ratio_c2_c1_p4']:.2f} -> "
          f"P8 {out['spmm15d_ratio_c2_c1_p8']:.2f} -> "
          f"P16 {out['spmm15d_ratio_c2_c1_p16']:.2f}; "
          f"c2 beats c1 at P=16 = {out['spmm15d_c2_beats_c1_at_p16']}, "
          f"c4 best at P=16 = {out['spmm15d_c4_best_at_p16']}")


if __name__ == "__main__":
    main()
