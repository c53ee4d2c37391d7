"""Fault injection + graceful degradation (``repro.faults``): under each
fault class, training must complete, every injected fault must be matched
by exactly one counted defense event, and the final loss must land within
tolerance of the clean run.

Four sections:

- **fault matrix** — the sim runtime over both feature modes
  (``features="device"|"host"``), one cell per applicable fault class:
  ``fetch_drop`` (bounded retry -> stale-tier reuse), ``fetch_delay``
  (slow-fetch detection -> prefetch degraded to synchronous),
  ``halo_corrupt`` (per-tier checksums -> forced plain refresh),
  ``grad_nan`` (divergence guard -> rollback to the last good snapshot)
  and ``mem_pressure`` (capacity shrink + slot-stable replan through the
  ``AdaptivePlanner``).  Per cell: run completes with a finite loss,
  ``injected[kind] == events[defense]`` *exactly*, loss gap vs the clean
  run under ``LOSS_TOL``.
- **event accounting** — a combined-fault run under the ``repro.obs``
  tracer: the per-step ``StepCounters`` fault deltas must sum to the
  report's ``fault_events`` exactly (the trace is the same ledger,
  before summation).  With ``REPRO_BENCH_TRACE=1`` the Perfetto timeline
  is exported for the CI schema gate (rollback/integrity/fetch_retry
  spans visible).
- **checkpoint integrity** — ``ckpt_truncate`` against the checksummed
  checkpoint format: the truncated file is detected
  (``CheckpointCorruptError``), ``latest_step`` falls back to the newest
  valid checkpoint, and the restored state matches the values saved
  there bit-for-bit.
- **SPMD transports** — re-execs this module with
  ``--xla_force_host_platform_device_count=4`` and runs the shard_map
  runtime in host mode over both halo transports (``p2p`` ring /
  ``allgather``) under a combined fault spec, asserting the same
  injected==defended accounting on each.  The child counts and times
  nothing, so it runs with ``JAX_PLATFORMS=cpu`` (on a chip host the
  parent holds the TPU).

``REPRO_BENCH_TINY=1`` shrinks everything for CI smoke runs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np

from ._util import BENCH_SCALE, DEFAULT_OUT, save

EPOCHS = 8
REFRESH_EVERY = 2
# |final loss - clean final loss| budget: a rollback legitimately loses
# the faulted step, so the faulted run trails the clean one by ~one step
LOSS_TOL = 0.25

# one row per fault class: spec, the defense counter it must equal, the
# guard knobs that arm the defense, and the feature modes it applies to
FAULT_MATRIX = (
    {"kind": "fetch_drop", "spec": "fetch_drop@3,5",
     "defense": "fetch_errors", "guard": {"fetch_retries": 2},
     "modes": ("host",)},
    {"kind": "fetch_delay", "spec": "fetch_delay@2:delay_s=0.12",
     "defense": "slow_fetches", "guard": {"fetch_timeout_s": 0.05},
     "modes": ("host",)},
    {"kind": "halo_corrupt", "spec": "halo_corrupt@3",
     "defense": "corruptions_detected", "guard": {"checksums": True},
     "modes": ("device", "host")},
    {"kind": "grad_nan", "spec": "grad_nan@3",
     "defense": "rollbacks", "guard": {"guard_every": 2},
     "modes": ("device", "host")},
    {"kind": "mem_pressure", "spec": "mem_pressure@4",
     "defense": "mem_backoffs", "guard": {}, "policy": "lru",
     "modes": ("device", "host")},
)


def _build(tiny: bool, features: str = "host", policy: str | None = None,
           parts: int = 2):
    """Fresh task/plan/runtime (donated state — never reuse across runs)."""
    from repro.core import (PROFILES, AdaptivePlanner, StalenessController,
                            build_cache_plan, cal_capacity)
    from repro.data import make_task
    from repro.dist import (TrainSpec, build_exchange_plan, make_sim_runtime,
                            stack_partitions)
    from repro.graph import build_partition, metis_partition
    from repro.models.gnn import GNNConfig
    from repro.optim import adam

    scale = BENCH_SCALE["flickr"] / (16 if tiny else 4)
    task = make_task("flickr", scale=scale, feat_dim=16, seed=0)
    ps = build_partition(task.graph,
                         metis_partition(task.graph, parts, seed=0), hops=1)
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=16, out_dim=task.num_classes, num_layers=2)
    cap = cal_capacity(ps, cfg.feat_dims, [PROFILES["rtx3090"]] * parts,
                       m_cpu_gib=1.0)
    planner = None
    if policy:
        planner = AdaptivePlanner(ps, cap, refresh_every=REFRESH_EVERY,
                                  policy=policy, seed=0)
        xplan = planner.exchange_plan()
    else:
        plan = build_cache_plan(ps, cap, refresh_every=REFRESH_EVERY)
        xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task)
    opt = adam(0.01)
    spec = TrainSpec(features=features, refresh_every=REFRESH_EVERY,
                     cache_policy=policy or "static")
    rt = make_sim_runtime(cfg, sp, xplan, opt, spec=spec)
    ctl = StalenessController(refresh_every=REFRESH_EVERY)
    return cfg, rt, xplan, parts, opt, planner, ctl


def _train(tiny: bool, spec: str | None = None, guard_kw: dict | None = None,
           features: str = "host", policy: str | None = None, tracer=None):
    from repro.dist import train_capgnn
    from repro.faults import FaultPlan, GuardConfig

    cfg, rt, xplan, parts, opt, planner, ctl = _build(tiny, features, policy)
    faults = FaultPlan.parse(spec, seed=0) if spec else None
    guard = GuardConfig(**guard_kw) if guard_kw is not None else None
    _, rep = train_capgnn(cfg, rt, xplan, parts, opt, epochs=EPOCHS,
                          controller=ctl, spec=rt.spec, planner=planner,
                          tracer=tracer, faults=faults, guard=guard)
    return rep


def fault_matrix_section(tiny: bool) -> list[dict]:
    """One cell per (fault class, feature mode): completes, exact
    accounting, loss within tolerance of the clean run."""
    clean: dict = {}        # (features, policy) -> clean losses
    rows = []
    for row in FAULT_MATRIX:
        policy = row.get("policy")
        for features in row["modes"]:
            key = (features, policy)
            if key not in clean:
                clean[key] = _train(tiny, features=features,
                                    policy=policy).losses
            rep = _train(tiny, spec=row["spec"], guard_kw=row["guard"],
                         features=features, policy=policy)
            injected = rep.faults_injected[row["kind"]]
            defended = rep.fault_events[row["defense"]]
            gap = abs(rep.losses[-1] - clean[key][-1])
            rows.append({
                "kind": row["kind"], "features": features,
                "injected": int(injected), "defended": int(defended),
                "accounting_exact": bool(injected == defended
                                         and injected > 0),
                "completed": bool(len(rep.losses) == EPOCHS
                                  and np.isfinite(rep.losses[-1])),
                "loss_clean": float(clean[key][-1]),
                "loss_faulted": float(rep.losses[-1]),
                "loss_gap": float(gap),
                "loss_within_tol": bool(gap <= LOSS_TOL),
                "events": {k: v for k, v in rep.fault_events.items() if v},
            })
    return rows


def accounting_section(tiny: bool, out_dir: str) -> dict:
    """Combined-fault traced run: per-step counter deltas must sum to the
    report's ledgers exactly; exports the Perfetto timeline when
    ``REPRO_BENCH_TRACE=1`` (CI gates its span kinds)."""
    from repro.obs import Tracer

    tr = Tracer()
    rep = _train(tiny, spec="fetch_drop@3;halo_corrupt@4;grad_nan@5",
                 guard_kw={"guard_every": 2, "fetch_retries": 1,
                           "checksums": True},
                 features="host", tracer=tr)
    tot = tr.totals()
    events_match = all(tot[k] == v for k, v in rep.fault_events.items())
    injected_match = tot["faults_injected"] == sum(
        rep.faults_injected.values())
    out = {
        "trace_events_match_report": bool(events_match),
        "trace_injected_match_report": bool(injected_match),
        "injected": {k: v for k, v in rep.faults_injected.items() if v},
        "events": {k: v for k, v in rep.fault_events.items() if v},
    }
    if bool(int(os.environ.get("REPRO_BENCH_TRACE", "0"))):
        out["trace_file"] = tr.export(out_dir,
                                      prefix="fault_tolerance")["trace"]
    return out


def checkpoint_section(tiny: bool) -> dict:
    """``ckpt_truncate`` vs the checksummed checkpoint format: detect,
    fall back, restore bit-for-bit."""
    import tempfile
    import warnings

    import jax

    from repro.checkpoint import (CheckpointCorruptError, latest_step,
                                  load_checkpoint, save_checkpoint,
                                  verify_checkpoint)
    from repro.dist import train_capgnn
    from repro.faults import FaultPlan

    cfg, rt, xplan, parts, opt, planner, ctl = _build(tiny)
    half = EPOCHS // 2
    params, rep = train_capgnn(cfg, rt, xplan, parts, opt, epochs=half,
                               controller=ctl, spec=rt.spec)
    mid = {"params": params, "opt_state": rep.final_opt_state}
    mid_host = jax.tree.map(np.asarray, mid)
    out: dict = {}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, half, mid)
        params, rep = train_capgnn(cfg, rt, xplan, parts, opt,
                                   epochs=EPOCHS - half, controller=ctl,
                                   spec=rt.spec, params0=params,
                                   opt_state0=rep.final_opt_state)
        save_checkpoint(d, EPOCHS,
                        {"params": params,
                         "opt_state": rep.final_opt_state})
        assert latest_step(d) == EPOCHS
        fp = FaultPlan.parse("ckpt_truncate@0:frac=0.4", seed=0)
        fp.truncate_checkpoint(os.path.join(d, f"ckpt_{EPOCHS:08d}.npz"))
        try:
            verify_checkpoint(d, EPOCHS)
            detected = False
        except CheckpointCorruptError:
            detected = True
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fallback = latest_step(d)
        restored = load_checkpoint(d, half, mid)
        flat_r = jax.tree.leaves(jax.tree.map(np.asarray, restored))
        flat_m = jax.tree.leaves(mid_host)
        exact = all(np.array_equal(a, b) for a, b in zip(flat_r, flat_m))
        out = {
            "injected": int(fp.injected["ckpt_truncate"]),
            "truncation_detected": bool(detected),
            "fallback_step": fallback,
            "fallback_ok": bool(fallback == half),
            "restore_bit_exact": bool(exact),
        }
    return out


# ---------------------------------------------------- forced-mesh transports

def spmd_sweep(tiny: bool, transports=("allgather", "p2p")) -> dict:
    """Runs in the forced-4-device child: SPMD host mode over both halo
    transports under a combined fault spec, exact accounting per
    transport."""
    import jax
    jax.devices()           # lock the forced host device count first
    from repro.core import (PROFILES, StalenessController, build_cache_plan,
                            cal_capacity)
    from repro.data import make_task
    from repro.dist import (TrainSpec, build_exchange_plan, stack_partitions,
                            train_capgnn)
    from repro.dist.capgnn_spmd import make_spmd_runtime
    from repro.faults import FaultPlan, GuardConfig
    from repro.graph import build_partition, metis_partition
    from repro.models.gnn import GNNConfig
    from repro.optim import adam

    parts = 4
    scale = BENCH_SCALE["flickr"] / (16 if tiny else 4)
    task = make_task("flickr", scale=scale, feat_dim=16, seed=0)
    ps = build_partition(task.graph,
                         metis_partition(task.graph, parts, seed=0), hops=1)
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=16, out_dim=task.num_classes, num_layers=2)
    cap = cal_capacity(ps, cfg.feat_dims, [PROFILES["rtx3090"]] * parts,
                       m_cpu_gib=1.0)
    plan = build_cache_plan(ps, cap, refresh_every=REFRESH_EVERY)
    xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task)
    opt = adam(0.01)
    mesh = jax.make_mesh((parts,), ("data",))

    def run(transport, spec=None, guard=None):
        tspec = TrainSpec(transport=transport, features="host",
                          refresh_every=REFRESH_EVERY)
        rt = make_spmd_runtime(cfg, sp, xplan, opt, mesh, spec=tspec)
        ctl = StalenessController(refresh_every=REFRESH_EVERY)
        faults = FaultPlan.parse(spec, seed=0) if spec else None
        _, rep = train_capgnn(cfg, rt, xplan, parts, opt, epochs=EPOCHS,
                              controller=ctl, spec=tspec, faults=faults,
                              guard=guard)
        return rep

    spec = "fetch_drop@3;grad_nan@5"
    out = {"transports": {}}
    for transport in transports:
        clean = run(transport)
        rep = run(transport, spec,
                  GuardConfig(guard_every=2, fetch_retries=1))
        exact = (rep.fault_events["fetch_errors"] > 0
                 and rep.faults_injected["fetch_drop"]
                 == rep.fault_events["fetch_errors"]
                 and rep.fault_events["rollbacks"] > 0
                 and rep.faults_injected["grad_nan"]
                 == rep.fault_events["rollbacks"])
        gap = abs(rep.losses[-1] - clean.losses[-1])
        out["transports"][transport] = {
            "completed": bool(len(rep.losses) == EPOCHS
                              and np.isfinite(rep.losses[-1])),
            "accounting_exact": bool(exact),
            "loss_clean": float(clean.losses[-1]),
            "loss_faulted": float(rep.losses[-1]),
            "loss_within_tol": bool(gap <= LOSS_TOL),
            "injected": {k: v for k, v in rep.faults_injected.items()
                         if v},
            "events": {k: v for k, v in rep.fault_events.items() if v},
        }
    out["exact_all"] = bool(all(
        r["completed"] and r["accounting_exact"] and r["loss_within_tol"]
        for r in out["transports"].values()))
    return out


def _spmd_subprocess(tiny: bool, transports=("allgather", "p2p")) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"   # counts only; the parent holds the chip
    env["REPRO_BENCH_TINY"] = "1" if tiny else "0"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.fault_tolerance",
         "--spmd-child", "--transport", *transports],
        capture_output=True, text=True, timeout=3600, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError("fault_tolerance spmd child failed:\n"
                           + res.stdout[-2000:] + res.stderr[-2000:])
    return json.loads(res.stdout.splitlines()[-1])


def run(out_dir: str = DEFAULT_OUT, tiny: bool | None = None,
        transports=("allgather", "p2p")) -> dict:
    if tiny is None:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
    matrix = fault_matrix_section(tiny)
    acct = accounting_section(tiny, out_dir)
    ckpt = checkpoint_section(tiny)
    spmd = _spmd_subprocess(tiny, transports)

    out = {
        "tiny": bool(tiny),
        "classes": len(matrix),
        "completed_all": bool(all(r["completed"] for r in matrix)),
        "accounting_exact_all": bool(all(r["accounting_exact"]
                                         for r in matrix)),
        "loss_within_tol_all": bool(all(r["loss_within_tol"]
                                        for r in matrix)),
        "trace_accounting_match": bool(
            acct["trace_events_match_report"]
            and acct["trace_injected_match_report"]),
        "ckpt_truncation_detected": ckpt["truncation_detected"],
        "ckpt_fallback_ok": ckpt["fallback_ok"],
        "ckpt_restore_bit_exact": ckpt["restore_bit_exact"],
        "spmd_exact_both_transports": spmd["exact_all"],
        "matrix": matrix,
        "accounting": acct,
        "checkpoint": ckpt,
        "spmd": spmd,
    }
    if "trace_file" in acct:
        # "trace_file" is in the regression gate's SKIP_KEYS: attached,
        # never gated
        out["trace_file"] = acct["trace_file"]
    save(out_dir, "fault_tolerance", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spmd-child", action="store_true",
                    help="internal: run only the SPMD fault sweep in this "
                         "(forced multi-device) process, JSON on stdout")
    ap.add_argument("--transport", nargs="*",
                    default=["allgather", "p2p"],
                    choices=["allgather", "p2p"])
    # parse_known_args: tolerate the benchmarks.run orchestrator's flags
    args, _ = ap.parse_known_args(argv)
    if args.spmd_child:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
        print(json.dumps(spmd_sweep(tiny, tuple(args.transport))))
        return
    out = run(transports=tuple(args.transport))
    print(f"fault_tolerance: {out['classes']} fault cells")
    for r in out["matrix"]:
        print(f"  {r['kind']:13s} [{r['features']:6s}]: injected "
              f"{r['injected']} == defended {r['defended']}, loss "
              f"{r['loss_clean']:.4f} -> {r['loss_faulted']:.4f} "
              f"(gap {r['loss_gap']:.4f})")
    c = out["checkpoint"]
    print(f"  ckpt_truncate: detected={c['truncation_detected']}, "
          f"fallback -> step {c['fallback_step']}, "
          f"bit-exact restore={c['restore_bit_exact']}")
    for t, r in out["spmd"]["transports"].items():
        print(f"  spmd {t:9s}: exact={r['accounting_exact']}, loss "
              f"{r['loss_clean']:.4f} -> {r['loss_faulted']:.4f}")
    assert out["completed_all"], "a faulted run did not complete"
    assert out["accounting_exact_all"], \
        "injected fault counts != counted defense events"
    assert out["loss_within_tol_all"], \
        f"a faulted run's final loss drifted beyond {LOSS_TOL}"
    assert out["trace_accounting_match"], \
        "per-step trace counters disagree with the report ledgers"
    assert (out["ckpt_truncation_detected"] and out["ckpt_fallback_ok"]
            and out["ckpt_restore_bit_exact"]), \
        "checkpoint integrity defense broken"
    assert out["spmd_exact_both_transports"], \
        "SPMD fault accounting drifted on a transport"


if __name__ == "__main__":
    main()
