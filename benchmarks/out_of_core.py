"""Out-of-core host feature store: step-time overhead of host-resident vs
device-resident features (``features="host"`` vs ``"device"``), a section
training a graph whose stacked features exceed a simulated device budget,
and an exact host-fetch accounting harness on a forced multi-device mesh.

Three sections:

- **overhead sweep** — steady-state pipelined step time with the halo
  feature table device-resident vs host-resident, across feature dims and
  host-tier fractions (the share of halo rows served from the host store
  instead of the local device cache).  The double-buffered prefetch ring
  should keep the host-backed step within ~1.5x of device-resident at the
  flickr benchmark scale (asserted by ``main``).
- **out-of-core budget** — device/host persistent feature residency under
  a simulated device byte budget set *between* the two: the stacked
  device-mode table exceeds it, the host-mode device footprint (the
  layer-0 local-tier block only) fits, and training still converges.
  Transient staging bytes (the in-flight prefetch buffers) are reported
  separately — they bound the peak, not the persistent residency.
- **accounting** — re-execs this module with
  ``--xla_force_host_platform_device_count=4`` and runs the SPMD runtime
  in host mode over both halo transports, asserting plan-counted host
  fetch rows/bytes == the store's consumed staged rows/bytes exactly
  (the identity :meth:`~repro.dist.ExchangePlan.host_fetch_rows`
  promises), plus the d2h writeback bytes of every emit step.  The
  child counts and times nothing, so it runs with ``JAX_PLATFORMS=cpu``
  (on a chip host the parent holds the TPU).

``REPRO_BENCH_TINY=1`` shrinks everything for CI smoke runs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from ._util import BENCH_SCALE, DEFAULT_OUT, save

EPOCHS = 9          # with refresh_every=4: plain refresh @0, pipelined @4,8
REFRESH_EVERY = 4


def _forced_cap(ps, host_frac: float, parts: int):
    """Capacity forcing all three tiers non-empty with ``host_frac`` of the
    widest worker's halo rows host-resident at layer 0 (uncached + global);
    the plan's actual tier sizes are what the sweep records."""
    from repro.core import CacheCapacity
    max_halo = max(pt.n_halo for pt in ps.parts)
    local = max(1, int(round((1.0 - host_frac) * max_halo)))
    # split the host share between the deduplicated global tier and
    # per-step uncached rows — both stage h2d at layer 0 in host mode
    c_cpu = max(1, int(round(0.5 * host_frac * max_halo * parts)))
    return CacheCapacity(c_gpu=[local] * parts, c_cpu=c_cpu)


def _time_step(fn, params, opt, cfg, xplan, parts, features: str = "device",
               repeats: int = 5, inner: int = 2) -> float:
    """Best-of-``repeats`` per-step seconds, chaining the returned state
    (steady-state loop; host mode includes the staging/prefetch work the
    wrapper does on the host thread)."""
    import jax
    import jax.numpy as jnp
    from repro.dist import init_caches

    pp = jax.tree.map(jnp.copy, params)
    oo = opt.init(pp)
    cc = init_caches(cfg, xplan, parts, features=features)
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


def overhead_sweep(tiny: bool) -> list[dict]:
    """Pipelined step time, device- vs host-resident features, across
    feature dims and host-tier fractions at flickr benchmark scale."""
    import jax
    from repro.core import build_cache_plan
    from repro.data import make_task
    from repro.dist import (build_exchange_plan, make_sim_runtime,
                            stack_partitions)
    from repro.graph import build_partition, metis_partition
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.optim import adam

    parts = 4
    scale = BENCH_SCALE["flickr"] / (8 if tiny else 1)
    dims = (32, 64) if tiny else (64, 256)
    fracs = (0.3, 0.7) if tiny else (0.2, 0.5, 0.8)

    rows = []
    for feat_dim in dims:
        task = make_task("flickr", scale=scale, feat_dim=feat_dim)
        ps = build_partition(task.graph,
                             metis_partition(task.graph, parts, seed=0),
                             hops=1)
        cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                        hidden_dim=64, out_dim=task.num_classes,
                        num_layers=3)
        sp = stack_partitions(ps, task)
        opt = adam(0.01)
        params = init_gnn(jax.random.PRNGKey(0), cfg)
        for frac in fracs:
            plan = build_cache_plan(ps, _forced_cap(ps, frac, parts),
                                    refresh_every=REFRESH_EVERY)
            xplan = build_exchange_plan(ps, plan)
            rt_dev = make_sim_runtime(cfg, sp, xplan, opt)
            rt_host = make_sim_runtime(cfg, sp, xplan, opt,
                                       features="host", prefetch_depth=2)
            dev_s = _time_step(rt_dev.step_pipelined, params, opt, cfg,
                               xplan, parts)
            host_s = _time_step(rt_host.step_pipelined, params, opt, cfg,
                                xplan, parts, features="host")
            rows.append({
                "feat_dim": feat_dim, "host_frac": frac,
                "host_rows_l0": int(xplan.host.n_fetch_rows),
                "local_rows_l0": int(xplan.local.n_rows),
                "global_unique": int(xplan.glob.n_unique),
                "device_ms": dev_s * 1e3, "host_ms": host_s * 1e3,
                "overhead": host_s / max(dev_s, 1e-12),
            })
    return rows


def ooc_budget_section(tiny: bool, tracer=None) -> dict:
    """Train with the stacked halo feature table exceeding a simulated
    device budget: host mode keeps only the layer-0 local-tier block
    persistent on device; the full table plus the device-mode global
    caches would not fit."""
    import jax
    from repro.core import StalenessController, build_cache_plan
    from repro.data import make_task
    from repro.dist import (build_exchange_plan, init_caches,
                            make_sim_runtime, stack_partitions, train_capgnn)
    from repro.graph import build_partition, metis_partition
    from repro.models.gnn import GNNConfig
    from repro.optim import adam

    parts = 4
    scale = BENCH_SCALE["flickr"] / (8 if tiny else 1)
    task = make_task("flickr", scale=scale, feat_dim=64)
    ps = build_partition(task.graph,
                         metis_partition(task.graph, parts, seed=0), hops=1)
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=64, out_dim=task.num_classes, num_layers=3)
    plan = build_cache_plan(ps, _forced_cap(ps, 0.7, parts),
                            refresh_every=REFRESH_EVERY)
    xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task)
    opt = adam(0.01)
    rt = make_sim_runtime(cfg, sp, xplan, opt, features="host",
                          prefetch_depth=2)
    store = rt.host_store

    # persistent residency: device mode keeps the whole stacked halo table
    # plus the per-layer global cache buffers on device for the entire
    # run; host mode keeps only the staged layer-0 local-tier block
    cc_dev = init_caches(cfg, xplan, parts)
    device_bytes = int(sp.halo_feats.nbytes
                       + sum(int(np.prod(g.shape)) * 4
                             for g in cc_dev["global"]))
    host_bytes = int(rt._state["l0loc"].nbytes)
    budget = (device_bytes + host_bytes) // 2   # simulated device budget
    ex_dims = cfg.feat_dims[1:cfg.num_layers]
    staging_bytes = int(store.prefetch_depth * parts * xplan.host.width
                        * cfg.feat_dims[0] * store.dtype_bytes
                        + sum(xplan.glob.n_unique * d * store.dtype_bytes
                              for d in ex_dims))

    ctl = StalenessController(refresh_every=REFRESH_EVERY)
    params, rep = train_capgnn(cfg, rt, xplan, parts, opt, epochs=EPOCHS,
                               controller=ctl, pipeline=True, eval_every=0,
                               tracer=tracer)
    # schedule: plain refresh @0 (no stale global staged), pipelined
    # refreshes + cached steps stage the global buffers every other step
    per = xplan.host_fetch_rows(True, len(ex_dims))
    expected_rows = EPOCHS * per["l0"] + (EPOCHS - 1) * per["global"]
    _, test_acc = rt.evaluate(params, "test")
    return {
        "nodes": int(task.graph.num_nodes),
        "device_feature_bytes": device_bytes,
        "host_device_feature_bytes": host_bytes,
        "sim_device_budget_bytes": int(budget),
        "peak_staging_bytes": staging_bytes,
        "host_store_resident_bytes": int(store.resident_bytes()),
        "exceeds_device_budget": bool(device_bytes > budget),
        "host_fits_budget": bool(host_bytes <= budget),
        "loss_first": rep.losses[0], "loss_last": rep.losses[-1],
        "loss_decreased": bool(rep.losses[-1] < rep.losses[0]),
        "test_acc": float(test_acc),
        "host_fetch_rows": int(rep.host_fetch_rows),
        "host_fetch_rows_expected": int(expected_rows),
        "rows_match": bool(rep.host_fetch_rows == expected_rows),
        "host_fetch_bytes": int(rep.host_fetch_bytes),
        "host_writeback_bytes": int(rep.host_writeback_bytes),
    }


# ------------------------------------------------- forced-mesh accounting

def accounting_sweep(tiny: bool, transports=("allgather", "p2p")) -> dict:
    """Runs in the forced-4-device child: SPMD host mode over both halo
    transports with exact plan-vs-store fetch accounting."""
    import jax
    jax.devices()           # lock the forced host device count first
    import jax.numpy as jnp
    from repro.core import build_cache_plan
    from repro.data import make_task
    from repro.dist import (build_exchange_plan, init_caches,
                            stack_partitions)
    from repro.dist.capgnn_spmd import make_spmd_runtime
    from repro.graph import build_partition, metis_partition
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.optim import adam

    parts = 4
    scale = BENCH_SCALE["flickr"] / (16 if tiny else 4)
    task = make_task("flickr", scale=scale, feat_dim=32)
    ps = build_partition(task.graph,
                         metis_partition(task.graph, parts, seed=0), hops=1)
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=32, out_dim=task.num_classes, num_layers=3)
    plan = build_cache_plan(ps, _forced_cap(ps, 0.7, parts),
                            refresh_every=REFRESH_EVERY)
    xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task)
    opt = adam(0.01)
    mesh = jax.make_mesh((parts,), ("data",))
    params = init_gnn(jax.random.PRNGKey(0), cfg)

    ex_dims = cfg.feat_dims[1:cfg.num_layers]
    per = xplan.host_fetch_rows(True, len(ex_dims))
    # step 0 is a plain refresh (fresh global built on-wire, nothing
    # staged); every later step — cached or pipelined — stages the
    # host-resident global buffers alongside the layer-0 rows
    expected_rows = EPOCHS * per["l0"] + (EPOCHS - 1) * per["global"]
    refresh_b = xplan.host_bytes_per_step(cfg.feat_dims[0], ex_dims, False)
    stale_b = xplan.host_bytes_per_step(cfg.feat_dims[0], ex_dims, True)
    expected_bytes = refresh_b + (EPOCHS - 1) * stale_b
    n_emit = 1 + (EPOCHS - 1) // REFRESH_EVERY       # steps 0, 4, 8
    expected_wb = n_emit * xplan.host_writeback_bytes(ex_dims)

    out = {"parts": parts, "tiny": bool(tiny),
           "nodes": int(task.graph.num_nodes),
           "host_rows_l0": int(xplan.host.n_fetch_rows),
           "global_unique": int(xplan.glob.n_unique),
           "transports": {}}
    losses = {}
    for transport in transports:
        rt = make_spmd_runtime(cfg, sp, xplan, opt, mesh,
                               transport=transport, features="host")
        store = rt.host_store
        snap = store.snapshot()
        pp = jax.tree.map(jnp.copy, params)
        oo = opt.init(pp)
        cc = init_caches(cfg, xplan, parts, features="host")
        hist = []
        for step in range(EPOCHS):
            if step == 0:
                fn = rt.step_refresh
            elif step % REFRESH_EVERY == 0:
                fn = rt.step_pipelined
            else:
                fn = rt.step_cached
            pp, oo, cc, m = fn(pp, oo, cc)
            hist.append(float(m["loss"]))
        d = store.delta(snap)
        losses[transport] = hist
        out["transports"][transport] = {
            "fetch_rows": d["fetch_rows"],
            "expected_rows": expected_rows,
            "fetch_bytes": d["fetch_bytes"],
            "expected_bytes": expected_bytes,
            "writeback_bytes": d["writeback_bytes"],
            "expected_writeback_bytes": expected_wb,
            "rows_match": bool(d["fetch_rows"] == expected_rows),
            "bytes_match": bool(d["fetch_bytes"] == expected_bytes
                                and d["writeback_bytes"] == expected_wb),
            "loss_last": hist[-1],
        }
    out["rows_match_all"] = bool(all(
        r["rows_match"] and r["bytes_match"]
        for r in out["transports"].values()))
    if len(losses) == 2:
        a, b = (np.array(losses[t]) for t in transports)
        out["transport_losses_agree"] = bool(np.abs(a - b).max() <= 1e-5)
    return out


def _accounting_subprocess(tiny: bool,
                           transports=("allgather", "p2p")) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"   # counts only; the parent holds the chip
    env["REPRO_BENCH_TINY"] = "1" if tiny else "0"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.out_of_core",
         "--accounting-child", "--transport", *transports],
        capture_output=True, text=True, timeout=3600, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError("out_of_core accounting child failed:\n"
                           + res.stdout[-2000:] + res.stderr[-2000:])
    return json.loads(res.stdout.splitlines()[-1])


def run(out_dir: str = DEFAULT_OUT, tiny: bool | None = None,
        transports=("allgather", "p2p")) -> dict:
    if tiny is None:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
    tracer = None
    if bool(int(os.environ.get("REPRO_BENCH_TRACE", "0"))):
        from repro.obs import Tracer
        tracer = Tracer()
    sweep = overhead_sweep(tiny)
    ooc = ooc_budget_section(tiny, tracer=tracer)
    acct = _accounting_subprocess(tiny, transports)

    overheads = np.array([r["overhead"] for r in sweep])
    out = {
        "tiny": bool(tiny),
        "nodes": ooc["nodes"],
        # geometric mean across (feat_dim, host_frac) cells; max is the
        # worst cell.  "_leq_" marks the bool as timing-derived so the
        # regression gate skips it (it is asserted by main() instead).
        "host_overhead_pipelined": float(np.exp(np.log(overheads).mean())),
        "host_overhead_max": float(overheads.max()),
        "host_overhead_leq_1p5": bool(
            np.exp(np.log(overheads).mean()) <= 1.5),
        "exceeds_device_budget": ooc["exceeds_device_budget"],
        "host_fits_budget": ooc["host_fits_budget"],
        "ooc_loss_decreased": ooc["loss_decreased"],
        "sim_host_rows_match": ooc["rows_match"],
        "host_fetch_rows": ooc["host_fetch_rows"],
        "host_fetch_bytes": ooc["host_fetch_bytes"],
        "accounting_rows_match_both_transports": acct["rows_match_all"],
        "transport_losses_agree": acct.get("transport_losses_agree", True),
        "overhead_sweep": sweep,
        "out_of_core": ooc,
        "accounting": acct,
    }
    if tracer is not None:
        # "trace_file" is in the regression gate's SKIP_KEYS: attached,
        # never gated
        out["trace_file"] = tracer.export(out_dir,
                                          prefix="out_of_core")["trace"]
    save(out_dir, "out_of_core", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--accounting-child", action="store_true",
                    help="internal: run only the SPMD accounting sweep in "
                         "this (forced multi-device) process, JSON on stdout")
    ap.add_argument("--transport", nargs="*",
                    default=["allgather", "p2p"],
                    choices=["allgather", "p2p"])
    # parse_known_args: tolerate the benchmarks.run orchestrator's flags
    args, _ = ap.parse_known_args(argv)
    if args.accounting_child:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
        print(json.dumps(accounting_sweep(tiny, tuple(args.transport))))
        return
    out = run(transports=tuple(args.transport))
    print(f"out_of_core: {out['nodes']} nodes, host/device pipelined step "
          f"overhead {out['host_overhead_pipelined']:.2f}x (max "
          f"{out['host_overhead_max']:.2f}x)")
    for r in out["overhead_sweep"]:
        print(f"  F={r['feat_dim']:4d} host_frac={r['host_frac']:.1f}: "
              f"device {r['device_ms']:7.2f} ms, host {r['host_ms']:7.2f} ms"
              f" ({r['overhead']:.2f}x), l0 host rows {r['host_rows_l0']}")
    o = out["out_of_core"]
    print(f"  budget: device-resident {o['device_feature_bytes']:.3e} B > "
          f"budget {o['sim_device_budget_bytes']:.3e} B >= host-resident "
          f"{o['host_device_feature_bytes']:.3e} B; "
          f"loss {o['loss_first']:.3f} -> {o['loss_last']:.3f}, "
          f"acc {o['test_acc']:.2%}")
    for t, r in out["accounting"]["transports"].items():
        print(f"  accounting {t:9s}: fetched {r['fetch_rows']} rows "
              f"(plan {r['expected_rows']}), {r['fetch_bytes']} B "
              f"(plan {r['expected_bytes']}), writeback "
              f"{r['writeback_bytes']} B — match={r['rows_match']}/"
              f"{r['bytes_match']}")
    assert out["exceeds_device_budget"] and out["host_fits_budget"], \
        "out-of-core budget demonstration broken"
    assert out["ooc_loss_decreased"], "host-mode training failed to learn"
    assert out["sim_host_rows_match"], "sim host-fetch accounting drifted"
    assert out["accounting_rows_match_both_transports"], \
        "SPMD host-fetch accounting drifted from the plan"
    assert out["host_overhead_pipelined"] <= 1.5, \
        (f"host-backed pipelined step {out['host_overhead_pipelined']:.2f}x "
         "device-resident (> 1.5x budget)")


if __name__ == "__main__":
    main()
