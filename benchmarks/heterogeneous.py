"""Paper Fig. 21 + §4.3: heterogeneous device groups, cost model and
straggler end-to-end.

Two sections:

1. **Cost model** (Fig. 21): per-device lambda (Eq. 13+14) before/after
   RAPA for uniform-split (DistGCN-style) vs RAPA partitions, across the
   paper's Table 4 groups.  Variance explodes for uniform splits as
   heterogeneity grows; RAPA keeps it flat.
2. **Straggler end-to-end**: on the skewed x4/x8 groups, the full
   resource-aware path — capability-weighted uneven partitions
   (``capability_weights``) + Alg. 2/3 halo adjustment + jointly-set
   cache budgets (``cal_capacity`` sees the same profiles) — against the
   uniform-split baseline, judged on the modeled straggler step time
   (``lambda_max``), the padded-row waste of the stacked ``[P, ...]``
   layout the runtimes compile, and exact byte accounting
   (plan-counted rows == stacked valid-mask rows == p2p packed rows).
   ``rapa_even`` (adjustment on even partitions) rides along as the
   ablation separating the two RAPA stages.

The straggler section runs on the flickr-scale benchmark graph: its
sparsity keeps halo sizes proportional to part sizes.  (At the reddit
benchmark density — avg degree ~350 — every part's halo saturates to
nearly the whole remainder of the graph, which blunts partition-shape
effects; the cost-model section keeps reddit for continuity.)

A subprocess with ``--xla_force_host_platform_device_count=4`` (same
pattern as ``benchmarks.comm_volume``) drives the compiled SPMD step for
the uneven partitions over BOTH halo transports and checks the wire-row
accounting and cross-transport loss agreement.  It counts rows and times
nothing, so it runs with ``JAX_PLATFORMS=cpu`` (on a chip host the parent
holds the TPU).  ``REPRO_BENCH_TINY=1``
shrinks every graph for CI smoke runs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np

from repro.core import (PAPER_GROUPS, RapaConfig, build_cache_plan,
                        cal_capacity, capability_weights, do_partition,
                        make_group, partition_lambdas)
from repro.dist import build_exchange_plan, stack_partitions
from repro.dist.exchange import exchange_capacity
from repro.graph import build_partition, metis_partition
from ._util import BENCH_SCALE, DEFAULT_OUT, save

STRAGGLER_GROUPS = ("x4", "x8")


def _flickr_task(tiny: bool):
    from repro.data import make_task
    scale = BENCH_SCALE["flickr"] / (4 if tiny else 1)
    return make_task("flickr", scale=scale, feat_dim=64, seed=0)


# ------------------------------------------------------------ cost model

def cost_model_rows(tiny: bool) -> list[dict]:
    from repro.data import make_task
    scale = BENCH_SCALE["reddit"] / (4 if tiny else 1)
    task = make_task("reddit", scale=scale, feat_dim=64, seed=0)
    g = task.graph
    cfg = RapaConfig(feat_dim=task.features.shape[1])
    rows = []
    for grp in ("x2", "x4", "x6", "x8"):
        profiles = make_group(PAPER_GROUPS[grp])
        p = len(profiles)
        ps = build_partition(g, metis_partition(g, p, seed=0), hops=1,
                             parts=p)
        lam_uniform = partition_lambdas(ps, profiles, cfg)
        res = do_partition(ps, profiles, cfg)
        lam_rapa = res.lambda_final
        rows.append({
            "group": grp, "parts": p,
            "uniform_max": float(lam_uniform.max()),
            "uniform_rel_std": float(lam_uniform.std() / lam_uniform.mean()),
            "rapa_max": float(np.max(lam_rapa)),
            "rapa_rel_std": float(np.std(lam_rapa) / np.mean(lam_rapa)),
            "heterogeneity": float(max(pr.mm for pr in profiles)
                                   / min(pr.mm for pr in profiles)),
        })
    return rows


# ------------------------------------------------- straggler end-to-end

def _build_variants(g, profiles, cfg, seed: int = 0) -> dict:
    """uniform (even split, no adjustment — the DistGCN-style baseline),
    rapa_even (adjustment only), rapa_uneven (the full §4.3 pipeline)."""
    p = len(profiles)
    w = capability_weights(profiles)
    ps_even = build_partition(g, metis_partition(g, p, seed=seed),
                              hops=1, parts=p)
    ps_wtd = build_partition(g, metis_partition(g, p, seed=seed, weights=w),
                             hops=1, parts=p)
    return {
        "uniform": ps_even,
        "rapa_even": do_partition(ps_even, profiles, cfg).partition_set,
        "rapa_uneven": do_partition(ps_wtd, profiles, cfg).partition_set,
    }


def _variant_stats(task, ps, profiles, cfg) -> dict:
    """Cost model + padding + cache budgets + row accounting for one
    (partitioning, device group) pair."""
    lam = partition_lambdas(ps, profiles, cfg)
    sp = stack_partitions(ps, task)
    stats = sp.padding_stats()
    feat_dims = (task.features.shape[1], 128, 128)
    # cache budgets from the SAME profiles that shaped the partitions:
    # big-memory devices absorb more residents (per-part c_gpu)
    cap = cal_capacity(ps, feat_dims, profiles)
    plan = build_cache_plan(ps, cap, refresh_every=4)
    xplan = build_exchange_plan(ps, plan)
    xcap = exchange_capacity(ps, cap)

    # every halo position is served by exactly one tier; the p2p packed
    # blocks re-ship exactly the plan rows (one slot per (row, consumer),
    # one per unique global row) — three independent data structures
    halo_valid = int(sp.halo_valid.sum())
    served = (xplan.uncached.n_rows + xplan.local.n_rows
              + int(xplan.glob.read_valid.sum()))
    plan_rows = (xplan.uncached.n_rows + xplan.local.n_rows
                 + xplan.glob.n_unique)
    p2p_rows = xplan.transport_rows("p2p", refresh=True)["total"]
    padded_total = (int(stats["inner_padded_rows"])
                    + int(stats["halo_padded_rows"])
                    + int(stats["edges_padded_rows"]))
    return {
        "inner_sizes": [int(pt.n_inner) for pt in ps.parts],
        "halo_sizes": [int(pt.n_halo) for pt in ps.parts],
        "c_gpu": [int(c) for c in cap.c_gpu],
        "mem_gib": [float(pr.mem_gib) for pr in profiles],
        "lambda_max": float(lam.max()),
        "lambda_rel_std": float(lam.std() / max(lam.mean(), 1e-12)),
        "halo_valid_rows": int(stats["halo_valid_rows"]),
        "halo_padded_rows": int(stats["halo_padded_rows"]),
        "inner_padded_rows": int(stats["inner_padded_rows"]),
        "edges_padded_rows": int(stats["edges_padded_rows"]),
        "padded_rows_total": padded_total,
        "stack_waste_frac": float(stats["waste_frac"]),
        "capacity_waste_frac": float(xcap.padding_waste()["waste_frac"]),
        "plan_recv_rows": int(plan_rows),
        "p2p_packed_rows": int(p2p_rows),
        "halo_rows_served": int(served),
        "accounting_exact": bool(served == halo_valid
                                 and p2p_rows == plan_rows),
    }


def _sim_uneven_run(task, ps, profiles, tiny: bool) -> dict:
    """Drive the ragged masked stacks through the sim runtime end-to-end
    (the compiled step the launcher runs) on the most skewed group."""
    from repro.core import StalenessController
    from repro.dist import make_sim_runtime, train_capgnn
    from repro.models.gnn import GNNConfig
    from repro.optim import adam

    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=64, out_dim=task.num_classes, num_layers=3)
    p = ps.num_parts
    cap = cal_capacity(ps, cfg.feat_dims, profiles)
    plan = build_cache_plan(ps, cap, refresh_every=4)
    xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task)
    opt = adam(0.01)
    rt = make_sim_runtime(cfg, sp, xplan, opt)
    ctl = StalenessController(refresh_every=4)
    epochs = 2 if tiny else 6
    params, rep = train_capgnn(cfg, rt, xplan, p, opt, epochs=epochs,
                               controller=ctl, eval_every=0)
    _, acc = rt.evaluate(params, "test")
    return {
        "epochs": epochs,
        "final_loss": float(rep.losses[-1]),
        "loss_finite": bool(np.isfinite(rep.losses).all()),
        "test_acc": float(acc),
        "comm_bytes": int(rep.comm_bytes),
        "comm_reduction": float(rep.comm_reduction),
        "stack_waste_frac": float(rt.padding_stats()["waste_frac"]),
    }


def straggler_section(tiny: bool) -> dict:
    task = _flickr_task(tiny)
    g = task.graph
    cfg = RapaConfig(feat_dim=task.features.shape[1])
    groups = {}
    for grp in STRAGGLER_GROUPS:
        profiles = make_group(PAPER_GROUPS[grp])
        variants = _build_variants(g, profiles, cfg)
        stats = {name: _variant_stats(task, ps, profiles, cfg)
                 for name, ps in variants.items()}
        uni, unv = stats["uniform"], stats["rapa_uneven"]
        groups[grp] = {
            "parts": len(profiles),
            "capability_weights":
                [float(x) for x in capability_weights(profiles)],
            "variants": stats,
            "uneven_cuts_lambda_max": bool(
                unv["lambda_max"] < uni["lambda_max"]),
            # total padded rows of the [P, ...] stack (inner+halo+edges):
            # uniform splits look tight on halos alone but pay for the
            # straggler part's inner/edge overshoot; uneven partitions
            # trade halo spread for a much smaller total allocation
            "uneven_cuts_padded_rows": bool(
                unv["padded_rows_total"] < uni["padded_rows_total"]),
            "uneven_cuts_stack_waste": bool(
                unv["stack_waste_frac"] < uni["stack_waste_frac"]),
            "lambda_max_reduction": float(
                1.0 - unv["lambda_max"] / max(uni["lambda_max"], 1e-12)),
        }
    # end-to-end: the x8 uneven partitions through the compiled sim step
    profiles8 = make_group(PAPER_GROUPS["x8"])
    ps8 = _build_variants(g, profiles8, cfg)["rapa_uneven"]
    sim = _sim_uneven_run(task, ps8, profiles8, tiny)
    return {"num_nodes": int(g.num_nodes), "num_edges": int(g.num_edges),
            "groups": groups, "sim_uneven_x8": sim}


# --------------------------------------- SPMD transport child (4 devices)

def straggler_transport_child(tiny: bool) -> dict:
    """Runs in the forced-4-device subprocess: the x4-group uneven
    partitions through the compiled shard_map step over both halo
    transports — wire-row accounting + cross-transport loss agreement."""
    import jax
    jax.devices()           # lock the forced host device count first
    import jax.numpy as jnp
    from repro.dist import init_caches
    from repro.dist.capgnn_spmd import make_spmd_runtime
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.optim import adam

    task = _flickr_task(tiny)
    g = task.graph
    parts = 4
    profiles = make_group(PAPER_GROUPS["x4"])
    rcfg = RapaConfig(feat_dim=task.features.shape[1])
    w = capability_weights(profiles)
    ps = build_partition(g, metis_partition(g, parts, seed=0, weights=w),
                         hops=1, parts=parts)
    ps = do_partition(ps, profiles, rcfg).partition_set
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=64, out_dim=task.num_classes, num_layers=3)
    cap = cal_capacity(ps, cfg.feat_dims, profiles)
    plan = build_cache_plan(ps, cap, refresh_every=4)
    xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task)
    opt = adam(0.01)
    mesh = jax.make_mesh((parts,), ("data",))
    params = init_gnn(jax.random.PRNGKey(0), cfg)

    plan_rows = {"uncached": xplan.uncached.n_rows,
                 "local": xplan.local.n_rows,
                 "global": xplan.glob.n_unique}
    out = {"parts": parts, "tiny": bool(tiny),
           "inner_sizes": [int(pt.n_inner) for pt in ps.parts],
           "plan_rows": plan_rows, "transports": {}}
    losses = {}
    for transport in ("allgather", "p2p"):
        rt = make_spmd_runtime(cfg, sp, xplan, opt, mesh,
                               transport=transport)
        pp = jax.tree.map(jnp.copy, params)
        oo = opt.init(pp)
        cc = init_caches(cfg, xplan, parts)
        step_loss = {}
        for name, fn in (("cached", rt.step_cached),
                         ("refresh", rt.step_refresh),
                         ("pipelined", rt.step_pipelined)):
            pp, oo, cc, m = fn(pp, oo, cc)
            step_loss[name] = float(np.asarray(m["loss"]).ravel()[0])
        losses[transport] = step_loss
        out["transports"][transport] = {
            "refresh_rows": rt.wire_rows(True),
            "step_losses": step_loss,
            "losses_finite": bool(
                np.isfinite(list(step_loss.values())).all()),
        }

    p2p = out["transports"]["p2p"]["refresh_rows"]
    ag = out["transports"]["allgather"]["refresh_rows"]
    p2p_ok = (p2p["uncached"] == plan_rows["uncached"]
              and p2p["local"] == plan_rows["local"]
              and p2p["global"] == plan_rows["global"])
    # allgather replicates every owner's dedup send buffer to all P devices
    ag_ok = (ag["uncached"] == parts * xplan.uncached.n_send_rows
             and ag["local"] == parts * xplan.local.n_send_rows
             and ag["global"] == parts * int(xplan.glob.send_valid.sum()))
    out["rows_match_plan_both_transports"] = bool(p2p_ok and ag_ok)
    out["transport_losses_agree"] = bool(all(
        abs(losses["allgather"][k] - losses["p2p"][k]) <= 1e-5
        for k in ("cached", "refresh", "pipelined")))
    return out


def _transport_child_subprocess(tiny: bool) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"   # counts only; the parent holds the chip
    env["REPRO_BENCH_TINY"] = "1" if tiny else "0"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.heterogeneous",
         "--straggler-child"],
        capture_output=True, text=True, timeout=3600, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if res.returncode != 0:
        raise RuntimeError("straggler transport child failed:\n"
                           + res.stdout[-2000:] + res.stderr[-2000:])
    return json.loads(res.stdout.splitlines()[-1])


# ------------------------------------------------------------------ run

def run(out_dir: str = DEFAULT_OUT, tiny: bool | None = None) -> dict:
    if tiny is None:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
    rows = cost_model_rows(tiny)
    straggler = straggler_section(tiny)
    child = _transport_child_subprocess(tiny)

    # Eq. 15 objective is max(lambda) + Std(lambda): the max term is the
    # step-time bound, which is what heterogeneity blows up for uniform
    # splits.  (rel-std alone is misleading once lambda is near zero.)
    improved = all(r["rapa_max"] <= r["uniform_max"] * 1.001 for r in rows)
    grp = straggler["groups"]
    x8 = grp["x8"]
    out = {
        "tiny": bool(tiny),
        "rows": rows,
        "rapa_reduces_max_cost": bool(improved),
        "max_cost_reduction": max(1 - r["rapa_max"] / r["uniform_max"]
                                  for r in rows),
        "straggler": straggler,
        "straggler_transport": child,
        # gated headline claims (deterministic; see check_regression.py)
        "uneven_cuts_lambda_max": bool(all(
            g["uneven_cuts_lambda_max"] for g in grp.values())),
        "uneven_cuts_padded_rows_x8": bool(x8["uneven_cuts_padded_rows"]),
        "uneven_cuts_stack_waste_x8": bool(x8["uneven_cuts_stack_waste"]),
        "x8_lambda_max_reduction": float(x8["lambda_max_reduction"]),
        "x8_uniform_padded_rows":
            int(x8["variants"]["uniform"]["padded_rows_total"]),
        "x8_uneven_padded_rows":
            int(x8["variants"]["rapa_uneven"]["padded_rows_total"]),
        "straggler_accounting_exact": bool(all(
            v["accounting_exact"]
            for g in grp.values() for v in g["variants"].values())),
        "rows_match_plan_both_transports":
            bool(child["rows_match_plan_both_transports"]),
        "transport_losses_agree": bool(child["transport_losses_agree"]),
        "sim_uneven_loss_finite":
            bool(straggler["sim_uneven_x8"]["loss_finite"]),
    }
    save(out_dir, "heterogeneous", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--straggler-child", action="store_true",
                    help="internal: run only the SPMD transport check in "
                         "this (forced multi-device) process, JSON on "
                         "stdout")
    # parse_known_args: tolerate the benchmarks.run orchestrator's flags
    args, _ = ap.parse_known_args(argv)
    if args.straggler_child:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
        print(json.dumps(straggler_transport_child(tiny)))
        return
    out = run()
    print("heterogeneous: RAPA reduces max cost =",
          out["rapa_reduces_max_cost"],
          f"(best reduction {out['max_cost_reduction']:.1%})")
    for r in out["rows"]:
        print(f"  {r['group']} (het {r['heterogeneity']:.1f}x): max "
              f"{r['uniform_max']:.2e} -> {r['rapa_max']:.2e}, rel-std "
              f"{r['uniform_rel_std']:.3f} -> {r['rapa_rel_std']:.3f}")
    for grp, g in out["straggler"]["groups"].items():
        uni = g["variants"]["uniform"]
        unv = g["variants"]["rapa_uneven"]
        print(f"  straggler {grp}: lambda_max {uni['lambda_max']:.2e} -> "
              f"{unv['lambda_max']:.2e} ({g['lambda_max_reduction']:.1%}), "
              f"stack padded rows {uni['padded_rows_total']} -> "
              f"{unv['padded_rows_total']} (waste "
              f"{uni['stack_waste_frac']:.3f} -> "
              f"{unv['stack_waste_frac']:.3f})")
    sim = out["straggler"]["sim_uneven_x8"]
    print(f"  sim x8 uneven: loss {sim['final_loss']:.4f}, acc "
          f"{sim['test_acc']:.3f}, comm saved {sim['comm_reduction']:.1%}")
    print(f"  accounting exact = {out['straggler_accounting_exact']}, "
          f"wire rows match plan (both transports) = "
          f"{out['rows_match_plan_both_transports']}, "
          f"transport losses agree = {out['transport_losses_agree']}")


if __name__ == "__main__":
    main()
