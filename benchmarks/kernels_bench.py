"""Operator-level benchmark: ELL padding waste + kernel-vs-oracle parity on
partition-shaped workloads (the paper's SpMM hot spot, Table 1's compute
side), plus ELL pack statistics before/after RAPA pruning and an
end-to-end aggregation-backend sweep (edges vs Pallas ell/hybrid through
the stacked runtime — logit parity + per-step wall time).

``REPRO_BENCH_TINY=1`` shrinks the task for CI smoke runs.  The Pallas
kernels follow the platform: compiled on a TPU, interpreted elsewhere.
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax.numpy as jnp

from repro.core import PAPER_GROUPS, RapaConfig, do_partition, make_group
from repro.graph import build_partition, metis_partition
from repro.kernels.ops import (ell_pack, ell_pack_hybrid, ell_spmm,
                               ell_stats, hybrid_spmm)
from repro.kernels import ref as R
from ._util import BENCH_SCALE, DEFAULT_OUT, bench_task, save


def _pack_partition(part):
    src, dst = part.local_graph.edges()
    keep = dst < part.n_inner
    w = part.local_graph.edge_weight
    w = w[keep] if w is not None else np.ones(keep.sum(), np.float32)
    return ell_pack(src[keep], dst[keep], w, part.n_inner)


def _backend_sweep(task, ps, epochs: int = 2) -> dict:
    """Same exchange plan + caches through every runtime backend: logit
    parity vs the edge-list reference and per-refresh-step wall time."""
    import jax
    from repro.core import PROFILES, build_cache_plan, cal_capacity
    from repro.dist import (build_exchange_plan, init_caches,
                            make_sim_runtime, stack_partitions)
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.optim import adam

    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=64, out_dim=task.num_classes, num_layers=3)
    cap = cal_capacity(ps, cfg.feat_dims,
                       [PROFILES["rtx3090"]] * ps.num_parts)
    plan = build_cache_plan(ps, cap, refresh_every=2)
    xplan = build_exchange_plan(ps, plan)
    opt = adam(1e-2)
    params = init_gnn(jax.random.PRNGKey(0), cfg)

    sweep = {}
    logits_ref = None
    for backend in ("edges", "ell", "hybrid"):
        sp = stack_partitions(ps, task, backend=backend)
        rt = make_sim_runtime(cfg, sp, xplan, opt, backend=backend)
        logits = np.asarray(rt.forward_fresh(params))
        if logits_ref is None:
            logits_ref = logits
        # the jitted steps donate their inputs: chain the returned state
        # (the realistic steady-state loop) instead of re-using arguments
        p_b = jax.tree.map(jnp.copy, params)
        opt_state = opt.init(p_b)
        caches = init_caches(cfg, xplan, ps.num_parts)
        p_b, opt_state, caches, m = rt.step_refresh(p_b, opt_state, caches)
        jax.block_until_ready(m["loss"])            # compile + run warm-up
        t0 = time.perf_counter()
        for _ in range(epochs):
            p_b, opt_state, caches, m = rt.step_refresh(p_b, opt_state,
                                                        caches)
        jax.block_until_ready(m["loss"])
        row = {"step_ms": (time.perf_counter() - t0) / epochs * 1e3,
               "logit_max_diff": float(np.abs(logits - logits_ref).max())}
        if sp.ell is not None:
            row["max_deg"] = sp.ell.max_deg
            row["tail_edges"] = int((sp.ell.tail_w != 0).sum())
        sweep[backend] = row
    return sweep


def run(out_dir: str = DEFAULT_OUT, tiny: bool | None = None) -> dict:
    if tiny is None:
        tiny = bool(int(os.environ.get("REPRO_BENCH_TINY", "0")))
    if tiny:
        from repro.data import make_task
        task = make_task("flickr", scale=BENCH_SCALE["flickr"] / 8,
                         feat_dim=64)
    else:
        task = bench_task("flickr")
    g = task.graph
    profiles = make_group(PAPER_GROUPS["x4"])
    ps = build_partition(g, metis_partition(g, 4, seed=0), hops=1)
    res = do_partition(ps, profiles, RapaConfig(feat_dim=64))

    rows = []
    for tag, pset in (("metis", ps), ("rapa", res.partition_set)):
        for part in pset.parts:
            cols, vals = _pack_partition(part)
            st = ell_stats(cols, vals)
            # kernel parity on the real partition shape
            h = np.random.default_rng(0).normal(
                size=(part.n_local, 64)).astype(np.float32)
            out = ell_spmm(jnp.asarray(cols), jnp.asarray(vals),
                           jnp.asarray(h))
            want = R.ell_spmm_ref(jnp.asarray(cols), jnp.asarray(vals),
                                  jnp.asarray(h))
            err = float(np.abs(np.asarray(out) - np.asarray(want)).max())
            # hybrid ELL+COO pack (beyond-paper): quantile-capped width
            src, dst = part.local_graph.edges()
            keep = dst < part.n_inner
            w = part.local_graph.edge_weight
            w = (w[keep] if w is not None
                 else np.ones(keep.sum(), np.float32))
            hc, hv, ts, td, tw = ell_pack_hybrid(src[keep], dst[keep], w,
                                                 part.n_inner)
            hyb = hybrid_spmm(jnp.asarray(hc), jnp.asarray(hv),
                              jnp.asarray(ts), jnp.asarray(td),
                              jnp.asarray(tw), jnp.asarray(h))
            err_h = float(np.abs(np.asarray(hyb) - np.asarray(want)).max())
            st_h = ell_stats(hc, hv)
            rows.append({"partitioner": tag, "part": part.part_id, **st,
                         "kernel_max_err": err,
                         "hybrid_pad_waste": st_h["pad_waste"],
                         "hybrid_tail_edges": int(ts.shape[0]),
                         "hybrid_max_err": err_h})
    waste_metis = np.mean([r["pad_waste"] for r in rows
                           if r["partitioner"] == "metis"])
    waste_rapa = np.mean([r["pad_waste"] for r in rows
                          if r["partitioner"] == "rapa"])
    out = {"tiny": bool(tiny), "rows": rows,
           "pad_waste_metis": float(waste_metis),
           "pad_waste_rapa": float(waste_rapa),
           "pad_waste_hybrid": float(np.mean([r["hybrid_pad_waste"]
                                              for r in rows])),
           "max_kernel_err": max(r["kernel_max_err"] for r in rows),
           "max_hybrid_err": max(r["hybrid_max_err"] for r in rows),
           "backend_sweep": _backend_sweep(task, ps)}
    save(out_dir, "kernels_bench", out)
    return out


def main():
    out = run()
    print(f"kernels: pad waste metis {out['pad_waste_metis']:.2%} -> "
          f"rapa {out['pad_waste_rapa']:.2%} -> hybrid ELL+COO "
          f"{out['pad_waste_hybrid']:.2%}; "
          f"max |kernel - oracle| = {out['max_kernel_err']:.2e}, "
          f"hybrid {out['max_hybrid_err']:.2e}")
    for be, row in out["backend_sweep"].items():
        print(f"  backend {be:7s}: {row['step_ms']:.1f} ms/refresh-step, "
              f"logit max diff {row['logit_max_diff']:.2e}")


if __name__ == "__main__":
    main()
