"""Subprocess helper for test_spmm15d: runs the 1.5D replicated-row SpMM
strategy on 4 forced host devices and checks it against the halo_1d sim
oracle at refresh_every=1 (the exact single-worker reference).  Exits
non-zero on any mismatch.

Invoked as:  python tests/spmm15d_parity_script.py [--eight]

``--eight`` forces 8 host devices instead and runs the ``c=2, pr=4``
(g=2) case — permute, gather and allreduce all live in one step.

Covers, per ISSUE 10's acceptance criteria:

- ``c=2`` (pr=2, g=1 — the permute + allreduce path) and ``c=1`` (pr=4,
  g=4 — the degenerate dense-1D all_gather path) on the same graph;
- logits parity <= 1e-5 vs the oracle's fresh forward (valid rows);
- explicit grads parity <= 1e-5 (one sgd(1.0) step: the param delta IS
  the gradient — this would expose the classic uniform-c / c**2
  replication-cotangent bugs exactly);
- loss-trajectory parity <= 1e-5 over 6 adam epochs;
- modeled forward collective bytes == HLO-measured
  (:func:`repro.launch.hlo_cost.collective_bytes` over the compiled
  forward), including the ``exchange_layer0=False`` pre-replicated
  variant.
"""
import os
import sys

NDEV = 8 if "--eight" in sys.argv else 4
os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={NDEV} "
                           + os.environ.get("XLA_FLAGS", ""))

import numpy as np  # noqa: E402
import jax  # noqa: E402


def build_problem(parts):
    from repro.core import PROFILES, build_cache_plan, cal_capacity
    from repro.data.gnn_data import FullBatchTask, split_masks
    from repro.dist import build_exchange_plan, stack_partitions
    from repro.graph import (build_partition, metis_partition, rmat,
                             symmetric_normalize, synth_features)
    from repro.models.gnn import GNNConfig

    g = rmat(360, 2200, seed=3)
    feats, labels = synth_features(g, 12, 5, seed=3)
    gn = symmetric_normalize(g)
    tr, va, te = split_masks(g.num_nodes, seed=3)
    task = FullBatchTask(graph=gn, features=feats, labels=labels,
                         train_mask=tr, val_mask=va, test_mask=te,
                         num_classes=5)
    ps = build_partition(gn, metis_partition(gn, parts, seed=3), hops=1)
    cfg = GNNConfig(model="gcn", in_dim=12, hidden_dim=16, out_dim=5,
                    num_layers=3)
    cap = cal_capacity(ps, cfg.feat_dims, [PROFILES["rtx3090"]] * parts)
    plan = build_cache_plan(ps, cap, refresh_every=1)
    xplan = build_exchange_plan(ps, plan)
    sp = stack_partitions(ps, task)
    return ps, task, cfg, sp, xplan


def check_case(c, pr, exchange_layer0=True):
    import jax.numpy as jnp
    from repro.dist import TrainSpec, get_strategy
    from repro.dist.capgnn_sim import make_sim_runtime, train_capgnn
    from repro.dist.strategy_15d import (build_spmm15d_layout,
                                         make_spmm15d_runtime,
                                         train_spmm15d)
    from repro.launch.hlo_cost import collective_bytes
    from repro.models.gnn import init_gnn
    from repro.optim import adam, sgd

    ps, task, cfg, sp, xplan = build_problem(pr)
    spec15 = TrainSpec(strategy="spmm_15d", replication=c,
                       exchange_layer0=exchange_layer0, donate=False)
    layout = build_spmm15d_layout(ps, task, spec15)
    assert layout.edges_total == sum(
        int((np.asarray(pt.local_graph.edges()[1]) < pt.n_inner).sum())
        for pt in ps.parts), "replica edge chunks must partition the edges"

    # --- oracle: halo_1d sim at refresh_every=1, identical spec knobs
    spec1d = TrainSpec(strategy="halo_1d", donate=False,
                       exchange_layer0=exchange_layer0)
    opt = adam(1e-2)
    sim = make_sim_runtime(cfg, sp, xplan, opt, spec=spec1d)
    rt = make_spmm15d_runtime(cfg, layout, opt, spec15)

    params = init_gnn(jax.random.PRNGKey(7), cfg)
    valid = np.asarray(sp.inner_valid)                      # [pr, NI]

    # ---- logits parity (every replica against its block row)
    lo_sim = np.asarray(sim.forward_fresh(params), np.float64)
    lo_15 = np.asarray(rt.forward_fresh(params), np.float64)
    for i in range(pr):
        for j in range(c):
            d = np.abs(lo_15[i * c + j][valid[i]] - lo_sim[i][valid[i]])
            assert d.max() <= 1e-5, (c, pr, i, j, d.max())

    # ---- explicit grads parity: one sgd(1.0) step, param delta == -grad
    s1 = sgd(1.0)
    sim_s = make_sim_runtime(cfg, sp, xplan, s1, spec=spec1d)
    rt_s = make_spmm15d_runtime(cfg, layout, s1, spec15)
    p_sim, _, _, m_sim = sim_s.step_refresh(params, s1.init(params),
                                            jax.tree.map(jnp.asarray,
                                                         sim_s.caches0))
    p_15, _, m_15 = rt_s.step(params, s1.init(params))
    assert abs(float(m_sim["loss"]) - float(m_15["loss"])) <= 1e-5
    for a, b in zip(jax.tree.leaves(p_sim), jax.tree.leaves(p_15)):
        d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
        assert d.max() <= 1e-5, (c, pr, d.max())

    # ---- loss trajectory over 6 adam epochs
    _, rep_sim = train_capgnn(cfg, sim, xplan, pr, opt, epochs=6,
                              spec=spec1d)
    _, rep_15 = train_spmm15d(cfg, rt, opt, spec15, epochs=6)
    traj = np.abs(np.asarray(rep_sim.losses) - np.asarray(rep_15.losses))
    assert traj.max() <= 1e-5, (c, pr, rep_sim.losses, rep_15.losses)
    assert rep_15.spec["strategy"] == "spmm_15d"
    assert rep_15.spec["replication"] == c

    # ---- byte-accounting contract: modeled == HLO-measured forward
    hlo = rt.lower_forward(params).compile().as_text()
    measured = collective_bytes(hlo)["total"]
    assert measured == rt.forward_bytes_per_device, (
        c, pr, measured, rt.forward_bytes_per_device,
        collective_bytes(hlo))
    strat = get_strategy("spmm_15d")
    assert strat.step_bytes(layout, cfg, spec15) == \
        rt.forward_bytes_per_device * layout.n_devices
    print(f"OK c={c} pr={pr} g={layout.g} xl0={exchange_layer0} "
          f"loss0={rep_15.losses[0]:.5f} "
          f"fwd_bytes/dev={rt.forward_bytes_per_device} (== HLO)")
    return float(traj.max()), measured


def main():
    if NDEV == 8:
        check_case(c=2, pr=4)                      # permute+gather+psum
    else:
        check_case(c=2, pr=2)                      # permute + psum path
        check_case(c=1, pr=4)                      # dense-1D gather path
        check_case(c=2, pr=2, exchange_layer0=False)  # pre-replicated
    print("OK spmm15d parity")


if __name__ == "__main__":
    main()
