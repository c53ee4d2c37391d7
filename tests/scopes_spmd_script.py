"""Subprocess helper for test_scopes: lowers the refresh, cached and
pipelined steps of the SPMD CaPGNN runtime on 4 forced host devices and of
the sim runtime on the same graph (GCN and SAGE, ``edges`` backend), and
checks that

- every instruction of each step sits under exactly one phase scope of
  ``repro.obs.annotations.PHASES``, layer parts inside a layer;
- both runtimes open the same phases, save the SPMD runtime's
  ``grad_sync`` and, with the p2p transport, the pipelined step's
  ``refresh_ring_*`` in place of its ``tier_pull_refresh``.

Invoked as:  python tests/scopes_spmd_script.py [--transport allgather|p2p]
Prints OK and exits zero on success.
"""
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402


def main():
    transport = (sys.argv[sys.argv.index("--transport") + 1]
                 if "--transport" in sys.argv else "allgather")
    jax.devices()           # lock the forced host device count first
    from scope_tree import layer_parts, step_phases

    from repro.core import CacheCapacity, build_cache_plan
    from repro.data.gnn_data import FullBatchTask, split_masks
    from repro.dist import (TrainSpec, build_exchange_plan, make_sim_runtime,
                            stack_partitions)
    from repro.dist.capgnn_sim import init_caches
    from repro.dist.capgnn_spmd import make_spmd_runtime
    from repro.graph import (build_partition, metis_partition, rmat,
                             symmetric_normalize, synth_features)
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.optim import adam

    parts = 4
    g = rmat(240, 1400, seed=7)
    feats, labels = synth_features(g, 8, 4, seed=7)
    gn = symmetric_normalize(g)
    trm, va, te = split_masks(g.num_nodes, seed=7)
    task = FullBatchTask(graph=gn, features=feats, labels=labels,
                         train_mask=trm, val_mask=va, test_mask=te,
                         num_classes=4)
    ps = build_partition(gn, metis_partition(gn, parts, seed=7), hops=1)
    max_halo = max(pt.n_halo for pt in ps.parts)
    cap = CacheCapacity(c_gpu=[max(1, max_halo // 3)] * parts,
                        c_cpu=max(1, max_halo))
    xplan = build_exchange_plan(ps, build_cache_plan(ps, cap,
                                                     refresh_every=2))
    sp = stack_partitions(ps, task)
    opt = adam(1e-2)
    mesh = jax.make_mesh((parts,), ("data",))
    spec = TrainSpec(transport=transport, refresh_every=2, pipeline=True)
    rings = {"refresh_ring_issue", "refresh_ring_advance",
             "refresh_ring_finish"}
    for model in ("gcn", "sage"):
        cfg = GNNConfig(model=model, in_dim=8, hidden_dim=8, out_dim=4,
                        num_layers=3)
        sim = make_sim_runtime(cfg, sp, xplan, opt, spec=spec)
        spmd = make_spmd_runtime(cfg, sp, xplan, opt, mesh, spec=spec)
        params = init_gnn(jax.random.PRNGKey(0), cfg)
        caches = init_caches(cfg, xplan, parts)
        for flavour in ("refresh", "cached", "pipelined"):
            args = (params, opt.init(params), caches)
            low_sim = sim.lower_step(flavour, *args)
            # the SPMD steps take the caches laid out on the mesh
            low_spmd = spmd.lower_step(flavour, params, opt.init(params),
                                       spmd.caches0)
            want = set(step_phases(low_sim)) | {"grad_sync"}
            got = set(step_phases(low_spmd))
            if transport == "p2p" and flavour == "pipelined":
                want = (want - {"tier_pull_refresh"}) | rings
            assert got == want, (model, flavour, sorted(got), sorted(want))
            assert layer_parts(low_spmd) == layer_parts(low_sim) == {
                "aggregate", "transform"}, (model, flavour)
            print(model, flavour, sorted(got))
    print("OK")


if __name__ == "__main__":
    main()
