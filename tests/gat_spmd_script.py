"""Subprocess helper for test_gat: the SPMD runtime on 4 forced host
devices against the sim runtime on the same graph, for the published GAT
over a refresh, a cached, a pipelined and a cached step.  The SPMD shards
keep a padded edge rectangle, whose padding edges must add nothing and
take no gradient.  Prints OK and exits zero when the losses and the
parameters after each step agree."""
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main():
    jax.devices()           # lock the forced host device count first
    from repro.core import CacheCapacity, build_cache_plan
    from repro.data.gnn_data import FullBatchTask, split_masks
    from repro.dist import (TrainSpec, build_exchange_plan, init_caches,
                            make_sim_runtime, stack_partitions)
    from repro.dist.capgnn_spmd import make_spmd_runtime
    from repro.graph import (build_partition, metis_partition, rmat,
                             symmetric_normalize, synth_features)
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.optim import sgd

    g = rmat(240, 1400, seed=7)
    feats, labels = synth_features(g, 8, 4, seed=7)
    gn = symmetric_normalize(g)
    trm, va, te = split_masks(g.num_nodes, seed=7)
    task = FullBatchTask(graph=gn, features=feats, labels=labels,
                         train_mask=trm, val_mask=va, test_mask=te,
                         num_classes=4)
    ps = build_partition(gn, metis_partition(gn, 4, seed=7), hops=1)
    mh = max(pt.n_halo for pt in ps.parts)
    cap = CacheCapacity(c_gpu=[max(1, mh // 3)] * 4, c_cpu=mh)
    xplan = build_exchange_plan(ps, build_cache_plan(ps, cap,
                                                     refresh_every=2))
    sp = stack_partitions(ps, task)
    assert sp.padding_stats()["edges_padded_rows"] > 0
    cfg = GNNConfig(model="gat", in_dim=8, hidden_dim=16, out_dim=4,
                    num_layers=3)
    opt = sgd(1.0)
    spec = TrainSpec(refresh_every=2, pipeline=True, donate=False)
    sim = make_sim_runtime(cfg, sp, xplan, opt, spec=spec)
    spmd = make_spmd_runtime(cfg, sp, xplan, opt,
                             jax.make_mesh((4,), ("data",)), spec=spec)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    a = (params, opt.init(params), init_caches(cfg, xplan, 4))
    b = (params, opt.init(params), spmd.caches0)
    for name in ("step_refresh", "step_cached", "step_pipelined",
                 "step_cached"):
        *a, ma = getattr(sim, name)(*a)
        *b, mb = getattr(spmd, name)(*b)
        # float32 round-off of sums taken in another order
        np.testing.assert_allclose(float(mb["loss"]), float(ma["loss"]),
                                   rtol=1e-5, err_msg=name)
        for x, y in zip(jax.tree.leaves(b[0]), jax.tree.leaves(a[0])):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-6, err_msg=name)
        print(name, float(ma["loss"]), float(mb["loss"]))
    print("OK")


if __name__ == "__main__":
    sys.exit(main())
