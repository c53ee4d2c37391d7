"""The training step's one named-scope tree (``repro.obs.annotations``).

Every instruction of a lowered step sits under exactly one phase
(``layer<i>``, ``tier_pull_*``, ``refresh_ring_*``, ``cache_read``,
``cache_update``, ``loss``, ``grad_sync``, ``optimizer``), and a layer
splits into ``aggregate`` and ``transform``.  The sim runtime is lowered
here; the SPMD runtime on four forced host devices, against the sim on the
same graph, in ``scopes_spmd_script.py``.
"""
import os
import subprocess
import sys

import pytest

from scope_tree import step_op_names, layer_parts, step_phases

_SCRIPT = os.path.join(os.path.dirname(__file__), "scopes_spmd_script.py")
_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
FLAVOURS = ("refresh", "cached", "pipelined")


def _sim(model, features):
    import jax
    from repro.core import CacheCapacity, build_cache_plan
    from repro.data.gnn_data import FullBatchTask, split_masks
    from repro.dist import (TrainSpec, build_exchange_plan, make_sim_runtime,
                            stack_partitions)
    from repro.dist.capgnn_sim import init_caches
    from repro.graph import (build_partition, metis_partition, rmat,
                             symmetric_normalize, synth_features)
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.optim import adam

    g = rmat(200, 1100, seed=9)
    feats, labels = synth_features(g, 8, 4, seed=9)
    gn = symmetric_normalize(g)
    trm, va, te = split_masks(g.num_nodes, seed=9)
    task = FullBatchTask(graph=gn, features=feats, labels=labels,
                         train_mask=trm, val_mask=va, test_mask=te,
                         num_classes=4)
    ps = build_partition(gn, metis_partition(gn, 2, seed=9), hops=1)
    cfg = GNNConfig(model=model, in_dim=8, hidden_dim=8, out_dim=4,
                    num_layers=3)
    max_halo = max(pt.n_halo for pt in ps.parts)
    cap = CacheCapacity(c_gpu=[max(1, max_halo // 3)] * 2,
                        c_cpu=max(1, max_halo))
    xplan = build_exchange_plan(ps, build_cache_plan(ps, cap,
                                                     refresh_every=2))
    opt = adam(1e-2)
    spec = TrainSpec(features=features, refresh_every=2, pipeline=True)
    rt = make_sim_runtime(cfg, stack_partitions(ps, task), xplan, opt,
                          spec=spec)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    return rt, (params, opt.init(params),
                init_caches(cfg, xplan, 2, features=features))


@pytest.mark.parametrize("features", ["device", "host"])
@pytest.mark.parametrize("model", ["gcn", "sage"])
def test_sim_step_scope_tree(model, features):
    rt, args = _sim(model, features)
    for flavour in FLAVOURS:
        low = rt.lower_step(flavour, *args)
        phases = set(step_phases(low))
        want = {"layer0", "layer1", "layer2", "cache_read",
                "tier_pull_uncached", "loss", "optimizer"}
        if flavour != "cached":
            # the steps that emit cache rows pull them fresh
            want.add("tier_pull_refresh")
            # and measure their drift, where the step holds a stale source
            # (a host refresh holds none)
            if flavour == "pipelined" or features == "device":
                want.add("cache_update")
        assert phases == want, (flavour, phases)
        assert layer_parts(low) == {"aggregate", "transform"}
        names = step_op_names(low)
        assert not any("spmm_layer" in n or "/spmm/" in n for n in names)


def test_device_scope_refuses_names_outside_the_vocabulary():
    from repro.obs import LAYER_PARTS, PHASES, device_scope
    for name in ["layer0", "layer12", *LAYER_PARTS,
                 *(k for k in PHASES if k != "layer{i}")]:
        with device_scope(name):
            pass
    for name in ("spmm_layer", "layer1/spmm", "layer{i}", "layers"):
        with pytest.raises(ValueError, match="not a scope"):
            device_scope(name)


@pytest.mark.parametrize("transport", ["allgather", "p2p"])
def test_spmd_matches_sim_scope_tree(transport):
    """The SPMD runtime on four forced host devices opens the sim's phases
    (plus ``grad_sync``; with p2p the pipelined step's rings in place of
    its refresh pulls), each instruction under exactly one."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, _SCRIPT, "--transport", transport],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-3000:]
    assert "OK" in res.stdout
