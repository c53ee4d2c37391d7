"""The published GAT (Velickovic et al. 2018, section 3.3) against a plain
float32 reference (``gat_reference.py``) on seeded random weights:
the whole graph through ``gnn_forward``, and the sim runtime over a
refresh, a cached, a pipelined and a cached step, where stale halo rows
feed both the logits and the weighted sums.

Tolerances: the program and the reference compute the same float32
operations in other orders (heads sliced from one matmul or computed
apart, sums over in-edges in another order, the softmax's maximum kept
out of the gradient), so they part by float32 round-off over three
layers, ~1e-7 relative; the limits are 100x that and below what a
missing head, skip or stale row moves (1e-4 of the loss and more)."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gat_reference as ref
from repro.core import CacheCapacity, build_cache_plan
from repro.data.gnn_data import FullBatchTask, split_masks
from repro.dist import (TrainSpec, build_exchange_plan, init_caches,
                        make_sim_runtime, stack_partitions, train_capgnn)
from repro.graph import (build_partition, metis_partition, rmat,
                         symmetric_normalize, synth_features)
from repro.models.gnn import (EdgeListAdj, GNNConfig, cross_entropy_loss,
                              gnn_forward, head_sum, init_gnn,
                              make_local_adj)
from repro.obs import Tracer
from repro.optim import adam, sgd

REPO = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
RTOL, ATOL = 1e-5, 1e-6
FEAT, CLASSES = 12, 5


def _cfg(**kw):
    base = dict(model="gat", in_dim=FEAT, hidden_dim=16, num_heads=4,
                out_heads=6, out_dim=CLASSES, num_layers=3)
    return GNNConfig(**{**base, **kw})


@pytest.fixture(scope="module")
def task():
    g = rmat(300, 1800, seed=3)
    feats, labels = synth_features(g, FEAT, CLASSES, seed=3)
    gn = symmetric_normalize(g)
    tr, va, te = split_masks(g.num_nodes, seed=3)
    return FullBatchTask(graph=gn, features=feats, labels=labels,
                         train_mask=tr, val_mask=va, test_mask=te,
                         num_classes=CLASSES)


def _close(got, want, what):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(np.max(np.abs(np.asarray(b))))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=RTOL,
                                   atol=ATOL * max(scale, 1.0),
                                   err_msg=what)


def test_full_graph_matches_reference(task):
    cfg = _cfg()
    params = init_gnn(jax.random.PRNGKey(7), cfg)
    x = jnp.asarray(task.features)
    src, dst = (jnp.asarray(a) for a in task.graph.edges())
    labels = jnp.asarray(task.labels)
    mask = jnp.asarray(task.train_mask, jnp.float32)
    adj = make_local_adj(task.graph, task.graph.num_nodes, backend="edges")

    def prog_loss(p):
        return cross_entropy_loss(gnn_forward(cfg, p, adj, x, None), labels,
                                  mask)

    want, _ = ref.forward(params, x, src, dst, x.shape[0])
    _close(gnn_forward(cfg, params, adj, x, None), want, "logits")
    _close(jax.value_and_grad(prog_loss)(params),
           jax.value_and_grad(ref.loss)(params, x, src, dst, labels, mask),
           "loss and gradients")


def _global_edges(ps, plan):
    """Every kept edge in global ids, and whether its source is read from a
    cache tier on a cached step (a halo row of a cached tier)."""
    src, dst, stale = [], [], []
    for part, w in zip(ps.parts, plan.workers):
        ls, ld = part.local_graph.edges()
        keep = ld < part.n_inner
        ls, ld = ls[keep], ld[keep]
        gids = np.concatenate([part.inner_nodes, part.halo_nodes])
        cached = np.zeros(part.n_halo + 1, bool)
        cached[np.concatenate([w.local_pos, w.global_pos])] = True
        src.append(gids[ls])
        dst.append(gids[ld])
        stale.append(cached[np.where(ls >= part.n_inner, ls - part.n_inner,
                                     part.n_halo)])
    return (jnp.asarray(np.concatenate(src)),
            jnp.asarray(np.concatenate(dst)), jnp.asarray(np.concatenate(stale)))


@pytest.fixture(scope="module")
def sim(task):
    ps = build_partition(task.graph, metis_partition(task.graph, 4, seed=3),
                         hops=1)
    max_halo = max(pt.n_halo for pt in ps.parts)
    cap = CacheCapacity(c_gpu=[max(1, max_halo // 3)] * ps.num_parts,
                        c_cpu=max(1, max_halo // 2))
    plan = build_cache_plan(ps, cap, refresh_every=4)
    return ps, plan, build_exchange_plan(ps, plan), stack_partitions(ps, task)


def test_sim_steps_match_reference_with_stale_rows(task, sim):
    ps, plan, xplan, sp = sim
    cfg = _cfg()
    # sgd(1.0): each step's gradient is the change of the parameters
    opt = sgd(1.0)
    rt = make_sim_runtime(cfg, sp, xplan, opt,
                          spec=TrainSpec(donate=False, refresh_every=4))
    src, dst, stale = _global_edges(ps, plan)
    assert bool(stale.any()) and not bool(stale.all())
    x = jnp.asarray(task.features)
    labels = jnp.asarray(task.labels)
    mask = jnp.asarray(task.train_mask, jnp.float32)

    params = init_gnn(jax.random.PRNGKey(11), cfg)
    state = (params, opt.init(params), init_caches(cfg, xplan, ps.num_parts))
    cached_inputs = None
    for kind in ("refresh", "cached", "pipelined", "cached"):
        step = {"refresh": rt.step_refresh, "cached": rt.step_cached,
                "pipelined": rt.step_pipelined}[kind]
        params = state[0]
        *state, m = step(*state)
        use = None if kind == "refresh" else cached_inputs
        want_loss, want_grad = jax.value_and_grad(ref.loss)(
            params, x, src, dst, labels, mask, stale, use)
        _close(float(m["loss"]), want_loss, f"{kind} loss")
        _close(jax.tree.map(lambda a, b: a - b, params, state[0]), want_grad,
               f"{kind} gradients")
        if use is not None:
            # the stale rows move the loss by far more than the tolerance
            fresh = ref.loss(params, x, src, dst, labels, mask)
            assert abs(float(fresh) - float(want_loss)) > 10 * RTOL * fresh
        if kind != "cached":
            # the caches now hold this step's layer inputs
            _, cached_inputs = ref.forward(params, x, src, dst, x.shape[0],
                                           stale, use)


def test_spmd_runtime_matches_sim():
    """The SPMD runtime on four forced host devices builds the same model:
    its padded edge rectangle adds nothing (``gat_spmd_script.py``)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(REPO / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, str(HERE / "gat_spmd_script.py")],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-3000:]
    assert "OK" in res.stdout


def test_checkpointed_head_sum_keeps_no_edge_rows():
    rng = np.random.default_rng(5)
    rows, n, e, k, width = 40, 30, 200, 2, 8
    z = jnp.asarray(rng.normal(size=(rows, k * width)), jnp.float32)
    alpha = jnp.asarray(rng.random((k, e)), jnp.float32)
    adj = EdgeListAdj(jnp.asarray(rng.integers(0, rows, e), jnp.int32),
                      jnp.asarray(rng.integers(0, n, e), jnp.int32),
                      jnp.ones(e, jnp.float32), n, rows)
    cot = jnp.asarray(rng.normal(size=(n, k * width)), jnp.float32)

    def grads(fn):
        out, vjp = jax.vjp(fn, z, alpha)
        return out, vjp(cot), {a.shape for a in jax.tree.leaves(vjp)}

    out_p, g_plain, kept_plain = grads(lambda z, a: adj.spmm_at(a, z))
    out_c, g_remat, kept_remat = grads(lambda z, a: head_sum(adj, a, z))
    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_p))
    # each head's columns weighed by its own coefficients
    for h in range(k):
        np.testing.assert_allclose(
            np.asarray(out_p[:, h * width:(h + 1) * width]),
            np.asarray(adj.spmm_at(alpha[h], z[:, h * width:(h + 1) * width])),
            rtol=1e-6)
    for a, b in zip(g_remat, g_plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
    # autodiff alone keeps the gathered source rows; the checkpoint keeps
    # only its inputs
    assert (e, k * width) in kept_plain
    assert not any(s[:1] == (e,) and len(s) == 2 for s in kept_remat)


def _bench_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_gat", REPO / "bench" / "reference" / "gat.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("widths", [(FEAT, 16, CLASSES), (500, 1024, 7)],
                         ids=["tiny", "published"])
def test_init_tree_equals_bench_reference(widths):
    din, hidden, classes = widths
    cfg = _cfg(in_dim=din, hidden_dim=hidden, out_dim=classes)
    bench = _bench_reference()
    assert (bench.HEADS, bench.OUT_HEADS) == (cfg.num_heads, cfg.out_heads)
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)  # noqa: E731
    ours = jax.eval_shape(lambda: init_gnn(jax.random.PRNGKey(0), cfg))
    theirs = jax.eval_shape(lambda: bench.init(
        jax.random.PRNGKey(0), [din, hidden, hidden, classes]))
    assert shapes(ours) == shapes(theirs)
    assert [sorted(p) for p in ours] == [["a_dst", "a_src", "b", "w"]] * 3
    assert ours[-1]["w"].shape == (hidden, cfg.out_heads * classes)
    assert ours[-1]["b"].shape == (classes,)


def test_heads_must_divide_hidden_width():
    with pytest.raises(ValueError, match="num_heads=4 does not divide"):
        init_gnn(jax.random.PRNGKey(0), _cfg(hidden_dim=30))


def test_attention_counter_counts_edge_heads(task, sim):
    ps, plan, xplan, sp = sim
    counts = {}
    for model in ("gat", "gcn"):
        cfg = _cfg(model=model)
        rt = make_sim_runtime(cfg, sp, xplan, adam(1e-2),
                              spec=TrainSpec(refresh_every=4))
        tr = Tracer()
        train_capgnn(cfg, rt, xplan, ps.num_parts, adam(1e-2), epochs=2,
                     tracer=tr, spec=TrainSpec(refresh_every=4))
        counts[model] = {c.attention_edge_heads for c in tr.counters}
    edges = int(sp.n_edges.sum())
    assert counts == {"gat": {(4 + 4 + 6) * edges}, "gcn": {None}}
