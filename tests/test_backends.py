"""Pluggable aggregation backends: edge-list vs Pallas blocked-ELL vs
hybrid ELL+COO through the Adjacency protocol, the stacked layout, and the
sim runtime.  (SPMD-side backend parity lives in test_spmd_runtime.py.)"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import PROFILES, build_cache_plan, cal_capacity
from repro.data.gnn_data import FullBatchTask, split_masks
from repro.dist import (TrainSpec, build_exchange_plan, init_caches,
                        make_sim_runtime, stack_partitions, train_capgnn)
from repro.dist.capgnn_sim import make_adj_builder
from repro.graph import (build_partition, metis_partition, rmat,
                        symmetric_normalize, synth_features)
from repro.models.gnn import (DenseAdj, EdgeListAdj, EllAdj, GNNConfig,
                              HybridAdj, gnn_forward, init_gnn,
                              make_local_adj)
from repro.optim import adam, sgd


def _task_and_parts(n=320, m=2000, parts=4, seed=2, feat=12, classes=5):
    g = rmat(n, m, seed=seed)
    feats, labels = synth_features(g, feat, classes, seed=seed)
    gn = symmetric_normalize(g)
    tr, va, te = split_masks(g.num_nodes, seed=seed)
    task = FullBatchTask(graph=gn, features=feats, labels=labels,
                         train_mask=tr, val_mask=va, test_mask=te,
                         num_classes=classes)
    ps = build_partition(gn, metis_partition(gn, parts, seed=seed), hops=1)
    return task, ps


# ---------------------------------------------------------------- protocol

def test_local_adj_backends_agree():
    """spmm and degree() agree across all four make_local_adj backends."""
    task, ps = _task_and_parts()
    part = ps.parts[0]
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(part.n_local, 16)).astype(np.float32))
    adjs = {b: make_local_adj(part.local_graph, part.n_inner, backend=b)
            for b in ("edges", "dense", "ell", "hybrid")}
    ref = np.asarray(adjs["edges"].spmm(h))
    deg_ref = np.asarray(adjs["edges"].degree())
    for name, adj in adjs.items():
        np.testing.assert_allclose(np.asarray(adj.spmm(h)), ref,
                                   rtol=1e-5, atol=1e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(adj.degree()), deg_ref,
                                   rtol=1e-5, atol=1e-5, err_msg=name)


def test_make_local_adj_types_and_unknown_backend():
    task, ps = _task_and_parts()
    part = ps.parts[0]
    assert isinstance(make_local_adj(part.local_graph, part.n_inner,
                                     backend="ell"), EllAdj)
    assert isinstance(make_local_adj(part.local_graph, part.n_inner,
                                     backend="hybrid"), HybridAdj)
    with pytest.raises(ValueError, match="nope"):
        make_local_adj(part.local_graph, part.n_inner, backend="nope")


def test_spmm_at_capabilities():
    """EdgeListAdj/EllAdj support spmm_at; DenseAdj/HybridAdj raise a
    precise capability error naming the backend and the edges fallback."""
    task, ps = _task_and_parts()
    part = ps.parts[0]
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(part.n_local, 8)).astype(np.float32))

    edges = make_local_adj(part.local_graph, part.n_inner, backend="edges")
    ell = make_local_adj(part.local_graph, part.n_inner, backend="ell")
    # scaled per-edge values: spmm_at(2w) == 2 * spmm on both backends
    np.testing.assert_allclose(
        np.asarray(edges.spmm_at(2.0 * edges.weight, h)),
        2.0 * np.asarray(edges.spmm(h)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ell.spmm_at(2.0 * ell.vals, h)),
        2.0 * np.asarray(ell.spmm(h)), rtol=1e-5, atol=1e-5)

    for backend, cls in (("dense", DenseAdj), ("hybrid", HybridAdj)):
        adj = make_local_adj(part.local_graph, part.n_inner, backend=backend)
        with pytest.raises(NotImplementedError) as ei:
            adj.spmm_at(jnp.ones(3), h)
        assert cls.__name__ in str(ei.value)
        assert "edges" in str(ei.value)


def test_gat_requires_edge_list_backend():
    task, ps = _task_and_parts()
    cfg = GNNConfig(model="gat", in_dim=task.features.shape[1],
                    hidden_dim=16, out_dim=task.num_classes, num_layers=2)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    adj = make_local_adj(task.graph, task.graph.num_nodes, backend="ell")
    with pytest.raises(NotImplementedError, match="EllAdj"):
        gnn_forward(cfg, params, adj, jnp.asarray(task.features), None)


# ------------------------------------------------------- stacked pack

def test_stacked_ell_pack_layout():
    task, ps = _task_and_parts()
    sp_ell = stack_partitions(ps, task, backend="ell")
    sp_hyb = stack_partitions(ps, task, backend="hybrid")
    p, ni = sp_ell.num_parts, sp_ell.n_inner_max
    assert sp_ell.ell is not None and sp_ell.ell.backend == "ell"
    assert sp_ell.ell.cols.shape[:2] == (p, ni)
    assert sp_ell.ell.tail_width == 0
    # hybrid caps the regular width and spills overflow to the tail
    assert sp_hyb.ell.max_deg <= sp_ell.ell.max_deg
    # nnz conservation: ELL slots + tail entries == stacked edge count
    nnz_edges = int((sp_ell.e_w != 0).sum())
    assert int((sp_ell.ell.vals != 0).sum()) == nnz_edges
    assert (int((sp_hyb.ell.vals != 0).sum())
            + int((sp_hyb.ell.tail_w != 0).sum())) == nnz_edges
    # padded tail rows are routed to the dropped row NI
    pad = sp_hyb.ell.tail_w == 0
    assert np.all(sp_hyb.ell.tail_dst[pad] == ni)
    with pytest.raises(ValueError, match="nope"):
        stack_partitions(ps, task, backend="nope")


def test_runtime_rejects_mismatched_pack():
    task, ps = _task_and_parts()
    cfg = GNNConfig(model="gcn", in_dim=task.features.shape[1],
                    hidden_dim=16, out_dim=task.num_classes, num_layers=2)
    cap = cal_capacity(ps, cfg.feat_dims, [PROFILES["rtx3090"]] * ps.num_parts)
    xplan = build_exchange_plan(ps, build_cache_plan(ps, cap, refresh_every=2))
    sp = stack_partitions(ps, task)                       # no pack
    with pytest.raises(ValueError, match="stack_partitions"):
        make_sim_runtime(cfg, sp, xplan, adam(1e-2), backend="ell")
    sp_ell = stack_partitions(ps, task, backend="ell")    # wrong pack kind
    with pytest.raises(ValueError, match="hybrid"):
        make_sim_runtime(cfg, sp_ell, xplan, adam(1e-2), backend="hybrid")


# ------------------------------------------------------- runtime parity

def _sim_fixture(model="gcn", refresh_every=2):
    task, ps = _task_and_parts()
    cfg = GNNConfig(model=model, in_dim=task.features.shape[1],
                    hidden_dim=16, out_dim=task.num_classes, num_layers=3)
    cap = cal_capacity(ps, cfg.feat_dims, [PROFILES["rtx3090"]] * ps.num_parts)
    plan = build_cache_plan(ps, cap, refresh_every=refresh_every)
    xplan = build_exchange_plan(ps, plan)
    return task, ps, cfg, xplan


@pytest.mark.parametrize("backend", ["ell", "hybrid"])
@pytest.mark.parametrize("model", ["gcn", "sage", "gin"])
def test_sim_runtime_backend_parity(model, backend):
    """Stacked runtime logits match the edges backend to ~1e-5, and a full
    refresh step produces identical loss and near-identical parameters."""
    task, ps, cfg, xplan = _sim_fixture(model=model)
    opt = sgd(1e-2)
    params = init_gnn(jax.random.PRNGKey(3), cfg)

    # donate=False: both runtimes step from the same params pytree
    rt_e = make_sim_runtime(cfg, stack_partitions(ps, task), xplan, opt,
                            donate=False)
    rt_b = make_sim_runtime(cfg, stack_partitions(ps, task, backend=backend),
                            xplan, opt, backend=backend, donate=False)
    le = np.asarray(rt_e.forward_fresh(params))
    lb = np.asarray(rt_b.forward_fresh(params))
    np.testing.assert_allclose(lb, le, rtol=1e-5, atol=1e-5)

    o1, o2 = opt.init(params), opt.init(params)
    c1 = init_caches(cfg, xplan, ps.num_parts)
    c2 = init_caches(cfg, xplan, ps.num_parts)
    p1, _, _, m1 = rt_e.step_refresh(params, o1, c1)
    p2, _, _, m2 = rt_b.step_refresh(params, o2, c2)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), abs=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ["ell", "hybrid"])
def test_train_capgnn_backend_comm_bytes_identical(backend):
    """Swapping the aggregation backend must not change the exchange byte
    accounting — communication is a plan property, not a kernel property."""
    task, ps, cfg, xplan = _sim_fixture()
    opt = adam(1e-2)
    rt_e = make_sim_runtime(cfg, stack_partitions(ps, task), xplan, opt)
    rt_b = make_sim_runtime(cfg, stack_partitions(ps, task, backend=backend),
                            xplan, opt, backend=backend)
    _, rep_e = train_capgnn(cfg, rt_e, xplan, ps.num_parts, opt, epochs=6)
    _, rep_b = train_capgnn(cfg, rt_b, xplan, ps.num_parts, opt, epochs=6)
    assert rep_b.comm_bytes == rep_e.comm_bytes
    assert rep_b.comm_bytes_vanilla == rep_e.comm_bytes_vanilla
    assert rep_b.refresh_steps == rep_e.refresh_steps
    np.testing.assert_allclose(rep_b.losses, rep_e.losses,
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------- stacked COO lists

def _padded_stacked_edges(sp):
    """The ``[P, ME]`` edge rectangle flattened into the stacked row space,
    padding slots routed to the dropped row ``P*NI``."""
    p, ni, nh = sp.num_parts, sp.n_inner_max, sp.n_halo_max
    part = np.arange(p)[:, None]
    src = np.where(sp.e_src < ni, part * ni + sp.e_src,
                   p * ni + part * nh + sp.e_src - ni)
    dst = np.where(sp.e_dst < ni, part * ni + sp.e_dst, p * ni)
    return {"src": jnp.asarray(src.reshape(-1), jnp.int32),
            "dst": jnp.asarray(dst.reshape(-1), jnp.int32),
            "w": jnp.asarray(sp.e_w.reshape(-1))}


def test_stacked_coo_lists_hold_real_edges_only():
    """The stacked builder flattens the real COO entries alone, in
    partition order; the per-partition (mesh) leaves stay ``[P, ME]``."""
    task, ps = _task_and_parts()
    sp = stack_partitions(ps, task, backend="hybrid")
    p, ni = sp.num_parts, sp.n_inner_max
    assert len(set(sp.n_edges.tolist())) > 1       # uneven parts: padding
    assert int(sp.n_edges.sum()) < sp.e_dst.size

    leaves, _ = make_adj_builder(sp, "edges", stacked=True)
    padded = _padded_stacked_edges(sp)
    real = np.asarray(padded["dst"]) < p * ni
    assert leaves["src"].shape == (int(sp.n_edges.sum()),)
    for k in ("src", "dst", "w"):
        np.testing.assert_array_equal(np.asarray(leaves[k]),
                                      np.asarray(padded[k])[real], err_msg=k)
    assert int(np.asarray(leaves["dst"]).max()) < p * ni

    hyb, _ = make_adj_builder(sp, "hybrid", stacked=True)
    n_tail = int((sp.ell.tail_dst < ni).sum())
    assert 0 < n_tail < sp.ell.tail_dst.size
    for k in ("tail_src", "tail_dst", "tail_w"):
        assert hyb[k].shape == (n_tail,), k
    td = np.asarray(hyb["tail_dst"])
    assert td.min() >= 0 and td.max() < p * ni
    assert hyb["cols"].shape == (p * ni, sp.ell.max_deg)

    mesh, _ = make_adj_builder(sp, "edges")
    for k, a in (("src", sp.e_src), ("dst", sp.e_dst), ("w", sp.e_w)):
        np.testing.assert_array_equal(np.asarray(mesh[k]), a, err_msg=k)
    mesh_h, _ = make_adj_builder(sp, "hybrid")
    np.testing.assert_array_equal(np.asarray(mesh_h["tail_dst"]),
                                  sp.ell.tail_dst)


@pytest.mark.parametrize("model", ["gcn", "sage"])
def test_sim_steps_match_padded_edge_list(model):
    """A refresh and a cached step over the real edges give the loss and
    gradients of the same steps fed the padded ``[P*ME]`` list."""
    task, ps, cfg, xplan = _sim_fixture(model=model)
    sp = stack_partitions(ps, task)
    opt = sgd(1.0)            # the parameters' change is the gradient
    params = init_gnn(jax.random.PRNGKey(5), cfg)

    def run(rt):
        caches = init_caches(cfg, xplan, ps.num_parts)
        out = []
        p0, s0 = params, opt.init(params)
        for step in (rt.step_refresh, rt.step_cached):
            p1, s0, caches, m = step(p0, s0, caches)
            out.append((float(m["loss"]),
                        [np.asarray(a) - np.asarray(b) for a, b in
                         zip(jax.tree.leaves(p1), jax.tree.leaves(p0))]))
            p0 = p1
        return out

    rt = make_sim_runtime(cfg, sp, xplan, opt, spec=TrainSpec(donate=False))
    real = run(rt)
    rt.data["adj"] = _padded_stacked_edges(sp)
    padded = run(rt)
    for (lr, gr), (lp, gp) in zip(real, padded):
        assert lr == pytest.approx(lp, rel=1e-6)
        for a, b in zip(gr, gp):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


def test_sim_padding_stats_count_processed_edge_rows():
    """The sim runtime reports the edge rows its aggregation processes:
    no padded edge row for the stacked edge list; the layout's own
    statistics (the SPMD runtime's) keep the ``[P, ME]`` rectangle."""
    task, ps, cfg, xplan = _sim_fixture()
    sp = stack_partitions(ps, task, backend="hybrid")
    rect = sp.padding_stats()
    n_real = int(sp.n_edges.sum())
    assert rect["edges_valid_rows"] == n_real
    assert rect["edges_padded_rows"] == sp.e_dst.size - n_real > 0

    stats = make_sim_runtime(cfg, sp, xplan, adam(1e-2),
                             spec=TrainSpec()).padding_stats()
    assert stats["edges_valid_rows"] == n_real
    assert stats["edges_padded_rows"] == 0
    for k in ("inner_valid_rows", "inner_padded_rows", "halo_valid_rows",
              "halo_padded_rows"):
        assert stats[k] == rect[k], k
    assert stats["waste_frac"] < rect["waste_frac"]

    hyb = make_sim_runtime(cfg, sp, xplan, adam(1e-2),
                           spec=TrainSpec(backend="hybrid")).padding_stats()
    ell_slots = sp.ell.cols.size
    assert hyb["edges_padded_rows"] == ell_slots - int(
        (sp.ell.vals != 0).sum())
