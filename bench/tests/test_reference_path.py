"""The plain reference's second path: model modules that aggregate
themselves (``OWN_AGGREGATION``), loaded as the harness loads a cell's
module, on the ``cpu-tiny`` rehearsal graph.  The test-local modules are
in ``bench/tests/reference/``."""
import dataclasses
from pathlib import Path

import jax
import numpy as np
import pytest

from harness import reference
from harness.cell import load_cell, load_partition, model_config
from harness.main import Reference, init_params, reference_graph

HERE = Path(__file__).resolve().parent
SEED = 2**31 + 4099
# refresh, then cached steps reading the stale tiers, a pipelined refresh
# and a cached step reading what it cached
KINDS = ["refresh", "cached", "cached", "cached", "pipelined", "cached"]


@pytest.fixture(scope="module")
def tiny():
    cell = load_cell("cpu-tiny.capgnn")
    art, _, _ = load_partition(cell)
    graph, _ = reference_graph(cell, art)
    assert graph.stale.any() and not graph.stale.all()
    return cell, graph


def with_model(cell, model: str, **config):
    return dataclasses.replace(cell, reference_dir=HERE / "reference",
                               config={**cell.config, "model": model,
                                       **config})


def follow(cell, graph, kinds):
    ref = Reference(cell, graph)
    return reference.run(ref.step, ref.data, init_params(cell, SEED), kinds)


def test_own_aggregation_gcn_matches_gcn_bit_for_bit(tiny):
    cell, graph = tiny
    assert cell.config["model"] == "gcn"
    own = with_model(cell, "gcn_own")
    assert own.model.OWN_AGGREGATION
    for n in (1, 2, len(KINDS)):
        assert follow(own, graph, KINDS[:n]) == follow(cell, graph,
                                                       KINDS[:n])


def _attention_np(params, x, g, stale_acts):
    """Forward pass of ``attn1`` in float64 numpy: on layers 1 and up an
    edge whose source sits in a cached tier reads that source's stale
    row, for its logit and its message alike.  Returns the logits and the
    inputs of layers 1 and up."""
    n = x.shape[0]
    h, acts = x.astype(np.float64), []
    for li, p in enumerate(params):
        p = {k: np.asarray(v, np.float64) for k, v in p.items()}
        if li > 0:
            acts.append(h)
        z = h @ p["w"]
        z_src = z[g.src]
        if li > 0 and stale_acts is not None:
            zs = np.asarray(stale_acts[li - 1], np.float64) @ p["w"]
            z_src = np.where(g.stale[:, None], zs[g.src], z_src)
        logit = z_src @ p["a_src"] + (z @ p["a_dst"])[g.dst]
        logit = np.where(logit > 0, logit, 0.2 * logit)
        top = np.full(n, -np.inf)
        np.maximum.at(top, g.dst, logit)
        e = np.exp(logit - top[g.dst])
        den = np.zeros(n)
        np.add.at(den, g.dst, e)
        out = np.zeros((n, z.shape[1]))
        np.add.at(out, g.dst, (e / den[g.dst])[:, None] * z_src)
        h = out if li == len(params) - 1 else np.where(
            out > 0, out, np.expm1(np.minimum(out, 0)))
    return h, acts


def _loss_np(logits, g):
    logp = logits - logits.max(-1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(-1, keepdims=True))
    nll = -logp[np.arange(len(g.labels)), g.labels]
    return float((nll * g.train_mask).sum() / g.train_mask.sum())


def test_own_aggregation_attention_reads_stale_rows(tiny):
    cell, graph = tiny
    att = with_model(cell, "attn1")
    ref = Reference(att, graph)
    params0 = init_params(att, SEED)
    zeros = [np.zeros((graph.num_nodes, 1), np.float32)]
    state = (jax.tree.map(np.zeros_like, params0),) * 2 + (np.float32(0),)
    p1, state, loss0, _, acts0 = ref.step(params0, state, zeros, ref.data,
                                          False)
    _, _, loss1, _, acts1 = ref.step(p1, state, acts0, ref.data, True)

    logits, want0 = _attention_np(params0, graph.features, graph, None)
    assert float(loss0) == pytest.approx(_loss_np(logits, graph), rel=1e-5)
    logits, want1 = _attention_np(p1, graph.features, graph, acts0)
    assert float(loss1) == pytest.approx(_loss_np(logits, graph), rel=1e-5)
    for got, want in zip(acts0 + acts1, want0 + want1):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-5)
    # the stale rows matter: read fresh, the cached step's loss is another
    fresh, _ = _attention_np(p1, graph.features, graph, None)
    assert abs(_loss_np(fresh, graph) - float(loss1)) > 1e-3 * float(loss1)


def test_model_config_passes_every_gnn_field(tiny):
    cell, _ = tiny
    cfg = model_config(with_model(cell, "gat", num_heads=6, residual=True))
    assert (cfg.model, cfg.num_heads, cfg.residual) == ("gat", 6, True)
    assert (cfg.in_dim, cfg.out_dim, cfg.hidden_dim) == (
        cell.config["feat_dim"], cell.config["num_classes"],
        cell.config["hidden_dim"])
