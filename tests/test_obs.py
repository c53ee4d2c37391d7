"""repro.obs: structured tracing, per-phase timing, Perfetto export.

Coverage layers:

- tracer unit properties: strict LIFO span nesting, step kinds never
  interleave, the disabled tracer is a true no-op (one shared context
  manager, no fences, no records);
- exact accounting: a traced sim train's per-step counter totals equal
  ``TrainReport.comm_bytes`` / ``host_fetch_*`` *exactly* (device and
  host feature modes, static and adaptive/replanning schedules); the
  SPMD runtime over both halo transports is covered by the forced-mesh
  subprocess (``obs_trace_script.py``);
- export: the Chrome trace round-trips through JSON and validates
  against the trace_event schema (spans as "X", counters as "C",
  per-worker counter tracks), the JSONL metrics stream reconstructs the
  counter records;
- zero overhead: a run without a tracer issues no
  ``jax.block_until_ready`` and no TraceMe beyond the untraced baseline
  and the donated steps stay warning-free;
- profiler clock: an enabled tracer's spans appear as ``repro/<name>``
  host events in a ``jax.profiler`` capture, a disabled one's do not.
"""
import dataclasses
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.obs import (NULL_TRACER, STEP_KINDS, StepCounters, Tracer,
                       chrome_trace_events, validate_chrome_trace,
                       write_chrome_trace, write_metrics_jsonl)

_SCRIPT = os.path.join(os.path.dirname(__file__), "obs_trace_script.py")
_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ------------------------------------------------------------- unit: spans

def test_spans_nest_strictly():
    tr = Tracer(fence=False)
    with tr.step_span("refresh", 0):
        with tr.span("l0_stage"):
            with tr.span("h2d_put", nbytes=128):
                pass
        with tr.span("writeback"):
            pass
    with tr.step_span("cached", 1):
        pass
    assert [s.name for s in tr.spans] == \
        ["h2d_put", "l0_stage", "writeback", "refresh", "cached"]
    by = {s.name: s for s in tr.spans}
    assert by["refresh"].depth == 0 and by["cached"].depth == 0
    assert by["l0_stage"].depth == 1 and by["h2d_put"].depth == 2
    assert by["h2d_put"].args == {"nbytes": 128}
    assert by["refresh"].step == 0 and by["cached"].step == 1
    # children lie inside their parent's interval
    for child, parent in (("h2d_put", "l0_stage"), ("l0_stage", "refresh")):
        c, p = by[child], by[parent]
        assert c.t0 >= p.t0 and c.t0 + c.dur <= p.t0 + p.dur + 1e-9


def test_step_kinds_never_interleave():
    tr = Tracer(fence=False)
    span = tr.step_span("refresh", 0)
    with span:
        with pytest.raises(RuntimeError, match="interleave"):
            with tr.step_span("cached", 1):
                pass
    # sub-spans must close LIFO
    a, b = tr.span("l0_stage"), tr.span("writeback")
    a.__enter__()
    b.__enter__()
    with pytest.raises(RuntimeError, match="nest strictly"):
        a.__exit__(None, None, None)


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    s1 = tr.span("anything", rows=3)
    s2 = tr.step_span("refresh", 0)
    assert s1 is s2 is NULL_TRACER.span("x")   # one shared no-op CM
    with s1:
        pass
    tr.count(StepCounters(step=0, kind="refresh"))
    assert tr.spans == [] and tr.counters == []
    assert tr.phase_stats() == {}
    assert tr.totals()["steps"] == 0


def test_disabled_fence_never_syncs(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or x)
    Tracer(enabled=False).fence(object())
    NULL_TRACER.fence(object())
    assert not calls
    Tracer().fence(object())
    assert len(calls) == 1
    Tracer(fence=False).fence(object())   # timing on, fencing opted out
    assert len(calls) == 1


def test_phase_stats_percentiles():
    tr = Tracer(fence=False)
    for e in range(10):
        with tr.step_span("cached", e):
            pass
    st = tr.phase_stats()
    assert set(st) == {"cached"}
    assert st["cached"]["count"] == 10
    assert 0 <= st["cached"]["p50_ms"] <= st["cached"]["p99_ms"]
    assert st["cached"]["total_s"] >= 0


# ------------------------------------------------------------ unit: export

def _fake_traced():
    tr = Tracer(fence=False)
    for e, kind in enumerate(("refresh", "cached", "pipelined")):
        with tr.step_span(kind, e):
            with tr.span("l0_stage"):
                pass
        tr.count(StepCounters(step=e, kind=kind, wire_rows_uncached=5 + e,
                              wire_bytes=100 * (e + 1),
                              wire_bytes_vanilla=400,
                              cache_hit_rate=None if e == 0 else 0.5,
                              wire_rows_by_worker=[2 + e, 3]))
    return tr


def test_chrome_trace_roundtrip(tmp_path):
    tr = _fake_traced()
    path = write_chrome_trace(tr, str(tmp_path / "trace.json"))
    with open(path) as f:
        payload = json.load(f)
    assert payload["displayTimeUnit"] == "ms"
    stats = validate_chrome_trace(payload)
    assert stats["spans_by_cat"] == {"refresh": 1, "cached": 1,
                                     "pipelined": 1, "l0_stage": 3}
    # per-worker counter tracks: 2 workers x 3 steps on pids 1, 2
    pids = {ev["pid"] for ev in payload["traceEvents"] if ev["ph"] == "C"}
    assert pids == {0, 1, 2}
    names = {ev["args"]["name"] for ev in payload["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "process_name"}
    assert {"train host", "worker0", "worker1"} <= names
    # ts are non-negative relative microsecond ints, spans ordered
    xs = [ev for ev in payload["traceEvents"] if ev["ph"] == "X"]
    assert all(ev["ts"] >= 0 and ev["dur"] >= 1 for ev in xs)
    steps = [ev["args"]["step"] for ev in xs if ev["cat"] in STEP_KINDS]
    assert steps == sorted(steps)


def test_counter_events_skip_none_fields():
    tr = _fake_traced()
    evs = chrome_trace_events(tr)
    hits = [ev for ev in evs if ev["ph"] == "C"
            and ev["name"] == "cache_hit_rate"]
    assert len(hits) == 2          # None on the refresh record -> skipped
    assert not any(ev["name"] in ("queries", "hot_hits") for ev in evs
                   if ev["ph"] == "C")   # serve fields absent on train recs


def test_metrics_jsonl_roundtrip(tmp_path):
    tr = _fake_traced()
    path = write_metrics_jsonl(tr, str(tmp_path / "metrics.jsonl"))
    rows = [json.loads(line) for line in open(path)]
    assert len(rows) == 3
    want = [dataclasses.asdict(c) for c in tr.counters]
    assert rows == want


def test_validate_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"foo": []})
    bad = {"traceEvents": [{"ph": "X", "name": "a", "ts": 0, "pid": 0}]}
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(bad)
    bad = {"traceEvents": [{"ph": "C", "name": "c", "ts": 0, "pid": 0,
                            "args": {"v": "nan-string"}}]}
    with pytest.raises(ValueError, match="numeric"):
        validate_chrome_trace(bad)
    with pytest.raises(ValueError, match="phase"):
        validate_chrome_trace({"traceEvents": [{"ph": "Z"}]})


# ----------------------------------------------- traced training (sim)

_CACHE: dict = {}


def _tiny(features="device", adaptive=False):
    import jax
    from repro.core import (AdaptivePlanner, CacheCapacity,
                            StalenessController, build_cache_plan)
    from repro.data.gnn_data import FullBatchTask, split_masks
    from repro.dist import (build_exchange_plan, make_sim_runtime,
                            stack_partitions)
    from repro.graph import (build_partition, metis_partition, rmat,
                             symmetric_normalize, synth_features)
    from repro.models.gnn import GNNConfig
    from repro.optim import adam

    g = rmat(200, 1100, seed=9)
    feats, labels = synth_features(g, 8, 4, seed=9)
    gn = symmetric_normalize(g)
    trm, va, te = split_masks(g.num_nodes, seed=9)
    task = FullBatchTask(graph=gn, features=feats, labels=labels,
                         train_mask=trm, val_mask=va, test_mask=te,
                         num_classes=4)
    parts = 2
    ps = build_partition(gn, metis_partition(gn, parts, seed=9), hops=1)
    cfg = GNNConfig(model="gcn", in_dim=8, hidden_dim=8, out_dim=4,
                    num_layers=2)
    max_halo = max(pt.n_halo for pt in ps.parts)
    cap = CacheCapacity(c_gpu=[max(1, max_halo // 3)] * parts,
                        c_cpu=max(1, max_halo))
    planner = None
    if adaptive:
        planner = AdaptivePlanner(ps, cap, refresh_every=2, policy="lru",
                                  seed=0)
        xplan = planner.exchange_plan()
    else:
        xplan = build_exchange_plan(
            ps, build_cache_plan(ps, cap, refresh_every=2))
    sp = stack_partitions(ps, task)
    opt = adam(1e-2)
    rt = make_sim_runtime(cfg, sp, xplan, opt, features=features)
    ctl = StalenessController(refresh_every=2)
    return cfg, rt, xplan, parts, opt, ctl, planner


def _traced_run(features="device", adaptive=False, epochs=6, tracer=...,
                eval_every=0):
    from repro.dist import train_capgnn
    cfg, rt, xplan, parts, opt, ctl, planner = _tiny(features, adaptive)
    tr = Tracer() if tracer is ... else tracer
    _, rep = train_capgnn(cfg, rt, xplan, parts, opt, epochs=epochs,
                          controller=ctl, pipeline=True,
                          eval_every=eval_every, planner=planner, tracer=tr)
    return tr, rep, rt


@pytest.mark.parametrize("features", ["device", "host"])
def test_traced_totals_match_report_sim(features):
    """The per-step counter stream is the report's accounting, pre-sum:
    totals equal comm_bytes / host_fetch_* exactly."""
    tr, rep, rt = _traced_run(features=features)
    tot = tr.totals()
    assert tot["wire_bytes"] == rep.comm_bytes
    assert tot["wire_bytes_vanilla"] == rep.comm_bytes_vanilla
    assert tot["host_fetch_rows"] == rep.host_fetch_rows
    assert tot["host_fetch_bytes"] == rep.host_fetch_bytes
    assert tot["host_writeback_bytes"] == rep.host_writeback_bytes
    if features == "host":
        assert rep.host_fetch_rows > 0
        # host mode stages h2d inside the staging/prefetch sub-spans
        kinds = {s.kind for s in tr.spans}
        assert {"l0_stage", "h2d_prefetch", "h2d_put"} <= kinds
    # wire rows on the counters re-derive wire_bytes per step
    dimb = sum(d * rt.halo_dtype_bytes for d in rt.comm_dims)
    for c in tr.counters:
        rows = (c.wire_rows_uncached + c.wire_rows_local
                + c.wire_rows_global)
        assert c.wire_bytes == rows * dimb


def test_traced_step_kind_schedule():
    """refresh_every=2, pipeline: refresh @0, pipelined @2,4, cached else;
    exactly one depth-0 span per step, in step order."""
    tr, rep, _ = _traced_run(epochs=6)
    kinds = [c.kind for c in tr.counters]
    assert kinds == ["refresh", "cached", "pipelined", "cached",
                     "pipelined", "cached"]
    depth0 = [s for s in tr.spans if s.depth == 0]
    assert [s.kind for s in depth0] == kinds
    assert [s.step for s in depth0] == list(range(6))
    # counters are monotone in step and stamp time
    assert [c.step for c in tr.counters] == list(range(6))
    ts = [c.t for c in tr.counters]
    assert ts == sorted(ts)
    assert all(c.wire_bytes >= 0 and c.wire_bytes <= c.wire_bytes_vanilla
               for c in tr.counters)
    # steady-state/compile split: both positive, wall excludes step 0
    assert rep.compile_s > 0 and rep.wall_time_s > 0
    assert set(rep.phase_stats) == {"refresh", "cached", "pipelined"}
    assert sum(p["count"] for p in rep.phase_stats.values()) == 6


def test_traced_adaptive_replan_exact():
    """Replanning schedules: transition steps traced with a nested replan
    span, and the totals stay exact across plan swaps."""
    tr, rep, _ = _traced_run(adaptive=True, epochs=7)
    assert rep.replan_events > 0
    kinds = [c.kind for c in tr.counters]
    assert "transition" in kinds
    assert tr.totals()["wire_bytes"] == rep.comm_bytes
    replans = [s for s in tr.spans if s.kind == "replan"]
    assert len(replans) == rep.replan_events
    assert all(s.depth == 1 for s in replans)
    trans = {s.step for s in tr.spans
             if s.depth == 0 and s.kind == "transition"}
    assert {s.step for s in replans} <= trans | {0}


def test_eval_spans_depth0():
    tr, rep, _ = _traced_run(epochs=4, eval_every=2)
    evals = [s for s in tr.spans if s.kind == "eval"]
    assert len(evals) == 2 and all(s.depth == 0 for s in evals)
    assert "eval" in rep.phase_stats


def test_traced_export_validates(tmp_path):
    tr, _, _ = _traced_run(features="host", epochs=4)
    paths = tr.export(str(tmp_path), prefix="t")
    with open(paths["trace"]) as f:
        stats = validate_chrome_trace(json.load(f))
    assert stats["n_spans"] == len(tr.spans)
    assert stats["spans_by_cat"].get("refresh", 0) > 0
    rows = [json.loads(line) for line in open(paths["metrics"])]
    assert len(rows) == len(tr.counters)


def test_untraced_run_adds_no_sync(monkeypatch):
    """tracer=None and a disabled tracer issue zero block_until_ready
    calls and no TraceMe from the training loop (the per-step float() is
    the only sync), and donation stays clean."""
    import jax
    import repro.obs.tracer as tracer_mod
    real = jax.block_until_ready
    calls, traceme = [], []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    real_ann = jax.profiler.TraceAnnotation

    def counted(name, **kw):
        traceme.append(name)
        return real_ann(name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counted)
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", counted)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _, rep_none, _ = _traced_run(tracer=None, epochs=4)
        n_none = len(calls)
        _, rep_off, _ = _traced_run(tracer=Tracer(enabled=False),
                                    epochs=4)
        n_off = len(calls) - n_none
    assert n_none == 0 and n_off == 0
    assert traceme == []
    bad = [str(x.message) for x in w if "donat" in str(x.message).lower()]
    assert not bad, bad
    assert rep_none.phase_stats is None and rep_off.phase_stats is None
    np.testing.assert_allclose(rep_none.losses, rep_off.losses)
    # ... and an enabled tracer fences once per step, and marks each span
    calls.clear()
    tr, _, _ = _traced_run(epochs=4)
    assert len(calls) == 4
    assert traceme == [f"repro/{s.name}" for s in
                       sorted(tr.spans, key=lambda s: s.t0)]


def _capture_names(trace_dir) -> set:
    """Names of the host events of the one capture under ``trace_dir``."""
    import jax
    path, = trace_dir.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    return {ev.name for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("enabled", [True, False])
def test_spans_on_the_profiler_clock(tmp_path, enabled):
    """An enabled tracer's spans are host events ``repro/<name>`` of a
    profiler capture, nested as the spans are; a disabled tracer's are
    not there."""
    import jax
    import jax.numpy as jnp
    tr = Tracer(enabled=enabled, fence=False)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with tr.step_span("refresh", 0):
            with tr.span("l0_stage"):
                f(x).block_until_ready()
        with tr.step_span("cached", 1):
            f(x).block_until_ready()
    names = {n for n in _capture_names(tmp_path) if n.startswith("repro/")}
    want = {"repro/refresh", "repro/l0_stage", "repro/cached"}
    assert names == (want if enabled else set())


def test_launcher_device_trace_carries_spans(tmp_path):
    """``launch.train gnn --device-trace-dir`` turns the tracer on, so its
    capture holds the step spans (without ``--trace`` nothing is
    exported)."""
    import argparse
    from repro.launch.train import run_gnn
    out = run_gnn(argparse.Namespace(
        dataset="flickr", scale=0.008, feat_dim=16, model="gcn",
        backend="edges", hidden=16, layers=2, parts=2, partitioner="metis",
        epochs=3, lr=0.01, jaca=True, rapa=False, pipeline=False,
        refresh_every=2, adaptive_staleness=False, cpu_cache_gib=1.0,
        seed=0, ckpt_dir="", resume=False,
        device_trace_dir=str(tmp_path / "capture")))
    assert "trace_file" not in out
    names = _capture_names(tmp_path / "capture")
    assert {"repro/refresh", "repro/cached"} <= names


# ------------------------------------------- SPMD runtimes (forced mesh)

def _run_script(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, _SCRIPT, *args],
                          capture_output=True, text=True, timeout=900,
                          env=env)


@pytest.mark.parametrize("transport,features",
                         [("allgather", "device"), ("allgather", "host"),
                          ("p2p", "device"), ("p2p", "host")])
def test_spmd_traced_totals_match_report(transport, features):
    """Plan rows == traced rows == report totals on the real SPMD runtime,
    both transports, device- and host-resident features."""
    res = _run_script("--transport", transport, "--features", features)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "OK" in res.stdout
    assert "donated buffers were not usable" not in res.stderr


# --------------------------------------------------------------- serving

def test_serve_stream_traced_counters():
    import jax
    from repro.core import build_cache_plan, cal_capacity, PROFILES
    from repro.data.gnn_data import FullBatchTask, split_masks
    from repro.dist import build_exchange_plan, stack_partitions
    from repro.graph import (build_partition, metis_partition, rmat,
                             symmetric_normalize, synth_features)
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.serve import (BatchConfig, GNNServeEngine, make_stream,
                             precompute_embeddings, rank_hot_nodes,
                             serve_stream)

    g = rmat(160, 800, seed=4)
    feats, labels = synth_features(g, 8, 4, seed=4)
    gn = symmetric_normalize(g)
    trm, va, te = split_masks(g.num_nodes, seed=4)
    task = FullBatchTask(graph=gn, features=feats, labels=labels,
                         train_mask=trm, val_mask=va, test_mask=te,
                         num_classes=4)
    ps = build_partition(gn, metis_partition(gn, 2, seed=4), hops=1)
    cfg = GNNConfig(model="gcn", in_dim=8, hidden_dim=8, out_dim=4,
                    num_layers=2)
    cap = cal_capacity(ps, cfg.feat_dims, [PROFILES["rtx3090"]] * 2)
    xplan = build_exchange_plan(ps, build_cache_plan(ps, cap,
                                                     refresh_every=2))
    sp = stack_partitions(ps, task)
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    store = precompute_embeddings(cfg, ps, sp, xplan, params)
    hot = rank_hot_nodes(gn, 40, ps=ps)
    engine = GNNServeEngine(store, params, gn, hot, features=feats)
    stream = make_stream("zipf", gn.num_nodes, 96, qps=1e9, seed=4)
    tr = Tracer(fence=False)
    report = serve_stream(engine, stream, BatchConfig(max_batch=32),
                          tracer=tr)
    assert tr.counters and all(c.kind == "serve" for c in tr.counters)
    tot_q = sum(c.queries for c in tr.counters)
    assert tot_q == engine.stats["queries"] == 96
    assert sum(c.hot_hits for c in tr.counters) == engine.stats["hot_hits"]
    assert sum(c.host_hits for c in tr.counters) == \
        engine.stats["host_hits"]
    batch_spans = [s for s in tr.spans if s.kind == "serve_batch"]
    assert len(batch_spans) == len(tr.counters) == engine.stats["batches"]
    # sub-phase spans nest inside batch spans
    subs = [s for s in tr.spans if s.kind in ("hot_gather", "host_fetch",
                                              "fresh_recompute")]
    assert subs and all(s.depth >= 1 for s in subs)
    # wire counters absent on serve records -> no zero-valued train tracks
    evs = chrome_trace_events(tr)
    cnames = {ev["name"] for ev in evs if ev["ph"] == "C"}
    assert "queries" in cnames and "wire_bytes" not in cnames
