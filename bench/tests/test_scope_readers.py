"""The readers of the training step's phase scopes (``aggregate_ms``,
``cache_ms``, ``unscoped_ms``): on a CPU capture of the
``cpu-tiny.capgnn`` rehearsal's window, on a built ``Reduced``, and on the
TPU capture recorded before the program had these scopes."""
import importlib.util
import re
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import trace
from harness.trace import Op, Reduced

BENCH = Path(__file__).resolve().parents[1]
CAPTURE = Path(__file__).with_name("data") / "gcn-flickr.capgnn.xplane.pb.gz"
READERS = ("aggregate_ms", "cache_ms", "unscoped_ms")
# the phases a training step opens; each operation sits under one
PHASE = re.compile(r"(?:^|[/(])(?:layer\d+|tier_pull_[^/()]*|"
                   r"refresh_ring_[^/()]*|cache_read|cache_update|loss|"
                   r"grad_sync|optimizer)(?:[/)]|$)")


def load(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(name, ctx):
    return load(name).read(ctx)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The harness's own set-up and traced window of the CPU rehearsal."""
    from harness import main as hm
    from harness.cell import load_cell
    cell = load_cell("cpu-tiny.capgnn")
    hm.prepare_env(cell)
    hm.TRACE_DIR = tmp_path_factory.mktemp("trace")
    trainer, *_ = hm.setup_run(cell, 2**31 + 11, time.perf_counter())
    win = hm.window(trainer, 0.3, cell.traffic["refresh_every"], True,
                    hm.CompileLog())
    red = trace.reduce_capture(hm.capture_path())
    return SimpleNamespace(red=red, trace=trace, epochs=win["epochs"],
                           chips=1)


def test_readers_return_numbers(rehearsal):
    for name in READERS:
        value = read(name, rehearsal)
        assert value is not None and value >= 0, name
    assert read("aggregate_ms", rehearsal) > 0
    assert read("cache_ms", rehearsal) > 0


def test_aggregate_is_inside_the_layer_tags(rehearsal):
    """Every ``aggregate`` operation is one that ``spmm_ms`` or
    ``dense_ms`` reads (a ``layer<i>`` op), so ``aggregate_ms`` can read
    no more than their sum."""
    agg = [o for ops in rehearsal.red.devices for o in ops
           if re.search(r"(?:^|[/(])aggregate(?:[/)]|$)", o.name)]
    assert agg
    assert all(o.tags and o.tags <= {"aggregation", "dense"} for o in agg)


def test_program_spans_on_the_capture(rehearsal):
    """The window's epochs are the tracer's step spans on the capture's
    host plane, so an idle stretch can be given to one."""
    steps = [n for _, _, n in rehearsal.red.host
             if n in ("repro/refresh", "repro/cached", "repro/pipelined")]
    assert len(steps) == rehearsal.epochs


def _op(start, end, name):
    return Op(start, end, name, "fusion", "", trace.tags_of(name, "fusion",
                                                             ""))


def test_phases_and_unscoped_add_up_to_busy():
    """Phase-scoped time plus ``unscoped_ms`` is the busy time, on each of
    two chips; the readers take the mean over chips."""
    dev0 = [_op(0, 10, "jit(step)/jvp(layer0)/aggregate/scatter-add"),
            _op(10, 12, "jit(step)/transpose(jvp(layer0))/transform/dot"),
            _op(12, 15, "jit(step)/cache_read/scatter"),
            _op(15, 16, "jit(step)/jvp(tier_pull_uncached)/gather"),
            _op(20, 21, "jit(step)/cache_update/reduce_max"),
            _op(21, 24, "copy.3"),
            _op(24, 25, "jit(step)/optimizer/mul;jit(step)/loss/exp")]
    dev1 = [_op(0, 20, "jit(step)/transpose(jvp(layer2))/aggregate/mul"),
            _op(25, 27, "jit(step)/shmap_body/grad_sync/all-reduce"),
            _op(27, 33, "fusion.12")]
    red = Reduced(window=(0, 40), devices=[dev0, dev1], host=[])
    ctx = SimpleNamespace(red=red, trace=trace, epochs=1, chips=2)
    ms = {name: read(name, ctx) for name in READERS}
    assert ms["aggregate_ms"] == pytest.approx((10 + 20) / 2 * 1e-6)
    assert ms["cache_ms"] == pytest.approx((3 + 1) / 2 * 1e-6)
    assert ms["unscoped_ms"] == pytest.approx((3 + 6) / 2 * 1e-6)
    phased = sum(trace.union_s([(o.start, o.end) for o in ops
                                if PHASE.search(o.name)])
                 for ops in red.devices) / 2
    assert 1e3 * phased + ms["unscoped_ms"] == pytest.approx(
        1e3 * trace.busy_s(red))


def test_readers_on_a_capture_without_the_scopes():
    """The recorded capture predates ``aggregate`` and the cache scopes:
    those readers return nothing, and ``unscoped_ms`` reads what the
    layer and exchange tags leave of the busy time (one stream, so the
    sums are exact)."""
    red = trace.reduce_capture(CAPTURE)
    ctx = SimpleNamespace(red=red, trace=trace, epochs=4, chips=1)
    assert read("aggregate_ms", ctx) is None
    assert read("cache_ms", ctx) is None
    tagged = sum(trace.tagged_s(red, t)
                 for t in ("aggregation", "dense", "exchange"))
    assert read("unscoped_ms", ctx) == pytest.approx(
        1e3 * (trace.busy_s(red) - tagged) / 4, rel=1e-9)
    assert read("unscoped_ms", ctx) > 0
