"""The trace reduction on a capture recorded on a TPU v5e: one refresh
period (4 epochs) of ``gcn-flickr.capgnn``'s window."""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import trace

BENCH = Path(__file__).resolve().parents[1]
CAPTURE = Path(__file__).with_name("data") / "gcn-flickr.capgnn.xplane.pb.gz"
EPOCHS = 4


@pytest.fixture(scope="module")
def red():
    return trace.reduce_capture(CAPTURE)


def test_window_and_device(red):
    assert len(red.devices) == 1
    assert 0 < trace.busy_s(red) <= red.window_s
    # one compute stream: its ops never overlap, so the union of each
    # layer's ops equals the plain sum of their durations
    for tag in ("aggregation", "dense", "exchange"):
        plain = sum(o.end - o.start for o in red.devices[0]
                    if tag in o.tags) / 1e9
        assert trace.tagged_s(red, tag) == pytest.approx(plain, rel=1e-9)
        assert plain > 0


def test_scopes_are_found(red):
    names = {o.name for o in red.devices[0] if o.tags}
    assert any("spmm_layer" in n for n in names)
    assert any("tier_pull" in n for n in names)


def test_readers_stay_within_their_bounds(red):
    work = load(BENCH / "work" / "gcn.py").work(
        {"nodes": 89250, "edges": 1_850_000, "rows_in": 190_000,
         "dims": [500, 256, 256, 7]})
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    ctx = SimpleNamespace(red=red, trace=trace, epochs=EPOCHS, chips=1,
                          work=work, peaks=peaks)
    for name in ("spmm_roofline", "step_mfu", "device_idle_share"):
        value = load(BENCH / "metrics" / f"{name}.py").read(ctx)
        assert 0 < value < 100, name
    for name in ("spmm_ms", "dense_ms", "exchange_ms"):
        assert load(BENCH / "metrics" / f"{name}.py").read(ctx) > 0
    bd = trace.breakdown(red)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(
        red.window_s - trace.busy_s(red), rel=1e-6)


def load(path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
