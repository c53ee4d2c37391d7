"""Interval arithmetic and op tagging of the trace reduction."""
import pytest

from harness.trace import (Op, Reduced, busy_s, gaps, tagged_s, tags_of,
                           union_s)


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert union_s(iv) == pytest.approx(30e-9)
    assert gaps(iv, 0, 50) == [(20, 30), (40, 50)]


def test_tags():
    assert tags_of("jit(step)/transpose(jvp(layer1))/spmm_layer/scatter-add",
                   "fusion", "custom fusion") == {"aggregation"}
    assert tags_of("jit(step)/layer0/spmm_layer/dot_general", "fusion",
                   "convolution fusion") == {"dense"}
    assert tags_of("jit(step)/tier_pull_refresh/gather", "fusion",
                   "loop fusion") == {"exchange"}
    assert tags_of("jit(step)/jit(shmap_body)/refresh_ring_issue/ppermute",
                   "collective-permute-start", "") == {"exchange"}
    assert tags_of("jit(step)/psum", "all-reduce", "") == frozenset()
    assert tags_of("jit(step)/layer10x/sub", "fusion", "") == frozenset()


def test_device_sums():
    def op(s, e, tags, opcode="fusion"):
        return Op(s, e, "n", opcode, "", frozenset(tags))
    dev0 = [op(0, 10, ["aggregation"]), op(10, 15, ["dense"]),
            op(20, 30, [], "all-reduce")]
    dev1 = [op(0, 20, ["aggregation"]), op(25, 30, ["dense"])]
    red = Reduced(window=(0, 40), devices=[dev0, dev1], host=[])
    assert tagged_s(red, "aggregation") == pytest.approx(15e-9)
    assert busy_s(red) == pytest.approx(25e-9)


def test_collective_exposed_ms():
    from types import SimpleNamespace

    from harness import trace
    from harness.cell import BENCH, load_module
    reader = load_module(BENCH / "metrics" / "collective_exposed_ms.py",
                         "bench_metric_collective_exposed_ms")

    def op(s, e, opcode, hlo="x"):
        return Op(s, e, "n", opcode, "", frozenset(), hlo)
    # device 0: a ring step hidden under compute, an all-reduce half
    # hidden, a wrapped all-gather in the open: 0 + 5 + 10 ns exposed
    dev0 = [op(0, 30, "fusion"), op(5, 25, "collective-permute-start"),
            op(25, 28, "collective-permute-done"),
            op(30, 40, "fusion"), op(35, 45, "all-reduce"),
            op(50, 55, "async-start", "all-gather-start.3"),
            op(55, 60, "async-done", "all-gather-done.3"),
            op(60, 70, "async-start", "slice-start.1")]
    # device 1: a collective in the open, 20 ns
    dev1 = [op(0, 10, "fusion"), op(10, 30, "all-reduce-done")]
    ctx = SimpleNamespace(trace=trace, epochs=2e-6, red=Reduced(
        window=(0, 100), devices=[dev0, dev1], host=[]))
    # mean of 15 and 20 ns over the chips, over 2e-6 epochs, in ms
    assert reader.read(ctx) == pytest.approx(1e3 * 17.5e-9 / 2e-6)
    assert [reader.is_collective(o) for o in dev0] == [
        False, True, True, False, True, True, True, False]
    ctx.red = Reduced(window=(0, 100), devices=[dev0[:1], dev1[:1]],
                      host=[])
    assert reader.read(ctx) is None
