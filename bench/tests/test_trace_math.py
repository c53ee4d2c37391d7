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
