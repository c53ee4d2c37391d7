"""The work counts of ``bench/work`` against a count by hand."""
import importlib.util
from pathlib import Path

import pytest

WORK = Path(__file__).resolve().parents[1] / "work"
# 10 inner vertices, 20 edges, 15 rows read (inner + halo), widths 4-3-2
SHAPE = {"nodes": 10, "edges": 20, "rows_in": 15, "dims": [4, 3, 2]}


def load(model):
    spec = importlib.util.spec_from_file_location(f"w_{model}",
                                                  WORK / f"{model}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gcn_by_hand():
    w = load("gcn").work(SHAPE)
    # forward aggregation at widths 4 and 3: 2*20*4 + 2*20*3 = 280;
    # backward only at layer 1 (width 3): 2*20*3 = 120
    assert w["spmm_flops"] == 280 + 120
    # forward layer 0: (15 + 10) rows * 4 * 4 B + 20 * 12 B = 640;
    # forward layer 1: 25 * 3 * 4 + 240 = 540; backward layer 1: 540
    assert w["spmm_bytes"] == 640 + 540 + 540
    # matmuls 2*10*4*3 = 240 and 2*10*3*2 = 120: each forward + weight
    # gradient, the second also an input gradient
    assert w["dense_flops"] == 2 * 240 + 3 * 120
    assert w["flops"] == 400 + 840


def test_sage_by_hand():
    w = load("sage").work(SHAPE)
    # GCN's aggregation plus one add per edge per layer for the degree
    assert w["spmm_flops"] == 400 + 2 * 20
    # plus 8 bytes per edge and 4 per output row per layer
    assert w["spmm_bytes"] == 1720 + 2 * (160 + 40)
    # two matmuls wherever GCN has one
    assert w["dense_flops"] == 2 * 840
    assert w["flops"] == 440 + 1680


@pytest.mark.parametrize("model", ["gcn", "sage"])
def test_flickr_epoch_matches_a_count_by_hand(model):
    """At Flickr's size GCN's epoch is ~87 GFLOP (forward 35, weight
    gradients 35, input gradients 12, aggregation 6)."""
    shape = {"nodes": 89250, "edges": 1_850_000, "rows_in": 190_000,
             "dims": [500, 256, 256, 7]}
    w = load(model).work(shape)
    lo, hi = (80e9, 95e9) if model == "gcn" else (160e9, 180e9)
    assert lo < w["flops"] < hi
