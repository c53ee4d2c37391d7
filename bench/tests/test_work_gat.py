"""The GAT work counts of ``bench/work/gat.py`` against a count by hand."""
import importlib.util
from pathlib import Path

WORK = Path(__file__).resolve().parents[1] / "work"
# 10 inner vertices, 20 edges, 15 rows read (inner + halo); widths 4-8-2:
# a hidden layer of 4 heads of 2, an output layer of 6 heads of 2
SHAPE = {"nodes": 10, "edges": 20, "rows_in": 15, "dims": [4, 8, 2]}


def load():
    spec = importlib.util.spec_from_file_location("w_gat", WORK / "gat.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gat_by_hand():
    w = load().work(SHAPE)
    # layer 0, 4 heads, 8 columns: scores 12*10*8 = 960, edge softmax
    # 14*20*4 = 1120, sums 6*20*8 = 960; layer 1, 6 heads, 12 columns:
    # 12*10*12 = 1440, 14*20*6 = 1680, 6*20*12 = 1440
    assert w["spmm_flops"] == (960 + 1120 + 960) + (1440 + 1680 + 1440)
    # rows: (6*15 + 2*10) rows of 8 and of 12 columns, 4 bytes each;
    # edges: forward 2 * (8 + 4H) bytes, backward 2 * (8 + 8H)
    assert w["spmm_bytes"] == (110 * 8 * 4 + 20 * (2 * 24 + 2 * 40)) + \
        (110 * 12 * 4 + 20 * (2 * 32 + 2 * 56))
    # matmuls 2*10*4*8 = 640 (forward + weight gradient) and 2*10*8*12 =
    # 1920 (forward, weight and input gradient)
    assert w["dense_flops"] == 2 * 640 + 3 * 1920
    assert w["flops"] == w["spmm_flops"] + w["dense_flops"]
    assert (w["attention_flops"], w["attention_bytes"]) == (
        w["spmm_flops"], w["spmm_bytes"])


def test_flickr_epoch_matches_a_count_by_hand():
    """At Flickr's size the published GAT's epoch is ~0.8 TFLOP: the
    transforms 500 -> 1024 and 1024 -> 1024 (forward, weight and input
    gradients) ~0.77, the attention ~0.02."""
    shape = {"nodes": 89250, "edges": 1_369_162, "rows_in": 148_463,
             "dims": [500, 1024, 1024, 7]}
    w = load().work(shape)
    assert 0.74e12 < w["dense_flops"] < 0.78e12
    assert 0.015e12 < w["attention_flops"] < 0.025e12
