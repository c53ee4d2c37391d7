"""The GAT cell's harness path, rehearsed on the CPU (``cpu-tiny-gat``: a
hundredth of Flickr, 4 heads of 8 and 6 output heads): the plain GAT
reference (``bench/reference/gat.py``, ``OWN_AGGREGATION``) finds a sound
run correct, and the control and each fault the cell can have, planted
under the timed path, not correct."""
import pytest

from test_correct import VARIANTS, run

WORKLOAD = "cpu-tiny-gat.capgnn"


@pytest.mark.parametrize("variant,correct", sorted(VARIANTS.items()))
def test_gat_rehearsal(variant, correct):
    res = run(WORKLOAD, variant, 2**31 + 977)
    assert res["correct"] is correct, res["checks"]
    assert res["checks"]["tier_rows_off"]["value"] == 0
