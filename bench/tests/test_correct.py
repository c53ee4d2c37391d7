"""The comparison that decides ``correct``, driven through a whole
rehearsal run on the CPU (a tiny configuration, no chip): sound runs come
out correct; the control (the program's bfloat16 halo path) and each fault
the cells can have, planted under the timed path, come out not correct.
Each case runs in its own process."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CELLS = ["cpu-tiny.capgnn", "cpu-tiny.vanilla"]
VARIANTS = {"sound": True, "control": False, "half_batch": False,
            "no_exchange": False, "unchanged": False}


def run(workload: str, variant: str, seed: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(HERE / "fault_run.py"),
                          workload, variant, str(seed)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("workload", CELLS)
def test_correct(workload, variant):
    res = run(workload, variant, 2**31 + 977)
    assert res["correct"] is VARIANTS[variant], res["checks"]
    assert list(res)[-1] == "checks"
