"""The comparison that decides ``correct``, driven through a whole
rehearsal run on the CPU (a tiny configuration, no chip): sound runs come
out correct; the control (the program's bfloat16 halo path) and each fault
the cells can have, planted under the timed path, come out not correct.
Each case runs in its own process, on four CPU devices, so that the
``spmd`` traffic rehearses one partition per device."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CELLS = ["cpu-tiny.capgnn", "cpu-tiny.vanilla", "cpu-tiny.p2p4"]
VARIANTS = {"sound": True, "control": False, "half_batch": False,
            "no_exchange": False, "unchanged": False}
# faults of the gradient exchange between chips, for the spmd traffic only
SPMD_VARIANTS = {"no_grad_sync": False}
CASES = [(w, v, ok) for w in CELLS for v, ok in sorted(VARIANTS.items())] + \
    [("cpu-tiny.p2p4", v, ok) for v, ok in SPMD_VARIANTS.items()]


def run(workload: str, variant: str, seed: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " "
                          "--xla_force_host_platform_device_count=4"))
    out = subprocess.run([sys.executable, str(HERE / "fault_run.py"),
                          workload, variant, str(seed)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,variant,correct", CASES,
                         ids=[f"{w}-{v}" for w, v, _ in CASES])
def test_correct(workload, variant, correct):
    res = run(workload, variant, 2**31 + 977)
    assert res["correct"] is correct, res["checks"]
    assert list(res)[-1] == "checks"
