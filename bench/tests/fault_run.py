"""One rehearsal run of ``bench/run.py`` with a fault planted under the
timed path (or the control switched on), for ``test_correct.py``:

    python bench/tests/fault_run.py <workload> <variant> <seed>

``variant`` is ``sound``, ``control`` (the program's bfloat16 halo path),
or a fault of ``harness.faults``.  Prints the run's result line."""
import dataclasses
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import faults, main as hm  # noqa: E402


def plant(variant: str) -> None:
    build, load = hm.build_runtime, hm.load_cell
    if variant == "control":
        hm.load_cell = lambda name: (lambda c: dataclasses.replace(
            c, traffic={**c.traffic, "halo_dtype": "bf16"}))(load(name))
    elif variant in faults.LAYOUT_FAULTS:
        hm.build_runtime = lambda cell, art: build(
            cell, art, faults.LAYOUT_FAULTS[variant])
    elif variant in faults.RUNTIME_FAULTS:
        def planted(cell, art):
            rt, *rest = build(cell, art)
            return (faults.RUNTIME_FAULTS[variant](rt), *rest)
        hm.build_runtime = planted
    elif variant != "sound":
        raise SystemExit(f"unknown variant {variant!r}")


if __name__ == "__main__":
    workload, variant, seed = sys.argv[1:4]
    plant(variant)
    sys.exit(hm.main(["--workload", workload, "--seed", seed,
                      "--seconds", "0.5", "--trace", "0"], T_START))
