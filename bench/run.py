#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``<name>`` is a workload of ``BENCHMARK.json``.  The run loads (or builds
and keeps, under ``build/bench/``) the cell's partition, draws the weights
from ``--seed`` on the device, runs the set-up steps that compile every
step flavour, then trains for ``--seconds`` and prints one JSON line.
With ``--trace 0`` its metrics are the end-to-end ones (``epoch_ms``,
``setup_s``); with ``--trace 1`` the window runs under the profiler and
the metrics are the per-layer ones read from its capture.  Either way the
first three set-up steps are compared with the plain reference
afterwards, and
``correct`` says whether each compared number stayed under its limit.

It needs a TPU with as many chips as the cell asks for, and exits non-zero
without one.  ``--workload cpu-tiny.capgnn`` (any traffic) rehearses the
harness on the CPU with ``JAX_PLATFORMS=cpu``; that run gives no device
figure.  An ``spmd`` traffic (``cpu-tiny.p2p4``) wants one CPU device per
partition: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from harness.main import main
    sys.exit(main(sys.argv[1:], T_START))
