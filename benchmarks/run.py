"""Benchmark orchestrator: one suite per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only name[,name...] ...]

``--only`` accepts space- and/or comma-separated suite names and rejects
unknown ones up front.  Each suite writes experiments/<name>.json and
prints a summary line; the final PASS/FAIL recap checks the paper's
qualitative claims hold.  After every invocation (even a --only subset)
the orchestrator folds the top-level scalars of ALL experiments/*.json
into a single experiments/bench_summary.json, so the perf trajectory
stays trackable across PRs from one artifact.  A suite that raises marks
its summary entry with ``_failed`` (so a stale JSON from an earlier run
can't masquerade as green — ``benchmarks.check_regression`` treats it as
a regression) and the process exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SUITES = ["halo_obs", "cache_hit", "comm_volume", "rapa_balance",
          "heterogeneous", "convergence", "overall", "kernels_bench",
          "serve_bench", "adaptive_cache", "out_of_core",
          "fault_tolerance"]

_SUMMARY = "bench_summary"
# not suite outputs: the folded summary itself and the regression baseline
_NON_SUITE = {_SUMMARY + ".json", "baseline.json"}
# trace artifacts (repro.obs exports) live beside the suite JSONs but are
# timelines, not headline scalars — never fold them into the summary
_TRACE_PREFIXES = ("trace_", "metrics_")


def provenance() -> dict:
    """Environment stamp folded into bench_summary.json so every archived
    summary records what produced it."""
    prov: dict = {}
    try:
        import jax
        devs = jax.devices()
        prov.update(jax_version=jax.__version__,
                    platform=devs[0].platform,
                    device_kind=devs[0].device_kind,
                    device_count=len(devs))
    except Exception as exc:  # noqa: BLE001 - stamp what we can
        prov["jax_error"] = repr(exc)
    import platform as _pl
    prov["python"] = _pl.python_version()
    prov["machine"] = _pl.machine()
    return prov


def summarize(out_dir: str, failed: dict | None = None) -> dict:
    """Fold every experiments/*.json into one summary: per file, the
    top-level scalar fields (the headline numbers each suite promotes)
    plus the file's mtime.  Nested sweeps stay in their own files."""
    summary = {}
    for fname in sorted(os.listdir(out_dir)):
        if (not fname.endswith(".json") or fname in _NON_SUITE
                or fname.startswith(_TRACE_PREFIXES)):
            continue
        path = os.path.join(out_dir, fname)
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError) as exc:
            summary[fname[:-5]] = {"unreadable": repr(exc)}
            continue
        scalars = {k: v for k, v in payload.items()
                   if isinstance(v, (int, float, bool, str))}
        # transport sweep headline numbers live one level down
        ts = payload.get("transport_sweep")
        if isinstance(ts, dict):
            scalars.update({f"transport_{k}": v for k, v in ts.items()
                            if isinstance(v, (int, float, bool))})
        scalars["_mtime"] = time.strftime(
            "%Y-%m-%dT%H:%M:%S", time.gmtime(os.path.getmtime(path)))
        summary[fname[:-5]] = scalars
    # a suite that raised this invocation may have left a stale (or no)
    # JSON behind — mark it so downstream gates see red, not stale green
    for name, err in (failed or {}).items():
        summary.setdefault(name, {})["_failed"] = err
    return summary


def write_summary(out_dir: str | None = None,
                  failed: dict | None = None,
                  walls: dict | None = None) -> str:
    if out_dir is None:
        out_dir = os.path.join(os.path.dirname(__file__), "..",
                               "experiments")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, _SUMMARY + ".json")
    summary = summarize(out_dir, failed=failed)
    # per-suite orchestrator wall time; "_wall_s" is in the regression
    # gate's SKIP_KEYS so it is recorded but never gated.  CI runs one
    # suite per invocation, so carry stamps for suites not in this run
    # forward from the previous summary instead of re-folding them away.
    try:
        with open(path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        prev = {}
    for name, fields in summary.items():
        old = prev.get(name)
        if (isinstance(fields, dict) and isinstance(old, dict)
                and "_wall_s" in old and name not in (walls or {})):
            fields["_wall_s"] = old["_wall_s"]
    for name, wall in (walls or {}).items():
        summary.setdefault(name, {})["_wall_s"] = round(wall, 2)
    # per-suite provenance: suites executed this invocation are stamped
    # with the current environment; entries folded from stale JSONs carry
    # their stamp forward from the previous summary.  When no previous
    # summary exists (fresh checkout + --only single-suite), every entry
    # still gets the current stamp instead of silently losing provenance.
    prov = provenance()
    for name, fields in summary.items():
        if not isinstance(fields, dict):
            continue
        old = prev.get(name)
        if (name in (walls or {}) or not isinstance(old, dict)
                or "_prov" not in old):
            fields["_prov"] = prov
        else:
            fields["_prov"] = old["_prov"]
    summary["_provenance"] = prov
    with open(path, "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="suite names, space- and/or comma-separated")
    ap.add_argument("--trace", action="store_true",
                    help="run the training suites under the repro.obs "
                         "tracer and attach Perfetto trace artifacts "
                         "(experiments/trace_<suite>.json) per suite")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        os.environ["REPRO_BENCH_TRACE"] = "1"
    names: list[str] = []
    for chunk in (args.only or []):
        names.extend(n for n in chunk.split(",") if n)
    names = names or SUITES
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        print(f"unknown suite(s) {unknown}; available: {SUITES}",
              file=sys.stderr)
        sys.exit(2)

    import importlib
    results, failures, walls = {}, [], {}
    for name in names:
        print(f"=== {name} ===", flush=True)
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.main()
            results[name] = "ok"
        except Exception as exc:  # noqa: BLE001 - keep the sweep going
            failures.append((name, repr(exc)))
            results[name] = f"FAIL {exc!r}"
            print(f"FAIL {name}: {exc!r}")
        walls[name] = time.perf_counter() - t0
        print(f"--- {name} done in {walls[name]:.1f}s\n", flush=True)

    path = write_summary(failed=dict(failures), walls=walls)
    print(f"=== summary (aggregated -> {os.path.relpath(path)}) ===")
    for name in names:
        print(f"  {name:15s} {results[name]}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
