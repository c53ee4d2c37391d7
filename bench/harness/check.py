"""The comparison that decides ``correct``.

Three numbers, each against its limit in ``bench/limits/<workload>.json``:

- ``loss_gap``: the largest relative gap between the program's loss and
  the reference's over the first three steps;
- ``grad_gap``: over the parameter leaves, the largest gap between the
  norm of the program's first gradient (read from Adam's first moment
  after one step) and the reference's, relative to the larger of that
  leaf's reference norm and the median leaf's;
- ``update_gap``: the same gap for the norm of each leaf's change over the
  three steps.  Leaves whose reference gradient is under a thousandth of
  the median leaf's move by round-off alone and are left out.

And one exact number, with the limit 0: ``tier_rows_off``, the halo rows
that the program's cache plan puts in another tier (cached or exchanged)
than CaPGNN's rules do (``reference.cache_tiers``).
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "update_gap")
EXACT = ("tier_rows_off",)
ROUNDOFF_LEAF = 1e-3


def _leaf_gap(prog, ref, keep=None) -> float:
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    scale = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(scale, 1e-30)
    if keep is not None:
        gap = gap[keep]
    return float(gap.max())


def compare(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: ``losses`` (per step), ``grad_norms`` and
    ``update_norms`` (per leaf).  Returns ``{number: value}``."""
    lp, lr = np.asarray(prog["losses"], float), np.asarray(ref["losses"], float)
    if lp.shape != lr.shape or not np.isfinite(lp).all():
        loss_gap = float("inf")
    else:
        loss_gap = float((np.abs(lp - lr) / np.abs(lr)).max())
    g_ref = np.asarray(ref["grad_norms"], float)
    keep = g_ref >= ROUNDOFF_LEAF * np.median(g_ref)
    out = {"loss_gap": loss_gap,
           "grad_gap": _leaf_gap(prog["grad_norms"], g_ref),
           "update_gap": _leaf_gap(prog["update_norms"], ref["update_norms"],
                                   keep)}
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {number: {"value", "limit"}})``; every number of
    ``NUMBERS`` needs a limit in ``limits``."""
    missing = [k for k in NUMBERS if k not in limits]
    if missing:
        raise SystemExit(f"no limit for {missing} in bench/limits")
    checks = {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS}
    checks.update({k: {"value": values[k], "limit": 0} for k in EXACT})
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
