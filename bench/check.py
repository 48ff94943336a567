"""The comparison that decides ``correct``.

For each checked campaign the program's answer is held against the plain
reference (``bench/reference.py``) on the same inputs:

  sched_mismatch  integer entries of the schedule that differ: tau, d and
                  learner of every aggregation, and for asynchronous
                  rows the version and the staleness of each upload; a
                  mixing weight or keep that differs by more than 1e-12
                  counts too. Exact: the limit is 0.
  param_gap       by the worst leaf, the gap between the norms of the
                  program's and the reference's parameter change over the
                  campaign, |‖p - p0‖ - ‖r - p0‖|, over the larger of the
                  reference's ‖r - p0‖ of that leaf and of the median leaf
  param_dev       by the worst leaf, ‖p - r‖ over the same denominator
  acc_gap         the largest gap between the program's and the
                  reference's accuracy after an aggregation

Beside them ``bench/harness.py`` holds the window to its own invariants,
each with the limit 0: ``failed_campaigns`` (campaigns that raised),
``staging_hits`` (campaigns that replayed a staged schedule) and
``window_compiles`` (programs lowered or compiled in the window).

Leaves whose reference change is under a thousandth of the median leaf's
are left out of the two parameter numbers (none of the MLPs here has one).
"""

from __future__ import annotations

import numpy as np

WEIGHT_TOL = 1e-12


def sched_mismatch(prog: list, ref: list) -> int:
    bad = abs(len(prog) - len(ref))
    for p, r in zip(prog, ref):
        for key in ("tau", "d", "learners"):
            if key in p:
                a, b = np.asarray(p[key]), np.asarray(r[key])
                bad += int(a.shape != b.shape) or int(np.sum(a != b))
        if "version" in p:
            bad += int(p["version"] != r["version"])
            bad += int(np.sum(np.asarray(p["staleness"]) != np.asarray(r["staleness"])))
            bad += int(np.sum(np.abs(np.asarray(p["weights"]) - r["weights"])
                              > WEIGHT_TOL))
            bad += int(abs(p["keep"] - r["keep"]) > WEIGHT_TOL)
    return int(bad)


def _leaves(tree):
    import jax

    return [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(tree)]


def param_numbers(init, prog, ref) -> tuple[float, float]:
    """(param_gap, param_dev) of one campaign."""
    p0, p, r = _leaves(init), _leaves(prog), _leaves(ref)
    ref_change = np.array([np.linalg.norm(b - a) for a, b in zip(p0, r)])
    med = float(np.median(ref_change))
    gap = dev = 0.0
    for a, pp, rr, rc in zip(p0, p, r, ref_change):
        if rc < 1e-3 * med:
            continue
        den = max(rc, med)
        gap = max(gap, abs(np.linalg.norm(pp - a) - rc) / den)
        dev = max(dev, np.linalg.norm(pp - rr) / den)
    if not all(np.isfinite(x).all() for x in p):
        gap = dev = float("inf")
    return float(gap), float(dev)


def acc_gap(prog: list, ref: list) -> float:
    if len(prog) != len(ref):
        return float("inf")
    return float(np.max(np.abs(np.subtract(prog, ref)))) if prog else 0.0


def numbers(outcome, ref_rows, ref_params, ref_accs, init) -> dict:
    gap, dev = param_numbers(init, outcome.params, ref_params)
    return {"sched_mismatch": sched_mismatch(outcome.schedule, ref_rows),
            "param_gap": gap, "param_dev": dev,
            "acc_gap": acc_gap(outcome.accs, ref_accs)}


def worst(readings: list) -> dict:
    """Each number's worst reading over the checked campaigns."""
    return {k: max(r[k] for r in readings) for k in readings[0]}


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit. A number
    that is missing (no campaign to check) or not finite fails, and is
    shown as null."""
    sound = {k: values[k] is not None and bool(np.isfinite(values[k]))
             for k in limits}
    shown = {k: {"value": values[k] if sound[k] else None, "limit": limits[k]}
             for k in limits}
    ok = all(sound[k] and values[k] <= limits[k] for k in limits)
    return bool(ok), shown
