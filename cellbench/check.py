"""How ``correct`` is decided: the timed path against the plain reference.

Training: the losses of the first three steps, the norm of the first
gradient as the optimizer got it and the norm of the parameters' change
after the three, each by the worst leaf. Serving: over a seeded sample of
greedy requests the window finished, the widest gap by which a served
token's logit lies below the reference's best. Every number compared has
a limit of its own in ``limits/<workload>.json``.
"""

from __future__ import annotations

import json
import sys

import numpy as np


def worst_leaf_gap(got: dict, ref: dict, skip: dict | None = None):
    """``max |got - ref| / max(ref, median ref)`` over leaves: the gap of
    the norms, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some leaves are all but zero). ``skip``
    marks leaves left out. Returns (gap, name of the worst leaf)."""
    names, g, r, keep = [], [], [], []
    for k in sorted(ref):
        n = len(ref[k])
        names += [f"{k}[{i}]" if n > 1 else k for i in range(n)]
        g += list(np.asarray(got[k], np.float64))
        r += list(np.asarray(ref[k], np.float64))
        keep += list(np.ones(n, bool) if skip is None
                     else ~np.asarray(skip[k], bool))
    g, r, keep = np.asarray(g), np.asarray(r), np.asarray(keep)
    gap = np.abs(g - r) / np.maximum(r, np.median(r))
    gap = np.where(keep & np.isfinite(gap), gap, np.where(keep, np.inf, -1))
    i = int(np.argmax(gap))
    return float(gap[i]), names[i]


def still_leaves(ref_grad_norms: dict) -> dict:
    """Leaves whose gradient is nought to rounding in the reference: under
    a thousandth of the median leaf's. Under Adam they move by round-off
    alone, so the comparison of the change leaves them out."""
    flat = np.concatenate([np.asarray(v, np.float64)
                           for v in ref_grad_norms.values()])
    floor = 1e-3 * np.median(flat)
    return {k: np.asarray(v) < floor for k, v in ref_grad_norms.items()}


def verdict(readings: dict, limits: dict) -> tuple:
    """``(correct, checks)``: every reading beside its limit, in order. A
    reading without a limit, or one that is not finite, is not correct."""
    checks, ok = {}, True
    for name, value in readings.items():
        if name.startswith("_"):
            continue               # kept for the calibration, not compared
        limit = limits.get(name)
        good = (limit is not None and value is not None
                and np.isfinite(value) and value <= limit)
        ok = ok and bool(good)
        checks[name] = {"value": value if value is None else float(value),
                        "limit": limit}
    return ok, checks


def print_checks(checks: dict, correct: bool) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error."""
    for name, c in checks.items():
        print(f"check {name} value={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(f"correct={json.dumps(bool(correct))}", file=sys.stderr, flush=True)
