"""The comparison that decides ``correct``: numbers of the timed path
against the plain reference, each held to its limit from
``limits/<workload>.json``."""

from __future__ import annotations

import numpy as np


def round_off_only(ref_grads: dict, share: float = 1e-3) -> list:
    """Weights whose reference gradient is under ``share`` of the median
    weight's: under Adam they move by round-off alone, so their change is
    not compared."""

    med = float(np.median(list(ref_grads.values())))
    return sorted(k for k, v in ref_grads.items() if v < share * med)


def norm_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """Worst weight by the gap between the program's norm and the
    reference's, over the reference's norm of that weight or of the median
    weight, whichever is larger."""

    med = float(np.median(list(ref.values())))
    worst, name = 0.0, ""
    for k, r in ref.items():
        if k in skip:
            continue
        g = abs(prog[k] - r) / max(r, med)
        if g > worst or not name:
            worst, name = g, k
    return worst, name


def train_numbers(prog: dict, ref: dict) -> dict:
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    excluded = round_off_only(ref["grad_norms"])
    grad, grad_at = norm_gap(prog["grad_norms"], ref["grad_norms"])
    change, change_at = norm_gap(prog["change_norms"], ref["change_norms"], excluded)
    return {
        "loss_gap": max(losses),
        "grad_gap": grad,
        "change_gap": change,
        "_where": {"grad": grad_at, "change": change_at, "excluded": excluded},
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number in ``limits`` at or under its limit.  A number that is
    missing or not finite fails."""

    checks, ok = {}, True
    for name, spec in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= spec["limit"]
        ok = ok and bool(good)
        checks[name] = {"value": None if v is None else float(v), "limit": spec["limit"]}
    return ok, checks
