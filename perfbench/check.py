"""The comparison that decides ``correct``.

Training cells compare the program's first steps with the reference's
(``perfbench/reference.py``) on the same seed and batches:

* ``loss_gap``: the largest relative gap between the two losses over
  the checked steps;
* ``grad_gap``: the worst leaf's gap between the norms of the first
  gradient as the optimizer gets it (clipped; the program's is read
  back from Adam's first moment, ``mu / (1 - b1)``);
* ``change_gap``: the worst leaf's gap between the norms of each
  parameter's change over the checked steps.

A leaf's gap is ``|norm_program - norm_reference|`` over the larger of
the reference's norm of that leaf and the median leaf's. Leaves whose
reference gradient is under a thousandth of the median leaf's move by
round-off alone under Adam and are left out of ``change_gap``.

Serving cells compare ``logit_gap``: over a sample of the requests the
window finished, the widest gap by which a served token's reference
logit lies below the reference's best at its position.

The numbers compared are those with a limit in
``perfbench/limits/<workload>.json`` (:func:`set_limits` sets them from
readings); one that is not finite or over its limit fails.
"""
from __future__ import annotations

import math
import statistics

SMALL_GRAD = 1e-3


def leaf_gap(prog: dict, ref: dict, keys=None):
    """(worst gap, its leaf) over ``keys`` (default: every leaf)."""
    keys = sorted(ref) if keys is None else sorted(keys)
    if set(keys) - set(prog):
        missing = sorted(set(keys) - set(prog))
        return math.inf, f"missing in program: {missing[:3]}"
    med = statistics.median(ref.values())
    worst, at = -1.0, None
    for k in keys:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, at = gap, k
    return worst, at


def moving_leaves(ref_grad: dict) -> list:
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= SMALL_GRAD * med]


def train_readings(prog: dict, ref: dict) -> dict:
    """The numbers a training cell compares, with the leaf each worst
    gap sits at (for the log)."""
    lg = max(abs(a - b) / abs(b) if math.isfinite(a) else math.inf
             for a, b in zip(prog["losses"], ref["losses"]))
    gg, g_at = leaf_gap(prog["grad_norms"], ref["grad_norms"])
    cg, c_at = leaf_gap(prog["change_norms"], ref["change_norms"],
                        moving_leaves(ref["grad_norms"]))
    return {"loss_gap": lg, "grad_gap": gg, "change_gap": cg,
            "_at": {"grad_gap": g_at, "change_gap": c_at}}


def judge(readings: dict, limits: dict) -> tuple:
    """(correct, check): every number that has a limit, beside it. A
    cell without limits is never correct."""
    check, ok = {}, bool(limits)
    for name, limit in limits.items():
        value = readings.get(name, math.inf)
        check[name] = {"value": value, "limit": limit}
        if not math.isfinite(value) or value > limit:
            ok = False
    return ok, check


def set_limits(program: list, control: list, faults: dict) -> tuple:
    """Limits from readings (each a list of {number: value} over seeds):
    the lower reading is the largest the program gives; the upper is
    the smallest the control gives where that is at least three times
    the lower, or a fault's smallest where that is at least ten times
    it (a state left unchanged, ``change_gap`` 1, at least three). The
    limit lies between, nearer the upper in log scale:
    ``lower^(1/3) * upper^(2/3)``, to two significant digits. A number
    with no upper reading gets no limit. Returns (limits, notes)."""
    limits, notes = {}, {}
    for name in program[0]:
        lower = max(r[name] for r in program)
        cands = []
        ctl = min((r[name] for r in control), default=None)
        if ctl is not None and ctl >= 3 * lower:
            cands.append(("control", ctl))
        for fault, rows in faults.items():
            worst = min(r[name] for r in rows)
            need = 3 if fault == "state_unchanged" else 10
            if worst >= need * lower:
                cands.append((fault, worst))
        notes[name] = {"lower": lower, "control": ctl,
                       "faults": {f: min(r[name] for r in rows) for f, rows in faults.items()}}
        if not cands:
            notes[name]["limit"] = None
            continue
        by, upper = min(cands, key=lambda c: c[1])
        limit = float(f"{lower ** (1 / 3) * upper ** (2 / 3):.2g}")
        limits[name] = limit
        notes[name].update(upper=upper, upper_from=by, limit=limit)
    return limits, notes
