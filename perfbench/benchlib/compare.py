"""The comparison that decides ``correct``: the program's readings of its
first two training steps against the plain reference's.

Numbers compared (each against a limit of its own, from
``perfbench/limits/<workload>.json``):

- ``loss1``, ``loss2``: relative gap of each step's loss;
- ``grad``: worst leaf's gap between the norms of the first gradient as
  the optimizer takes it, over the reference's norm of that leaf or of
  the median leaf, whichever is larger;
- ``change``: the same for the norm of each leaf's change after the two
  steps, over the leaves whose reference gradient is at least
  ``MOVED`` of the median leaf's (a leaf whose gradient is nought to
  rounding, as a key bias under softmax, moves by round-off alone)."""
from __future__ import annotations

import math
import statistics
from typing import Dict, Tuple

from benchlib.reference import Readings

MOVED = 1e-3
NAMES = ("loss1", "loss2", "grad", "change")


def _gaps(prog: Dict[str, float], ref: Dict[str, float], keep
          ) -> Dict[str, float]:
    floor = statistics.median(ref[n] for n in keep)
    return {n: abs(prog[n] - ref[n]) / max(ref[n], floor) for n in keep}


def leaf_gaps(prog: Readings, ref: Readings) -> Dict[str, Dict[str, float]]:
    """Per leaf, the gaps that ``grad`` and ``change`` take the worst of."""
    if set(prog.grad) != set(ref.grad) or set(prog.change) != set(ref.change):
        raise ValueError("program and reference name different leaves")
    med = statistics.median(ref.grad.values())
    moved = sorted(n for n, g in ref.grad.items() if g >= MOVED * med)
    return {"grad": _gaps(prog.grad, ref.grad, sorted(ref.grad)),
            "change": _gaps(prog.change, ref.change, moved)}


def numbers(prog: Readings, ref: Readings) -> Dict[str, float]:
    out = {f"loss{i + 1}": abs(p - r) / abs(r)
           for i, (p, r) in enumerate(zip(prog.losses, ref.losses))}
    for name, gaps in leaf_gaps(prog, ref).items():
        out[name] = max(gaps.values())
    return out


def judge(nums: Dict[str, float], limits: dict) -> Tuple[bool, dict]:
    """(correct, {name: [number, limit]}).  A number that is not finite,
    or one without a limit, is not correct."""
    checked, ok = {}, True
    for name in NAMES:
        v = nums.get(name, math.nan)
        lim = limits[name]["limit"] if name in limits else math.nan
        checked[name] = [v, lim]
        ok = ok and math.isfinite(v) and math.isfinite(lim) and v <= lim
    return ok, checked
