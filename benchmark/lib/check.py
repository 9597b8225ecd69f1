"""The comparison that decides `correct`: every number a runner compared
against the reference, each beside the limit of its own from the cell's limits
file. A number with no limit in the file fails the run: a limit is set from
readings (PERF.md), never assumed."""
from __future__ import annotations

import math
import statistics


def judge(compared: dict, limits_file: dict, rehearsal: bool = False) -> dict:
    limits = limits_file["rehearsal_limits" if rehearsal else "limits"]
    out, ok_all = {}, True
    for name, value in compared.items():
        limit = limits.get(name)
        ok = (limit is not None and value is not None
              and math.isfinite(value) and value <= limit)
        ok_all = ok_all and ok
        out[name] = {"value": value, "limit": limit, "ok": ok}
    missing = [n for n in limits if n not in compared]
    for n in missing:                      # a number the run did not produce
        out[n] = {"value": None, "limit": limits[n], "ok": False}
    return {"correct": ok_all and not missing, "compared": out}


def worst_leaf_gap(got: dict, ref: dict, keep=None) -> tuple:
    """Largest over leaves of |got's norm - ref's norm| over the larger of
    the reference's norm of that leaf and of its median leaf. Returns (gap,
    leaf). `keep` restricts the leaves that count."""
    names = [n for n in ref if keep is None or n in keep]
    floor = statistics.median(ref[n] for n in names)
    worst, where = 0.0, None
    for n in names:
        gap = abs(got[n] - ref[n]) / max(ref[n], floor)
        if gap >= worst:
            worst, where = gap, n
    return worst, where


def leaves_with_gradient(ref_grad: dict) -> set:
    """Leaves whose reference gradient is a thousandth of the median leaf's
    or more: the others move under Adam by round-off alone and are left out
    of the parameter-change comparison."""
    floor = 1e-3 * statistics.median(ref_grad.values())
    return {n for n, g in ref_grad.items() if g >= floor}
