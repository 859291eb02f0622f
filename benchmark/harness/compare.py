"""The comparison that decides ``correct``: each number compared has a limit
of its own, kept in ``limits/<cell>.json`` with the two readings it was set
from (PERF.md gives them too). A number the file does not list is printed
and not compared; an exact comparison has the limit 0."""

from __future__ import annotations

import json
import math
import os
import sys
from typing import Dict, Optional, Sequence, Tuple

import numpy as np


def load_limits(bench_dir: str, cell: str) -> Dict[str, Dict]:
    path = os.path.join(bench_dir, "limits", cell + ".json")
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    return doc["numbers"]


def worst_leaf_gap(program: Sequence[float], reference: Sequence[float],
                   counted: Optional[Sequence[bool]] = None
                   ) -> Tuple[float, int]:
    """The gap between the program's per-leaf norm and the reference's (not
    the norm of their difference), against the reference's norm of that leaf
    or of the median leaf, whichever is larger; the worst leaf and its
    index."""
    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    floor = float(np.median(r))
    gap = np.abs(p - r) / np.maximum(r, floor)
    if counted is not None:
        gap = np.where(np.asarray(counted, bool), gap, 0.0)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def moved_leaves(reference_grad_norms: Sequence[float]) -> np.ndarray:
    """Leaves counted in the parameters' change: those whose first gradient,
    in the reference, is at least a thousandth of the median leaf's. The
    others move under Adam by round-off alone."""
    g = np.asarray(reference_grad_norms, np.float64)
    return g >= 1e-3 * float(np.median(g))


def judge(numbers: Dict[str, float], limits: Dict[str, Dict]
          ) -> Tuple[bool, Dict[str, Dict]]:
    """(correct, {name: {"value", "limit"}}): correct only if every number
    that has a limit is a number and does not pass it, and every number the
    limits file lists was produced."""
    rows: Dict[str, Dict] = {}
    ok = True
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit")
        rows[name] = {"value": value, "limit": lim}
        if lim is None:
            continue
        if value is None or not math.isfinite(float(value)) \
                or float(value) > float(lim):
            ok = False
    for name, spec in limits.items():
        if name not in numbers and spec.get("limit") is not None:
            rows[name] = {"value": None, "limit": spec["limit"]}
            ok = False
    return ok, rows


def verdict(limits: Dict[str, Dict], numbers: Dict[str, float],
            over: Optional[Dict[str, float]] = None) -> bool:
    """``correct`` as a run's last line would say it, of ``numbers`` with
    those in ``over`` put in their place (of the limits, those whose number
    these readings produce: a window's own, as the closing loss, are not).
    The calibration's readings go through it."""
    got = dict(numbers, **(over or {}))
    return judge(got, {k: v for k, v in limits.items() if k in got})[0]


def print_rows(rows: Dict[str, Dict], correct: bool) -> None:
    """Each number compared beside its limit, as the last lines on standard
    error."""
    for name, row in rows.items():
        print(f"compared {name}: value {row['value']} limit {row['limit']}",
              file=sys.stderr)
    print(f"correct: {str(bool(correct)).lower()}", file=sys.stderr,
          flush=True)
