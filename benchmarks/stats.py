"""Small arithmetic the metric readers share."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0-100) by linear interpolation between the
    order statistics; None of nothing."""
    xs = sorted(v for v in values if v is not None)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values: Sequence[float]) -> Optional[float]:
    xs = [v for v in values if v is not None]
    return sum(xs) / len(xs) if xs else None


def field(run: Dict[str, Any], name: str) -> List[float]:
    """One stamp of every request the window finished."""
    return [r.get(name) for r in run.get("requests", [])]


def calls(run: Dict[str, Any], kind: str, lo: float, hi: float):
    """The benchmark's spans of one kind (`decode`, `prefill`) whose
    midpoint fell in [lo, hi] on the host's monotonic clock: tuples of
    (kind, start, end, tokens processed, live context tokens)."""
    return [c for c in run.get("calls", [])
            if c[0] == kind and lo <= (c[1] + c[2]) / 2 <= hi]


def traced_steps(run: Dict[str, Any]) -> Optional[int]:
    t = run.get("traced") or {}
    if "step_close" in t:
        return t["step_close"] - t["step_open"]
    return None


def work(run: Dict[str, Any]) -> Dict[str, int]:
    """What the cell's architecture gives the readers to count with: its
    adapter's `work` (models/__init__.py)."""
    cell = run["cell"]
    return cell["model"].work(cell["model"].dims(cell["config"]))
