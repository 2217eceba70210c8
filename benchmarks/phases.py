"""The program's own phase records (`determined_tpu.common.trace.phase`),
cut to a run's window for the metric readers.

A record is `{name, start, end, parent, iteration, thread, counts}` on the
host's monotonic clock — the clock of every stamp in `loops.py`. A program
that has no phases (the parent of the PR that brought them) gives no
records, and a reader then returns nothing.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple


def records(lo: float, hi: float) -> List[Dict[str, Any]]:
    """The records that started in [lo, hi), oldest first."""
    from determined_tpu.common import trace

    log = getattr(trace, "phase_log", None)
    if log is None:
        return []
    return [r for r in log(since=lo) if lo <= r["start"] < hi]


def serve_window(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The window's edges are ends of engine calls, so a batcher phase
    lies on one side of them or the other."""
    return records(run["t_open"], run["t_close"])


def train_window(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """From the window's first report to its last: `fit` steps that began
    between the two stamps."""
    reports = run.get("reports") or []
    if len(reports) < 2:
        return []
    return records(reports[0]["t"], reports[-1]["t"])


def seconds(record: Dict[str, Any]) -> float:
    return record["end"] - record["start"]


def less_child(log: List[Dict[str, Any]], parent: str,
               child: str) -> Optional[List[float]]:
    """Seconds of every `parent` record less its `child` (the phase in
    which the host waits for the device): the host's own time in it."""
    waits: Dict[Tuple[int, int], float] = {}
    for r in log:
        if r["name"] == child and r["parent"] == parent:
            key = (r["thread"], r["iteration"])
            waits[key] = waits.get(key, 0.0) + seconds(r)
    return [seconds(r) - waits.get((r["thread"], r["iteration"]), 0.0)
            for r in log if r["name"] == parent]
