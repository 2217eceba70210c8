"""Trial-lifecycle tracing: spans from submit to step (docs/observability.md).

A span is `{trace_id, span_id, parent, name, start_us, end_us, attrs}` with
wall-clock epoch microseconds, so master/agent/harness spans from different
hosts land on one timeline. The master opens the root span (span_id ==
trace_id) at trial submit and propagates the trace id to every container as
`DET_TRACE_ID`; everything the harness emits parents to that root unless
nested under an enclosing `span()` context.

Always-on cheap: `span()`/`emit()` append to an in-memory buffer — no I/O,
no locks on the step critical path (spans mark phase boundaries and ship).
The buffer is flushed alongside the metrics
flush via `flush()`, POSTing one idempotency-keyed batch to
`POST /api/v1/trials/{id}/spans`. A lost span sink must never hurt the
trial: flush failures log and drop (the `trace.span.drop` fault point
proves that path deterministically, docs/chaos.md).

Per-step work is timed by `phase()`: the batcher loop and the fit loop open
one around each part of an iteration. A phase is a
`jax.profiler.TraceAnnotation` (so it sits in the host plane of any profiler
session, on the clock of the device's `XLA Ops` line) and one record in a
bounded process-wide ring on `time.monotonic()`, read back by `phase_log()`.
Phases never ship: at 10-50 records a second a replica they must not reach
the master's span table. `Tracer.span()` opens the same annotation.

Span and phase names are registered in common/metric_names.py (SPAN_NAMES);
the metric/span lint keeps emitters and registry in sync.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from determined_tpu.common import faultpoint

logger = logging.getLogger("determined_tpu.common")

FAULT_SPAN_DROP = "trace.span.drop"


# Records the phase ring holds before the oldest is dropped: a 30 s window
# and its set-up at ~200 records a second (a 20 ms decode step leaves four),
# with room over.
PHASE_RING = 32768


def _switched_off() -> bool:
    return os.environ.get("DET_TRACE_OFF", "") in ("1", "true")


# Read once: phases sit on the step path, and a look at the environment
# costs more than the rest of a phase.
_PHASES_ON = not _switched_off()
_ring: "collections.deque[Phase]" = collections.deque(maxlen=PHASE_RING)
_open = threading.local()    # .top: this thread's innermost open phase
_annotation_cls: Any = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _annotation(name: str):
    """A profiler annotation, or None in a process that has not loaded jax
    (a plain-Python trial has no profiler to sit in, and importing jax for
    the sake of a span would cost it seconds)."""
    global _annotation_cls
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            return None
        _annotation_cls = TraceAnnotation
    return _annotation_cls(name)


class Phase:
    """One timed part of a loop iteration; see `phase()`."""

    __slots__ = ("name", "start", "end", "parent", "iteration", "thread",
                 "counts", "_annotation", "_outer", "_keep")
    live = True   # False on the stand-in that DET_TRACE_OFF=1 hands out

    def __init__(self, name: str, iteration: Optional[int],
                 counts: Dict[str, Any]):
        self.name, self.iteration, self.counts = name, iteration, counts
        self.start = self.end = 0.0
        self.parent: Optional[str] = None
        self._keep = True

    def __enter__(self) -> "Phase":
        outer = self._outer = getattr(_open, "top", None)
        if outer is not None:
            self.parent = outer.name
            if self.iteration is None:
                self.iteration = outer.iteration
        _open.top = self
        self.thread = threading.get_ident()
        self._annotation = _annotation(self.name)
        if self._annotation is not None:
            self._annotation.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.monotonic()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _open.top = self._outer
        if self._keep:
            _ring.append(self)
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def set(self, **counts) -> None:
        """Counts that are known only inside the phase."""
        self.counts.update(counts)

    def cancel(self) -> None:
        """Leave no record: the phase turned out to hold no work."""
        self._keep = False

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "start": self.start, "end": self.end,
                "parent": self.parent, "iteration": self.iteration,
                "thread": self.thread, "counts": self.counts}


class _NoPhase:
    """What `phase()` hands out under DET_TRACE_OFF=1."""

    live = False
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **counts) -> None:
        pass

    def cancel(self) -> None:
        pass


_NO_PHASE = _NoPhase()


def phase(name: str, iteration: Optional[int] = None, **counts):
    """Context manager timing one part of a loop iteration.

    `iteration` is the loop's own number for this turn; a phase opened
    inside another inherits it. Open phases only on the thread that runs
    the loop (the batcher thread, the thread inside `fit`): a profiler
    trace names a device gap by the latest host event over all threads.
    """
    if not _PHASES_ON:
        return _NO_PHASE
    return Phase(name, iteration, counts)


def phase_log(since: Optional[float] = None) -> List[Dict[str, Any]]:
    """The ring's records, oldest first, as `{name, start, end, parent,
    iteration, thread, counts}` with times on `time.monotonic()`; with
    `since`, those that ended at or after it."""
    records = list(_ring)
    if since is not None:
        # appended at exit, so ends rise along the ring
        first = len(records)
        while first and records[first - 1].end >= since:
            first -= 1
        records = records[first:]
    return [p.to_dict() for p in records]


def now_us() -> int:
    """Wall-clock epoch microseconds (all components share this domain)."""
    return int(time.time() * 1e6)


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


class Span:
    __slots__ = ("trace_id", "span_id", "parent", "name", "start_us",
                 "end_us", "attrs")

    def __init__(self, trace_id: str, name: str, parent: str = "",
                 start_us: Optional[int] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent = parent
        self.name = name
        self.start_us = start_us if start_us is not None else now_us()
        self.end_us = 0
        self.attrs = dict(attrs or {})

    def to_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent": self.parent,
            "name": self.name,
            "start_us": self.start_us,
            "end_us": self.end_us,
            "attrs": self.attrs,
        }


class Tracer:
    """Buffered span emitter for one trial process.

    Chief-only on multi-host trials (non-chief construction yields a
    disabled tracer); local/masterless mode buffers into `local_spans` so
    the same instrumentation is inspectable without a cluster.
    `DET_TRACE_OFF=1` disables emission entirely (the bench A/B switch).
    """

    def __init__(
        self,
        session=None,
        trial_id: int = 0,
        trace_id: Optional[str] = None,
        enabled: Optional[bool] = None,
    ):
        self._session = session
        self._trial_id = trial_id
        self.trace_id = trace_id or os.environ.get("DET_TRACE_ID") or \
            uuid.uuid4().hex[:16]
        if enabled is None:
            enabled = not _switched_off()
        self.enabled = enabled
        # The root span lives master-side with span_id == trace_id; local
        # mode has no master, so parentage still resolves to the trace id.
        self.root_span_id = self.trace_id
        self._buf: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._tls = threading.local()  # per-thread current-parent stack
        # Local mode keeps every span ever emitted (tests, `bench.py`).
        self.local_spans: List[Dict[str, Any]] = []
        self.dropped = 0  # batches lost to sink failure (observability only)

    # -- emission ------------------------------------------------------

    def _parent(self) -> str:
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else self.root_span_id

    def emit(self, name: str, start_us: int, end_us: int,
             attrs: Optional[Dict[str, Any]] = None,
             parent: Optional[str] = None) -> Optional[Span]:
        """Record a completed span (buffer append only; no I/O)."""
        if not self.enabled:
            return None
        sp = Span(self.trace_id, name,
                  parent=parent if parent is not None else self._parent(),
                  start_us=start_us, attrs=attrs)
        sp.end_us = end_us
        rec = sp.to_dict()
        with self._lock:
            self._buf.append(rec)
        return sp

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Context manager: times the block, nests children under it.

        Yields the Span (attrs may be amended inside the block); exceptions
        propagate after the span is recorded with `error` set.
        """
        if not self.enabled:
            yield None
            return
        sp = Span(self.trace_id, name, parent=self._parent(), attrs=attrs)
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(sp.span_id)
        annotation = _annotation(name) or contextlib.nullcontext()
        try:
            with annotation:
                yield sp
        except BaseException as e:
            sp.attrs["error"] = type(e).__name__
            raise
        finally:
            stack.pop()
            sp.end_us = now_us()
            with self._lock:
                self._buf.append(sp.to_dict())

    # -- flushing ------------------------------------------------------

    def pending(self) -> int:
        with self._lock:
            return len(self._buf)

    def flush(self) -> int:
        """Ship the buffered batch. Off the step critical path — called at
        metric-flush boundaries and close(). Never raises: a dead span sink
        must not take the trial with it. Returns spans shipped (or locally
        recorded)."""
        with self._lock:
            if not self._buf:
                return 0
            batch, self._buf = self._buf, []
        if faultpoint.fire(FAULT_SPAN_DROP) is not faultpoint.Action.NONE:
            logger.warning("faultpoint dropped %d span(s)", len(batch))
            self.dropped += 1
            return 0
        if self._session is None:
            self.local_spans.extend(batch)
            return len(batch)
        try:
            # idempotent: a retry after a lost response must not
            # double-insert the batch (master dedupes by span_id anyway —
            # the header saves it the writes).
            self._session.post(
                f"/api/v1/trials/{self._trial_id}/spans",
                body={"spans": batch},
                idempotent=True,
            )
            return len(batch)
        except Exception:
            # Tracing is best-effort by contract: drop the batch, keep
            # training (docs/chaos.md `trace.span.drop`).
            self.dropped += 1
            logger.warning("span flush failed; dropped %d span(s)",
                           len(batch), exc_info=True)
            return 0

    def close(self) -> None:
        self.flush()


def render_waterfall(spans: List[Dict[str, Any]], width: int = 48) -> str:
    """Text waterfall for `det trial trace` — one line per span, indented
    by parentage, with an offset-scaled duration bar."""
    if not spans:
        return "(no spans)"
    spans = sorted(spans, key=lambda s: (int(s.get("start_us", 0) or 0)))
    by_id = {s["span_id"]: s for s in spans if s.get("span_id")}

    def depth(s, limit=16):
        d, cur = 0, s
        while d < limit:
            p = cur.get("parent") or ""
            if not p or p not in by_id or p == cur.get("span_id"):
                break
            cur = by_id[p]
            d += 1
        return d

    t0 = min(int(s.get("start_us", 0) or 0) for s in spans)
    ends = [int(s.get("end_us", 0) or 0) for s in spans]
    t1 = max([e for e in ends if e] + [t0 + 1])
    scale = max(t1 - t0, 1)
    name_w = max(len("  " * depth(s) + s.get("name", "?")) for s in spans)
    lines = [f"{'span':<{name_w}}  {'start_ms':>9} {'dur_ms':>9}  timeline"]
    for s in spans:
        start = int(s.get("start_us", 0) or 0)
        end = int(s.get("end_us", 0) or 0)
        off_ms = (start - t0) / 1000.0
        dur_ms = (end - start) / 1000.0 if end else float("nan")
        lo = int((start - t0) / scale * width)
        hi = int(((end if end else t1) - t0) / scale * width)
        bar = " " * lo + ("#" * max(hi - lo, 1) if end else "~" * max(width - lo, 1))
        label = "  " * depth(s) + s.get("name", "?")
        dur = f"{dur_ms:9.1f}" if end else "  running"
        lines.append(f"{label:<{name_w}}  {off_ms:9.1f} {dur}  |{bar}|")
    return "\n".join(lines)
