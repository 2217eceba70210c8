"""From the profiler's `.xplane.pb` to numbers: device busy and idle time,
time per device operation under names that survive a recompile, the idle
gaps by what the host was doing, and the `breakdown` of a result line.

Read with nothing but `jax.profiler.ProfileData`. What the planes look
like on the v5e (checked on a recorded trace, tests/benchmark/fixtures):
one plane per chip, `/device:TPU:<n>`, whose line `XLA Ops` holds one
event per executed HLO operation, named by its whole instruction text
(`%fusion.7 = bf16[8,1024]{...} fusion(...), kind=kLoop`; asynchronous
copies and collectives also span `Async XLA Ops`), and a host plane
`/host:CPU` with one line per thread, where the benchmark's
`jax.profiler.TraceAnnotation`s (`bench.*`) sit beside the runtime's own
events. All planes share one clock, nanoseconds from the trace's start.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)
CONTAINERS = ("while", "conditional", "call")
# The program names its phases and spans in lowercase segments joined by
# dots (`serve.step.fetch`); the runtime's own host events are not so named.
PHASE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")
Interval = Tuple[int, int]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        elif e > s:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of the merged intervals `a` that no interval of the
    merged `b` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def parse_instruction(text: str) -> Tuple[str, str, str]:
    """An `XLA Ops` event is named by its whole HLO instruction,
    `%fusion.7 = bf16[8,1024]{1,0} fusion(bf16[...] %p), kind=kLoop`:
    → (instruction name without its number, first result type, opcode).
    Anything else (a host event, a step) comes back as (text, "", "")."""
    m = re.match(r"^%?([^ =]+) = ", text)
    if not m:
        return text, "", ""
    rest = text[m.end():]
    if rest.startswith("("):               # a tuple of results
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result, rest = rest[1:i], rest[i + 1:].lstrip()
    else:
        result, _, rest = rest.partition(" ")
    first = re.match(r"[a-z]+\d*\[[\d,]*\]", result)
    opcode = re.match(r"[A-Za-z][\w-]*", rest)
    return (re.sub(r"\.\d+$", "", m.group(1)),
            first.group(0) if first else "",
            opcode.group(0) if opcode else "")


def stable_name(text: str) -> str:
    """An operation's name without what a recompile renumbers: the
    instruction's name stem and its first result's type and shape, as in
    `copy_bf16_36_1281_16_20_64_`."""
    stem, result, _ = parse_instruction(text)
    return re.sub(r"[^A-Za-z0-9.-]+", "_", f"{stem}_{result}" if result
                  else stem)[:120]


def is_kernel(text: str) -> bool:
    """A Pallas kernel's call: the opcode, never an operand's name."""
    stem, _, opcode = parse_instruction(text)
    return opcode == "custom-call" or "custom_call" in stem \
        or "custom-call" in stem


def is_collective(text: str) -> bool:
    stem, _, opcode = parse_instruction(text)
    return bool(COLLECTIVE.search(opcode) or COLLECTIVE.search(stem))


def _stats(event) -> Dict[str, Any]:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def find_xplane(directory: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e) for e in line.events]


def reduce(profile) -> Optional[Dict[str, Any]]:
    """The whole reduction. Returns None when no device operation was
    traced — a reader then returns nothing, never a 0."""
    devices: Dict[int, List[Tuple[int, int, str]]] = {}
    host: List[Tuple[int, int, str, int]] = []   # start, end, name, thread
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for s, e, ev in _events(line):
                    ops.append((s, e, ev.name))
        elif plane.name.startswith("/host:"):
            for tid, line in enumerate(plane.lines):
                for s, e, ev in _events(line):
                    host.append((s, e, ev.name, tid))
    devices = {d: ops for d, ops in devices.items() if ops}
    if not devices:
        return None
    spans = [(s, e) for s, e, name, _ in host if name == WINDOW_SPAN]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        lo = min(s for ops in devices.values() for s, _, _ in ops)
        hi = max(e for ops in devices.values() for _, e, _ in ops)
    window_ns = hi - lo

    # The `XLA Ops` line is one core's serial stream: what runs there is
    # not hidden behind anything, so a collective's time on it (a
    # synchronous one, or the wait in an asynchronous one's `-done`) is
    # exposed time.
    busy_ns, by_name, kernels, coll_ns = [], {}, {}, []
    first = min(devices)
    for dev, ops in sorted(devices.items()):
        ops = [(max(s, lo), min(e, hi), text) for s, e, text in ops
               if min(e, hi) > max(s, lo)]
        busy = union((s, e) for s, e, _ in ops)
        busy_ns.append(total(busy))
        kinds: Dict[str, Tuple[Optional[str], bool]] = {}
        coll = 0
        for s, e, text in ops:
            if text not in kinds:
                kinds[text] = (stable_name(text) if is_kernel(text) else None,
                               is_collective(text))
            kernel, collective = kinds[text]
            if kernel is not None:
                kernels[kernel] = kernels.get(kernel, 0) + (e - s)
            coll += (e - s) if collective else 0
        coll_ns.append(coll)
        if dev == first:
            first_busy = busy
            # A loop's or a call's own event spans its body's: leave the
            # containers out of the table, or the body is counted twice.
            names = {text: stable_name(text) for text in kinds
                     if parse_instruction(text)[2] not in CONTAINERS}
            for s, e, text in ops:
                if text in names:
                    by_name[names[text]] = \
                        by_name.get(names[text], 0) + (e - s)

    gaps = subtract([(lo, hi)], first_busy)
    idle_by: Dict[str, int] = {}
    starts = sorted((s, e, name, tid) for s, e, name, tid in host
                    if name != WINDOW_SPAN)
    keys = [s for s, _, _, _ in starts]
    for s, e in gaps:
        idle_by_name = _host_span_at(starts, keys, s)
        idle_by[idle_by_name] = idle_by.get(idle_by_name, 0) + (e - s)

    def top(table):
        return [[k, v / 1e9] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:10]]

    n = len(busy_ns)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "chips": n,
        "kernel_s": sum(kernels.values()) / n / 1e9,
        "kernels": {k: v / n / 1e9 for k, v in kernels.items()},
        "collective_exposed_s": sum(coll_ns) / n / 1e9,
        "ops": {k: v / 1e9 for k, v in by_name.items()},
        "breakdown": {"device_ops": top(by_name), "idle_gaps": top(idle_by)},
    }


def _host_span_at(starts, keys, t: int) -> str:
    """What the host was doing at time t: the benchmark's own span open
    then, and inside it, on that same thread, the innermost of the
    program's phases (failing that, the innermost event of any kind: the
    runtime's own calls sit inside the phases and say less). With no span
    of the benchmark's open, the same choice over every thread."""
    i = bisect.bisect_right(keys, t)
    open_then = [(s, name, tid) for s, e, name, tid
                 in starts[max(0, i - 4000):i] if e > t]
    bench = max((ev for ev in open_then if ev[1].startswith("bench.")),
                default=None)
    inside = [ev for ev in open_then if not ev[1].startswith("bench.")
              and (bench is None or (ev[2] == bench[2] and ev[0] >= bench[0]))]
    inner = max((ev for ev in inside if PHASE.match(ev[1])),
                default=max(inside, default=None))
    where = "no_bench_span" if bench is None else bench[1]
    return where if inner is None else where + "___" + _clean(inner[1])


def _clean(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9.:-]+", "_", name)[:60]


def reduce_dir(directory: str) -> Optional[Dict[str, Any]]:
    path = find_xplane(directory)
    return reduce(load(path)) if path else None


def dump(path: str, per_line: int = 6) -> None:
    """What a trace holds, for a human: planes, lines, a few events."""
    for plane in load(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:per_line]:
                print("    ", ev.name, ev.start_ns, ev.duration_ns,
                      dict(list(_stats(ev).items())[:8]))


if __name__ == "__main__":
    dump(sys.argv[1])
