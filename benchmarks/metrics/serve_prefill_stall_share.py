"""Share of the window in which live lanes stood still while `_admit`
prefilled other requests: the time inside the batcher's `serve.loop.admit`
phases during which at least one lane was live — all of a pass that was
entered with one, and of a pass that found none (every reply of a
lock-step round came in the same step) the part after its first request
went live (`live_from`)."""
from benchmarks.phases import serve_window


def read(run):
    admits = [r for r in serve_window(run) if r["name"] == "serve.loop.admit"]
    if not admits or run["window_s"] <= 0:
        return None
    stalled = 0.0
    for r in admits:
        counts = r["counts"]
        since = r["start"] if counts["live_lanes"] >= 1 \
            else counts.get("live_from", r["end"])
        stalled += r["end"] - since
    return 100.0 * stalled / run["window_s"]
