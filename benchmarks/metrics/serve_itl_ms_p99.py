"""99th percentile of the gap between two tokens of one request, over
every gap the window's decode steps closed (`gaps_ms` of the batcher's
`serve.loop.step` phases): the tail a streaming client sees, a token that
waited behind other requests' prefills included."""
from benchmarks.phases import serve_window
from benchmarks.stats import percentile


def read(run):
    gaps = [g for r in serve_window(run) if r["name"] == "serve.loop.step"
            for g in r["counts"]["gaps_ms"]]
    return percentile(gaps, 99)
