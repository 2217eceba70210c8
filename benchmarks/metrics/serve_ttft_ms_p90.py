"""The tail of time to first token; per-layer until a window holds the
requests a tail needs."""
from benchmarks.stats import field, percentile


def read(run):
    return percentile(field(run, "ttft_ms"), 90)
