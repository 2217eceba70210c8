"""Median submit-to-admit wait: what a request spends behind the
batcher's step and other requests' prefills."""
from benchmarks.stats import field, percentile


def read(run):
    return percentile(field(run, "queue_ms"), 50)
