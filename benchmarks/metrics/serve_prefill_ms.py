"""Mean prefill_end - prefill_start of the requests the window finished
(copy-on-write, the bucket's prefill call, the first sample)."""
from benchmarks.stats import field, mean


def read(run):
    return mean(field(run, "prefill_ms"))
