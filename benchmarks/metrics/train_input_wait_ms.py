"""Mean of `fit`'s own input_wait_ms reports over the window: how long a
step waited for the prefetcher."""
from benchmarks.stats import mean


def read(run):
    return mean([r["input_wait_ms"] for r in run.get("reports", [])])
