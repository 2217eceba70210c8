"""Mean, over every request the window finished, of submit to first
token (queue wait and prefill), from the request's own stamps."""
from benchmarks.stats import field, mean


def read(run):
    return mean(field(run, "ttft_ms"))
