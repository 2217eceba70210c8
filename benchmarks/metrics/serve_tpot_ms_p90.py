"""90th percentile, over every request the window finished, of
(finish - first token) / (tokens - 1), from the request's own stamps."""
from benchmarks.stats import field, percentile


def read(run):
    return percentile(field(run, "tpot_ms"), 90)
