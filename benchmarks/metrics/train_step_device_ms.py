"""Device-busy time per optimizer step over the traced steps (union of
the operations on a chip, mean over chips)."""
from benchmarks.stats import traced_steps


def read(run):
    trace, steps = run.get("trace"), traced_steps(run)
    if not trace or not steps:
        return None
    return trace["busy_s"] / steps * 1e3
