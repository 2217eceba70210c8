"""Collective time per step during which no other operation ran on the
chip (mean over chips), over the traced steps."""
from benchmarks.stats import traced_steps


def read(run):
    trace, steps = run.get("trace"), traced_steps(run)
    if not trace or not steps or run.get("chips", 1) < 2:
        return None
    return trace["collective_exposed_s"] / steps * 1e3
