"""Host-clock time inside `engine.decode` over the window, per step (each
call ends in the device-to-host copy of the sampled tokens)."""
from benchmarks.stats import calls


def read(run):
    steps = calls(run, "decode", run["t_open"], run["t_close"])
    if not steps:
        return None
    return sum(c[2] - c[1] for c in steps) / len(steps) * 1e3
