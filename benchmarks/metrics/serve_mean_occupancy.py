"""Live lanes per decode step over the window (what the batcher's
occupancy_sum / active_steps counts), from the benchmark's span around
every decode call."""


def read(run):
    if not run.get("active_steps"):
        return None
    return run["occupancy_sum"] / run["active_steps"]
