"""The fit loop's serial time on the host, per step: mean over the
window's `harness.step` phases of the step less its `harness.flush.fetch`
(the wait for the device). The cells report every step, so the loop is
synchronous and this is all the host adds to a step."""
from benchmarks.phases import less_child, train_window
from benchmarks.stats import mean


def read(run):
    host = less_child(train_window(run), "harness.step", "harness.flush.fetch")
    return mean([s * 1e3 for s in host]) if host else None
