"""The host's own time in a decode step: mean over the window's
`serve.loop.step` phases of the step less its `serve.step.fetch` (the wait
for the sampled tokens)."""
from benchmarks.phases import less_child, serve_window
from benchmarks.stats import mean


def read(run):
    host = less_child(serve_window(run), "serve.loop.step", "serve.step.fetch")
    return mean([s * 1e3 for s in host]) if host else None
