"""The whole serving step's share of the chip's peak: 2 FLOPs per
parameter a token's forward multiplies, for every token the engine
processed in the window (novel prompt tokens prefilled + live lanes
decoded), over window and peak."""
from benchmarks.stats import calls, work


def read(run):
    if run["kind"] != "closed_loop" or not run.get("peak"):
        return None
    lo, hi = run["t_open"], run["t_close"]
    tokens = sum(c[3] for c in calls(run, "decode", lo, hi)) \
        + sum(c[3] for c in calls(run, "prefill", lo, hi))
    flops = 2.0 * work(run)["params_per_token"] * tokens
    return 100.0 * flops / run["window_s"] / run["peak"]["bf16_flops_per_s"]
