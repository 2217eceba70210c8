"""The decode kernel's share of its roofline over the traced part of the
window: the floor of every decode call there (K and V of each live lane's
context read once, bandwidth-bound; kernel_work.py) times the layers,
over the kernel's device time in the trace."""
from benchmarks import kernel_work
from benchmarks.stats import calls, work


def read(run):
    trace, traced, peak = run.get("trace"), run.get("traced"), run.get("peak")
    if not trace or not traced or not peak or not trace["kernel_s"]:
        return None
    w = work(run)
    heads, head_dim = w["q_heads"], w["head_dim"]
    floor = 0.0
    for c in calls(run, "decode", traced["t_open"], traced["t_close"]):
        # c[3] live lanes, c[4] their context tokens in all: the work is
        # linear in both, so one call with the totals is the sum of lanes.
        call = kernel_work.paged_decode_work([c[4]], heads, head_dim,
                                             kv_heads=w["kv_heads"])
        call["bytes"] += 2.0 * (c[3] - 1) * heads * head_dim * 2
        floor += kernel_work.floor_seconds(call, peak)
    if floor <= 0:
        return None
    return 100.0 * floor * w["attn_layers"] / trace["kernel_s"]
