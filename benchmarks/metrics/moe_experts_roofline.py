"""The expert layer's grouped matmuls' share of their roofline over the
traced part of the window: the floor of every decode AND prefill call
there (the touched experts' three matrices read once, the assignments'
rows, 6 d width operations an assignment; moe_work.py — it overstates a
decode call's floor by the experts that drew no token, ~2%) times the
expert layers, over the kernel's own device time in the trace, by name."""
from benchmarks import kernel_work, moe_work
from benchmarks.stats import calls, work

# The program wraps the kernel in a jitted `moe_grouped_matmul`.
KERNEL = "moe_grouped_matmul"


def read(run):
    trace, traced, peak = run.get("trace"), run.get("traced"), run.get("peak")
    if not trace or not traced or not peak:
        return None
    seconds = sum(s for name, s in trace.get("kernels", {}).items()
                  if KERNEL in name)
    w = work(run)
    if not seconds or "moe_layers" not in w:
        return None
    floor = 0.0
    for kind in ("decode", "prefill"):
        for c in calls(run, kind, traced["t_open"], traced["t_close"]):
            # c[3]: live lanes of a decode call, novel tokens of a prefill
            floor += kernel_work.floor_seconds(moe_work.moe_experts_work(
                c[3], w["moe_top_k"], w["moe_experts"], w["moe_d_model"],
                w["moe_width"]), peak)
    if floor <= 0:
        return None
    return 100.0 * floor * w["moe_layers"] / seconds
