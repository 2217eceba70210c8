"""The flash kernels' share of their roofline over the traced steps: the
floor of one forward and one backward per layer on a chip's rows
(kernel_work.py; the remat's second forward is not required work) over
the custom calls' device time."""
from benchmarks import kernel_work
from benchmarks.stats import traced_steps, work


def read(run):
    trace, steps, peak = run.get("trace"), traced_steps(run), run.get("peak")
    if not trace or not steps or not peak or not trace["kernel_s"]:
        return None
    w = work(run)
    step = kernel_work.flash_attention_work(
        run["batch"] // run["chips"], run["seq_len"], w["q_heads"],
        w["head_dim"], kv_heads=w["kv_heads"])
    floor = kernel_work.floor_seconds(step, peak) * w["attn_layers"] * steps
    return 100.0 * floor / trace["kernel_s"]
