"""The flash kernels' share of their roofline over the traced steps: the
floor of one forward and one backward per layer on a chip's rows
(kernel_work.py; the remat's second forward is not required work) over
the custom calls' device time."""
from benchmarks import kernel_work
from benchmarks.stats import dims, traced_steps


def read(run):
    trace, steps, peak = run.get("trace"), traced_steps(run), run.get("peak")
    if not trace or not steps or not peak or not trace["kernel_s"]:
        return None
    d = dims(run)
    work = kernel_work.flash_attention_work(
        run["batch"] // run["chips"], run["seq_len"], d["n_head"],
        d["d_model"] // d["n_head"])
    floor = kernel_work.floor_seconds(work, peak) * d["n_layer"] * steps
    return 100.0 * floor / trace["kernel_s"]
