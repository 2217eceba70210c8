"""The latent decode kernel's share of its roofline over the traced part
of the window: the floor of every decode call there (each live lane's
cached latents and rotary keys read once, the absorbed form's operations;
mla_work.py) times the layers, over the kernel's own device time in the
trace — its custom call's, by name: the expert layer's grouped matmuls run
in the same step."""
from benchmarks import kernel_work, mla_work
from benchmarks.stats import calls, work

# The custom call under its stable name (trace_reduce.stable_name): the
# program wraps the kernel in a jitted `mla_decode_attention`.
KERNEL = "mla_decode_attention"


def read(run):
    trace, traced, peak = run.get("trace"), run.get("traced"), run.get("peak")
    if not trace or not traced or not peak:
        return None
    seconds = sum(s for name, s in trace.get("kernels", {}).items()
                  if KERNEL in name)
    w = work(run)
    if not seconds or "mla_layers" not in w:
        return None
    floor = 0.0
    for c in calls(run, "decode", traced["t_open"], traced["t_close"]):
        # c[3] live lanes, c[4] their context tokens in all
        floor += kernel_work.floor_seconds(mla_work.mla_decode_work(
            c[3], c[4], w["mla_heads"], w["mla_rank"], w["mla_rope"]), peak)
    if floor <= 0:
        return None
    return 100.0 * floor * w["mla_layers"] / seconds
