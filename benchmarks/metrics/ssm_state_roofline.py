"""The state kernel's share of its roofline over the traced part of the
window: the floor of every decode call there (each live lane's recurrent
state read once and written once, bandwidth-bound; ssm_work.py) times the
layers that keep a state, over the kernel's own device time in the trace
— its custom call's, by name, not every custom call's: the paged decode
kernel runs in the same step."""
from benchmarks import kernel_work, ssm_work
from benchmarks.stats import calls, work

# The state update's custom call under its stable name
# (trace_reduce.stable_name): the instruction's stem, then its first
# result's type. The program names the call `ssm_state_update`.
KERNEL = "ssm_state_update"


def read(run):
    trace, traced, peak = run.get("trace"), run.get("traced"), run.get("peak")
    if not trace or not traced or not peak:
        return None
    seconds = sum(s for name, s in trace.get("kernels", {}).items()
                  if KERNEL in name)
    w = work(run)
    if not seconds or "ssm_layers" not in w:
        return None
    floor = 0.0
    for c in calls(run, "decode", traced["t_open"], traced["t_close"]):
        floor += kernel_work.floor_seconds(ssm_work.ssm_decode_work(
            c[3], w["ssm_heads"], w["ssm_head_dim"], w["ssm_state"],
            w["ssm_groups"], w["ssm_state_itemsize"]), peak)
    if floor <= 0:
        return None
    return 100.0 * floor * w["ssm_layers"] / seconds
