"""The whole training step's share of the chip's peak: (6N + attention)
FLOPs per token times tokens/s/chip over the bf16 peak; recomputation is
not counted."""
from benchmarks import kernel_work
from benchmarks.reference import param_count
from benchmarks.stats import dims


def read(run):
    if run["kind"] != "train_steps" or not run.get("peak"):
        return None
    d = dims(run)
    per_token = kernel_work.train_flops_per_token(
        param_count(d), d["n_layer"], d["d_model"], run["seq_len"])
    rate = run["tokens"] / run["window_s"] / run["chips"]
    return 100.0 * per_token * rate / run["peak"]["bf16_flops_per_s"]
