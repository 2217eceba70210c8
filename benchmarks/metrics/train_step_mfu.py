"""The whole training step's share of the chip's peak: (6N + attention)
FLOPs per token times tokens/s/chip over the bf16 peak; recomputation is
not counted."""
from benchmarks import kernel_work
from benchmarks.stats import work


def read(run):
    if run["kind"] != "train_steps" or not run.get("peak"):
        return None
    w = work(run)
    per_token = kernel_work.train_flops_per_token(
        w["params_per_token"], w["attn_layers"],
        w["q_heads"] * w["head_dim"], run["seq_len"])
    rate = run["tokens"] / run["window_s"] / run["chips"]
    return 100.0 * per_token * rate / run["peak"]["bf16_flops_per_s"]
