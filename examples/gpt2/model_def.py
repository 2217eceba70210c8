"""GPT-2 pretraining trial — the flagship distributed workload.

The JaxTrial equivalent of the reference's HF-Trainer GPT-2 path (reference:
examples/hf_trainer_api/hf_language_modeling/run_clm.py), re-designed for
TPU: bf16 + pallas flash attention, GSPMD sharding over the allocation's
mesh (dp/fsdp/tp from the `mesh` hparam block), remat, multi-step dispatch
via the Trainer.

Data: streams deterministic synthetic token sequences by default so the
example runs air-gapped; point `tokens_path` at a memory-mapped token file
(np.memmap int32, produced by any tokenizer) for real pretraining.
"""

import logging
import os

import numpy as np

from determined_tpu import core
from determined_tpu.models import gpt2
from determined_tpu.train import JaxTrial, Trainer
from determined_tpu.train.trial import TrialContext


class GPT2Trial(JaxTrial):
    def __init__(self, context: TrialContext):
        super().__init__(context)
        size = context.hparams.get("model_size", "small")
        base = {
            "tiny": gpt2.Config.tiny,
            "small": gpt2.Config.small,
            "medium": gpt2.Config.medium,
            "large": gpt2.Config.large,
        }[size]()
        seq_len = int(context.hparams.get("seq_len", 1024))
        # `optimizations:` config block (validated by expconf; see
        # docs/training-perf.md). The block wins over the legacy
        # attention_impl hparam so platform-level A/Bs need no trial edit.
        opt = context.optimizations
        self.cfg = gpt2.Config(
            vocab_size=base.vocab_size,
            # Long-context runs (long_context.yaml) train past the preset's
            # position-table size: widen wpe to the configured sequence.
            n_positions=max(base.n_positions, seq_len),
            d_model=base.d_model,
            n_layer=base.n_layer,
            n_head=base.n_head,
            remat=bool(context.hparams.get("remat", True)),
            attention_impl=opt.get(
                "attention_impl",
                context.hparams.get("attention_impl", "flash")),
            attention_bf16=bool(opt.get("attention_bf16", False)),
            overlap_allgather=bool(opt.get("overlap_allgather", False)),
            scan_unroll=int(context.hparams.get("scan_unroll", 0)),
            # MoE: num_experts > 1 routes every block's FFN over the mesh
            # `expert` axis (ops/moe.py).
            num_experts=int(context.hparams.get("num_experts", 1)),
            moe_top_k=int(context.hparams.get("moe_top_k", 2)),
        )
        self.seq_len = seq_len
        path = context.hparams.get("tokens_path") or os.environ.get("GPT2_TOKENS")
        self.tokens = None
        if path and os.path.exists(path):
            self.tokens = np.memmap(path, dtype=np.int32, mode="r")

    def init_params(self, rng):
        return gpt2.init(rng, self.cfg)

    def flops_per_step(self):
        # fwd+bwd FLOPs per optimizer step → profiler device_flops_util
        return (gpt2.flops_per_token(self.cfg, self.seq_len)
                * self.context.global_batch_size * self.seq_len)

    def loss(self, params, batch, rng):
        return gpt2.loss_fn(params, batch, self.cfg, self.sharding_rules())

    def supports_expert_parallel(self):
        # Only a MoE config routes tokens over the expert axis; declaring
        # support unconditionally would re-open the decoy-axis trap.
        return self.cfg.num_experts > 1

    def loss_pipelined(self, params, batch, rng, mesh):
        # Selected by the Trainer whenever the config mesh has pipeline > 1
        # (GPipe over the `pipeline` axis, parallel/pipeline.py).
        return gpt2.loss_fn_pipelined(
            params, batch, self.cfg, mesh, self.sharding_rules()
        )

    def param_logical_axes(self):
        return gpt2.param_logical_axes(self.cfg)

    def optimizer(self):
        import optax

        lr = float(self.context.get_hparam("learning_rate", 3e-4))
        warmup = int(self.context.hparams.get("warmup_steps", 100))
        sched = optax.warmup_cosine_decay_schedule(
            0.0, lr, warmup, int(self.context.hparams.get("decay_steps", 10000))
        )
        return optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(sched, b2=0.95,
                        weight_decay=float(self.context.hparams.get(
                            "weight_decay", 0.1))),
        )

    def build_training_data(self):
        b, s = self.context.global_batch_size, self.seq_len
        rng = np.random.default_rng(0)
        if self.tokens is not None:
            n = len(self.tokens) - (s + 1)
            while True:
                starts = rng.integers(0, n, b)
                yield {"tokens": np.stack(
                    [self.tokens[i : i + s + 1] for i in starts])}
        else:
            while True:
                yield {"tokens": rng.integers(
                    0, self.cfg.vocab_size, size=(b, s + 1)).astype(np.int32)}

    def build_validation_data(self):
        b, s = self.context.global_batch_size, self.seq_len
        rng = np.random.default_rng(7)
        for _ in range(4):
            yield {"tokens": rng.integers(
                0, self.cfg.vocab_size, size=(b, s + 1)).astype(np.int32)}

    def evaluate(self, params, batch):
        loss = gpt2.loss_fn(params, batch, self.cfg, self.sharding_rules())
        return {"validation_loss": loss}

    def evaluate_pipelined(self, params, batch, mesh):
        loss = gpt2.loss_fn_pipelined(
            params, batch, self.cfg, mesh, self.sharding_rules()
        )
        return {"validation_loss": loss}


if __name__ == "__main__":
    # The Trainer says at INFO which devices and attention path it got.
    logging.basicConfig(format="%(name)s: %(message)s")
    logging.getLogger("determined_tpu").setLevel(logging.INFO)
    with core.init() as ctx:
        trial = GPT2Trial(
            TrialContext(hparams=ctx.hparams, core_context=ctx,
                         n_devices=ctx.distributed.size)
        )
        Trainer(trial, core_context=ctx).fit(report_period=10)
