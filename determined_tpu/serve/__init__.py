"""`det serve` — high-throughput inference serving from trained checkpoints.

The subsystem that takes the platform past the checkpoint (ROADMAP item 2):
a SERVING task type loads a COMPLETED, integrity-verified checkpoint,
AOT-compiles bucketed prefill + single-token decode executables, and runs
continuous token-level batching — sequences join at decode-step boundaries
and retire without draining the batch, behind a bounded admission queue.

Layout:
  model.py      KV-cached GPT-2 prefill/decode steps over the paged
                block pool (paged_prefill/paged_decode_step; shape-static,
                AOT)
  falcon_h1.py  a second family's steps: a Mamba-2 mixer beside grouped-
                query attention; a recurrent-state pool indexed by lane
                beside the paged pool (docs/serving.md "Families with
                recurrent state")
  kv_cache.py   KV block manager: paged admission accounting, refcounted
                prefix caching, copy-on-write
  engine.py     checkpoint loading + compiled executables + device state
                (the family's cache + block tables); `family_of` finds a
                family's steps
  scheduler.py  bounded admission queue + the continuous batcher
  http.py       HTTP front-end (generate/stats/health)
  task.py       cluster entrypoint (drain lifecycle, proxy registration)

The paged decode-attention kernel itself lives in
determined_tpu/ops/paged_attention.py (docs/serving.md "Paged KV &
prefix caching"), the recurrent-state kernel in ops/ssm_state.py.

Docs: docs/serving.md.
"""

from determined_tpu.serve.engine import ServingEngine, load_checkpoint_params
from determined_tpu.serve.kv_cache import BlockManager, KVBlockError
from determined_tpu.serve.scheduler import (
    AdmissionQueue,
    ContinuousBatcher,
    Draining,
    QueueFull,
    Request,
)

__all__ = [
    "AdmissionQueue",
    "BlockManager",
    "ContinuousBatcher",
    "Draining",
    "KVBlockError",
    "QueueFull",
    "Request",
    "ServingEngine",
    "load_checkpoint_params",
]
