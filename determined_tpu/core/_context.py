"""core.init() — one context bundling all training services.

Reference: harness/determined/core/_context.py:190-320. Two modes:

  - **managed**: launched by an agent; ClusterInfo comes from DET_* env, a
    Session talks to the master, preemption/searcher/metrics are live.
  - **local**: no master; metrics accumulate in-memory, the searcher yields a
    single op of `max_length`, checkpoints go to a local directory. The same
    user code runs in both (reference "train anywhere" semantics).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional

from determined_tpu._info import ClusterInfo, get_cluster_info
from determined_tpu.common.api import Session
from determined_tpu.common.trace import Tracer
from determined_tpu.core._checkpoint import CheckpointContext
from determined_tpu.core._distributed import DistributedContext
from determined_tpu.core._preempt import PreemptContext
from determined_tpu.core._profiler import ProfilerContext
from determined_tpu.core._searcher import SearcherContext
from determined_tpu.core._train import TrainContext
from determined_tpu.storage import from_config as storage_from_config

logger = logging.getLogger("determined_tpu.core")


class Context:
    def __init__(
        self,
        train: TrainContext,
        searcher: SearcherContext,
        checkpoint: CheckpointContext,
        preempt: PreemptContext,
        distributed: DistributedContext,
        profiler: ProfilerContext,
        info: Optional[ClusterInfo] = None,
        tracer: Optional[Tracer] = None,
    ):
        self.train = train
        self.searcher = searcher
        self.checkpoint = checkpoint
        self.preempt = preempt
        self.distributed = distributed
        self.profiler = profiler
        self.info = info
        # Lifecycle tracing (docs/observability.md): chief-only emitter,
        # buffered, flushed with metrics. Never None — local mode buffers
        # into tracer.local_spans so instrumented code needs no guards.
        self.tracer = tracer if tracer is not None else Tracer()

    @property
    def hparams(self) -> Dict[str, Any]:
        return self.info.trial.hparams if (self.info and self.info.trial) else {}

    @property
    def trial_seed(self) -> int:
        return self.info.trial.trial_seed if (self.info and self.info.trial) else 0

    @property
    def latest_checkpoint(self) -> Optional[str]:
        return self.info.trial.latest_checkpoint if (self.info and self.info.trial) else None

    def close(self) -> None:
        # Order matters (reference _context.py:79-118): drain checkpoint
        # writes first, final tensorboard sync, then stop watchers, then
        # tear down distributed. The tracer flushes after the checkpoint
        # drain so phase-2 commit spans make the final batch.
        self.checkpoint.close()
        self.tracer.close()
        if getattr(self.train, "_tb", None) is not None:
            self.train._tb.close()
        self.profiler.close()
        self.preempt.close()
        self.distributed.shutdown()

    def __enter__(self) -> "Context":
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        self.close()


def init(
    *,
    max_length: Optional[int] = None,
    storage_config: Optional[Dict[str, Any]] = None,
    checkpoint_dir: str = "/tmp/determined_tpu/checkpoints",
    distributed: Optional[DistributedContext] = None,
    async_checkpointing: bool = True,
) -> Context:
    """Bring up the Core API. Managed vs local is auto-detected from env."""
    try:
        from determined_tpu.compile.runtime import enable_compilation_cache

        enable_compilation_cache()
    except ImportError:
        # A task environment without jax/numpy (torch or plain-Python
        # trials under their own venv): there is no XLA compile to cache.
        logger.debug("jax not importable; no compilation cache")
    info = get_cluster_info()

    if distributed is None:
        if info and info.rendezvous and info.rendezvous.num_hosts > 1:
            distributed = DistributedContext.from_allocation(
                coordinator_addr=info.rendezvous.coordinator_addr
                or info.rendezvous.container_addrs[0] + ":8476",
                num_processes=info.rendezvous.num_hosts,
                process_id=info.rendezvous.container_rank,
            )
        else:
            distributed = DistributedContext.local()

    session: Optional[Session] = None
    trial_id, run_id, allocation_id = 0, 0, None
    if info is not None:
        # Every state-mutating call from this context carries the fencing
        # epoch the master minted for THIS allocation run: after a
        # partition-driven reassignment bumps the run, a zombie of the old
        # run gets a 409 instead of corrupting the successor's lineage
        # (docs/cluster-ops.md "Leases, fencing & split-brain").
        fence_headers = (
            {"X-Allocation-Epoch": str(info.allocation_epoch)}
            if info.allocation_epoch is not None
            else None
        )
        session = Session(info.master_url, info.session_token,
                          headers=fence_headers)
        allocation_id = info.allocation_id
        if info.trial is not None:
            trial_id = info.trial.trial_id
            run_id = info.trial.run_id
        if info.trial and info.trial.config.get("checkpoint_storage"):
            storage_config = storage_config or info.trial.config["checkpoint_storage"]

    storage = storage_from_config(storage_config, default_base=checkpoint_dir)

    # Per-trial tfevents written locally + synced into checkpoint storage
    # (reference tensorboard/base.py async upload thread); chief only.
    tb_manager = None
    if info is not None and info.trial is not None and (
        distributed is None or distributed.is_chief
    ):
        from determined_tpu.tensorboard import TensorboardManager

        try:
            tb_manager = TensorboardManager(
                storage, info.trial.experiment_id, info.trial.trial_id
            )
        except Exception:
            logger.debug("tensorboard manager unavailable", exc_info=True)

    train = TrainContext(
        session,
        trial_id=trial_id,
        run_id=run_id,
        distributed=distributed,
        tensorboard_manager=tb_manager,
    )
    searcher = SearcherContext(
        session,
        trial_id=trial_id,
        distributed=distributed,
        local_max_length=max_length,
    )
    checkpoint = CheckpointContext(
        session,
        storage,
        trial_id=trial_id,
        allocation_id=allocation_id,
        distributed=distributed,
        async_save=async_checkpointing,
    )
    preempt = PreemptContext(session, allocation_id=allocation_id, distributed=distributed)
    profiler = ProfilerContext(train)
    # Span emitter: chief-only (non-chief ranks would duplicate every
    # phase span), trace id from DET_TRACE_ID (minted by the master at
    # trial submit; local mode mints its own so the same instrumentation
    # is inspectable without a cluster).
    is_chief = distributed is None or distributed.is_chief
    tracer = Tracer(
        session if is_chief else None,
        trial_id=trial_id,
        enabled=None if is_chief else False,
    )
    checkpoint.tracer = tracer  # phase-1/phase-2 commit spans
    ctx = Context(train, searcher, checkpoint, preempt, distributed,
                  profiler, info, tracer=tracer)
    if session is not None:
        try:
            session.post(f"/api/v1/trials/{trial_id}/run_prepare", body={})
        except Exception:
            logger.debug("run_prepare failed", exc_info=True)
    return ctx
