"""`det serve` task entrypoint — one serve replica.

Launched by the master as a SERVING task (`python3 -m
determined_tpu.serve.task`; config travels in DET_SERVING_CONFIG), or
locally via `det serve <config> --local`. Lifecycle:

  1. build the model config (`serving.model` / `serving.model_config`),
  2. load + integrity-verify a COMPLETED checkpoint (engine.py),
  3. AOT-compile prefill buckets + decode, start the batcher + HTTP
     front-end, report the proxy address to the master,
  4. long-poll the allocation preemption signal (the same channel trials
     use, core/_preempt.py): on a drain — spot notice, maintenance,
     scheduler preemption — stop admitting, finish every accepted
     request inside the grace window, and exit 0 so the master
     reschedules the replica on surviving capacity
     (docs/cluster-ops.md "Preemption & drain lifecycle").

SIGTERM gets the same drain treatment, so `det deploy local down` and
plain kills are graceful too.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import socket
import sys
import threading
import time
from typing import Any, Dict, Optional

logger = logging.getLogger("determined_tpu.serve")

DRAIN_SAFETY_MARGIN_S = 2.0
HEARTBEAT_PERIOD_S = 2.0


class ReplicaHeartbeat:
    """Pushes the replica's load report (queue depth, occupancy, KV
    blocks, drain state) to the master on a fixed period — the router's
    least-loaded signal and the deployment autoscaler's input
    (docs/serving.md "Deployments & autoscaling"). Loss-tolerant: a
    failed POST is logged and the next beat retries; the master treats
    stale reports as "no signal", never as "dead"."""

    def __init__(self, session, allocation_id: str, batcher,
                 period_s: float = HEARTBEAT_PERIOD_S):
        self._session = session
        self._allocation_id = allocation_id
        self._batcher = batcher
        self._period = max(0.2, float(period_s))
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self) -> None:
        """One synchronous report. Called by the loop, and directly at
        drain start (the drain handshake: the master must see
        draining=true before the grace window burns down, so the router
        ejects the replica immediately rather than at the next period)."""
        if self._session is None or not self._allocation_id:
            return
        try:
            stats = self._batcher.heartbeat_stats()
            # Model-lifecycle confirmation (docs/serving.md "Model
            # lifecycle"): echo the version label the master pinned at
            # spawn — the deployment detail shows what each replica
            # ACTUALLY serves, not only what the controller intended.
            mv = os.environ.get("DET_MODEL_VERSION")
            if mv:
                stats["model_version"] = mv
            adapters = getattr(self._batcher.engine, "adapter_names", None)
            if adapters:
                stats["adapters"] = list(adapters)
            self._session.post(
                f"/api/v1/allocations/{self._allocation_id}/serve_stats",
                body=stats)
        except Exception:
            logger.debug("serve_stats heartbeat failed", exc_info=True)

    def _run(self) -> None:
        while not self._stop_evt.wait(self._period):
            self.beat()

    def start(self) -> "ReplicaHeartbeat":
        if self._session is None or not self._allocation_id:
            return self  # local/masterless mode: nothing to report to
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="serve-heartbeat")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


def build_model(serving: Dict[str, Any]):
    """serving.model/model_config → the family's models/* Config, made by
    the family's own `config_from`; `gpt2` where none is named."""
    from determined_tpu.serve.engine import family_module

    return family_module(serving.get("model", "gpt2")).config_from(
        dict(serving.get("model_config") or {}))


def serving_signature(serving: Dict[str, Any],
                      params: Optional[Dict[str, Any]] = None,
                      cache: Optional[Dict[str, Any]] = None) -> str:
    """Compile-farm signature for a serving config: every shape-affecting
    knob (model geometry, slots, buckets, paged-KV layout) plus the
    runtime tag, so two replicas of the same deployment — or a respawn
    after scale-to-zero — address the same AOT artifacts, and a config
    change can never load a stale executable. `params` is the engine's
    resident tree: the executables take its leaves as arguments, so its
    dtypes and shapes (a float32 or a bfloat16 checkpoint, and what the
    engine narrowed at load) are part of the key; `cache` is the engine's
    `cache_avals()`, every pool the executables carry."""
    import hashlib

    from determined_tpu.compile.signature import runtime_tag

    shape_keys = ("model", "model_config", "max_batch_size", "max_seq_len",
                  "kv_block_size", "kv_num_blocks", "prefill_buckets",
                  "attention_impl", "seed", "adapters")
    key = {k: serving.get(k) for k in shape_keys}
    key["runtime_tag"] = runtime_tag()
    if cache is not None:
        # every pool the executables carry: a family with recurrent state
        # has a state pool beside the paged one, shaped by the lanes
        key["cache_avals"] = {name: f"{x.dtype}{list(x.shape)}"
                              for name, x in sorted(cache.items())}
    if params is not None:
        import jax

        key["resident_avals"] = jax.tree_util.tree_map(
            lambda x: f"{x.dtype}{list(x.shape)}", params)
    blob = json.dumps(key, sort_keys=True, default=str).encode()
    return "serve-" + hashlib.sha256(blob).hexdigest()[:32]


def _trial_id_for(serving: Dict[str, Any]) -> int:
    from determined_tpu.core._checkpoint import _STATE_ID_RE

    ckpt = str(serving.get("checkpoint", "latest"))
    m = _STATE_ID_RE.match(ckpt)
    if m:
        return int(m.group(1))
    return int(serving.get("trial_id", 0))


def build_replica(config: Dict[str, Any], session=None):
    """Config → (engine, batcher). Shared by the cluster task, the local
    CLI mode, tests, and the bench."""
    from determined_tpu.core._checkpoint import CheckpointContext
    from determined_tpu.serve.engine import (
        ServingEngine, family_of, load_checkpoint_params)
    from determined_tpu.serve.kv_cache import BlockManager
    from determined_tpu.serve.scheduler import (
        AdmissionQueue, ContinuousBatcher)
    from determined_tpu.storage import from_config

    serving = config.get("serving") or {}
    cfg = build_model(serving)
    prefix_cache = bool(serving.get("prefix_cache", True))
    if family_of(cfg).RECURRENT_STATE:
        # A recurrent state is the whole prefix folded into one tensor a
        # lane: it cannot be rebuilt from shared prefix blocks.
        if "prefix_cache" not in serving:
            prefix_cache = False
        elif prefix_cache:
            raise ValueError(
                "serving.prefix_cache: true — a family with recurrent "
                "state cannot share prefix blocks (the state after a "
                "prefix is not in them); set prefix_cache: false")
    storage = from_config(config.get("checkpoint_storage"))
    ckpt_ctx = CheckpointContext(
        session, storage, trial_id=_trial_id_for(serving), async_save=False)
    params = load_checkpoint_params(
        ckpt_ctx, str(serving.get("checkpoint", "latest")))

    # Multi-adapter replicas (docs/serving.md "Model lifecycle"): each
    # serving.adapters entry restores a head-tuned fine-tune through the
    # same verified-COMPLETED path as the base, then lives as one table
    # in the engine's adapter stack — per-request `model:` names select
    # it. Adapter checkpoints may come from other trials; each resolves
    # its own lineage scope from its checkpoint id.
    adapters = {}
    for a in serving.get("adapters") or []:
        a_ckpt = str(a["checkpoint"])
        from determined_tpu.core._checkpoint import _STATE_ID_RE

        m = _STATE_ID_RE.match(a_ckpt)
        a_ctx = CheckpointContext(
            session, storage,
            trial_id=int(m.group(1)) if m else _trial_id_for(serving),
            async_save=False)
        adapters[str(a["name"])] = load_checkpoint_params(a_ctx, a_ckpt)

    slots = int(serving.get("max_batch_size", 8))
    max_seq = int(serving.get(
        "max_seq_len", min(family_of(cfg).position_limit(cfg) or 1024, 1024)))
    block_size = int(serving.get("kv_block_size", 16))
    num_blocks = serving.get("kv_num_blocks")
    engine = ServingEngine(
        params, cfg,
        slots=slots,
        max_seq_len=max_seq,
        prefill_buckets=serving.get("prefill_buckets"),
        seed=int(serving.get("seed", 0)),
        attention_impl=str(serving.get("attention_impl", "auto")),
        kv_block_size=block_size,
        kv_num_blocks=int(num_blocks) if num_blocks else None,
        adapters=adapters or None,
    )
    # Warm AOT (docs/serving.md "Scale to zero"): scope a compile-farm
    # client to this config's serving signature so compile() deserializes
    # executables from the node-local AOT dir / master artifact store and
    # saves fresh compiles back. Opt out with serving.warm_aot: false.
    if serving.get("warm_aot", True):
        from determined_tpu.compile.runtime import FarmClient

        engine.farm = FarmClient(
            session=session,
            signature=serving_signature(serving, engine.params,
                                        engine.cache_avals()))
    # The device pool IS the budget: the manager mirrors it exactly.
    blocks = BlockManager(
        num_blocks=engine.num_blocks, block_size=engine.block_size,
        prefix_cache=prefix_cache)
    queue = AdmissionQueue(maxsize=int(serving.get("queue_depth", 64)))
    batcher = ContinuousBatcher(engine, queue=queue, block_manager=blocks)
    return engine, batcher


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO, format="%(name)s: %(message)s")
    argv = list(sys.argv[1:] if argv is None else argv)

    raw = os.environ.get("DET_SERVING_CONFIG")
    if raw is None and argv:
        with open(argv[0]) as f:  # local mode: config file on the cli
            raw = f.read()
    if not raw:
        print("no serving config (DET_SERVING_CONFIG or a config path)",
              file=sys.stderr)
        return 1
    config = json.loads(raw) if raw.lstrip().startswith("{") else __import__(
        "yaml").safe_load(raw)

    master = os.environ.get("DET_MASTER")
    allocation_id = os.environ.get("DET_ALLOCATION_ID")
    session = None
    if master and allocation_id:
        from determined_tpu.common.api import Session

        session = Session(master, os.environ.get("DET_SESSION_TOKEN"))

    from determined_tpu.compile.runtime import enable_compilation_cache

    # Before the first compile: a respawned replica whose AOT artifacts
    # are gone still finds its executables in the persistent cache.
    enable_compilation_cache()
    engine, batcher = build_replica(config, session=session)

    # Per-request span tracing (docs/observability.md "Request spans"):
    # retire-time span trees batch-POST to the master's request_spans
    # store; errors/SLO breaches always traced, the rest at
    # serving.trace_sample. serving.trace_sample: 0 disables entirely.
    from determined_tpu.serve.tracing import RequestTracer

    serving_cfg = config.get("serving") or {}
    sample = float(serving_cfg.get("trace_sample", 1.0))
    tracer = None
    if sample > 0:
        tracer = RequestTracer(
            session, allocation_id or "", sample=sample,
            slo_ms=serving_cfg.get("slo_ms"))
        batcher.tracer = tracer
        tracer.start()

    batcher.start()  # compiles everything AOT before serving

    from determined_tpu.serve.http import ServingServer

    serving = config.get("serving") or {}
    server = ServingServer(batcher, port=int(serving.get("port", 0)))
    server.start()
    addr = f"http://{socket.gethostname()}:{server.port}"
    logger.info("serve replica up at %s (slots=%d, buckets=%s)",
                addr, engine.slots, engine.prefill_buckets)

    from determined_tpu.exec._util import report_proxy_address

    report_proxy_address(addr)
    if session is not None and allocation_id:
        try:
            session.post(f"/api/v1/allocations/{allocation_id}/ready")
        except Exception:
            logger.warning("ready report failed", exc_info=True)

    heartbeat = ReplicaHeartbeat(
        session, allocation_id or "", batcher,
        period_s=float(serving.get("heartbeat_period_s",
                                   HEARTBEAT_PERIOD_S)))
    heartbeat.start()

    # -- drain plumbing -------------------------------------------------
    from determined_tpu.core._preempt import PreemptContext

    preempt = PreemptContext(session, allocation_id)
    drain_requested = threading.Event()

    def _sigterm(signum, frame):
        logger.info("SIGTERM: draining")
        drain_requested.set()

    signal.signal(signal.SIGTERM, _sigterm)
    signal.signal(signal.SIGINT, _sigterm)

    stats_every = float(serving.get("stats_log_period_s", 30.0))
    last_stats = time.monotonic()
    try:
        while not drain_requested.is_set():
            if preempt.should_preempt():
                logger.info(
                    "preemption signal (%s): draining",
                    preempt.preemption_reason() or "unspecified")
                break
            if stats_every and time.monotonic() - last_stats >= stats_every:
                last_stats = time.monotonic()
                logger.info("stats: %s", json.dumps(batcher.stats()))
            time.sleep(0.5)

        # Drain: stop admitting (HTTP 503), finish accepted work inside
        # the grace window, then exit cleanly so the master reschedules.
        deadline = preempt.preemption_deadline()
        budget = (max(1.0, deadline - DRAIN_SAFETY_MARGIN_S)
                  if deadline is not None else 60.0)
        t0 = time.monotonic()
        batcher.queue.drain()
        # Drain handshake: report draining=true NOW so the deployment
        # router stops dispatching here immediately instead of waiting
        # out the heartbeat period (requests it already forwarded still
        # finish — that's the zero-dropped contract below).
        heartbeat.beat()
        finished = batcher.drain(timeout=budget)
        logger.info(
            "drain %s in %.2fs (budget %.1fs): %s",
            "complete" if finished else "TIMED OUT", time.monotonic() - t0,
            budget, json.dumps(batcher.stats()))
        # Clean exit either way — a blown budget means the node is about
        # to die; rescheduling beats burning the rest of the grace.
        return 0
    finally:
        heartbeat.stop()
        server.stop()
        batcher.stop()
        if tracer is not None:
            tracer.stop()  # final flush: drained requests keep traces
        preempt.close()


if __name__ == "__main__":
    sys.exit(main())
