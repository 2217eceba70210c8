"""Async input pipeline: device-prefetch, double-buffered batches.

The trainer's hot loop is fully async on the device side (one jit per
trial, batched metric fetches), but a synchronous `next(data_iter)` puts
host preprocessing + the H2D copy on the step critical path — exactly the
tf.data prefetch-to-device problem (Murray et al.) and the Pathways rule
that the host must always run ahead of the accelerator.

`DevicePrefetcher` wraps any trial's `build_training_data()` /
`build_validation_data()` iterable:

  - a background thread pulls host batches into a bounded queue
    (configurable depth; default 2 = double buffering),
  - each batch is sharded with the mesh's batch `NamedSharding` via
    `jax.device_put` and blocked-until-ready *in the producer thread*, so
    the batch is resident on HBM — the H2D copy overlaps the previous
    step's compute instead of serializing with it,
  - multi-host processes go through
    `jax.make_array_from_process_local_data` so each host transfers
    only its local shard,
  - iterator exceptions are re-raised in the consumer (after any batches
    queued before the failure — order preserved), and `close()` tears the
    thread down deterministically on preemption / op boundaries,
  - per-step `input_wait_ms` / `h2d_ms` / queue-depth gauges accumulate in
    a window the Trainer drains at report boundaries, so an input-bound
    trial is visible in metrics instead of masquerading as slow TPU time.

Chaos: the producer honors the `data.prefetch.queue` fault point
(`DET_FAULTS=data.prefetch.queue:error` etc. — docs/chaos.md).
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from typing import Any, Dict, Iterable, Iterator, Optional, Tuple

from determined_tpu.common import faultpoint

logger = logging.getLogger("determined_tpu.data")

FAULT_POINT_QUEUE = "data.prefetch.queue"

_SENTINEL = object()


@dataclasses.dataclass
class PrefetchConfig:
    """Resolved prefetch knobs (trial attribute over expconf block).

    expconf block (validated by `expconf.validate`)::

        prefetch:
          enabled: true     # opt-out switch; prefetch is ON by default
          depth: 2          # queue depth; 2 = double buffering
          shard: true       # device_put with the mesh batch sharding

    A trial can override per-trial with `prefetch = False` (opt out) or
    `prefetch = {"depth": 4}` (see JaxTrial.prefetch).
    """

    enabled: bool = True
    depth: int = 2
    shard: bool = True

    @classmethod
    def from_block(cls, block: Any) -> "PrefetchConfig":
        if block is None:
            return cls()
        if isinstance(block, bool):
            return cls(enabled=block)
        if isinstance(block, dict):
            return cls(
                enabled=bool(block.get("enabled", True)),
                depth=max(1, int(block.get("depth", 2))),
                shard=bool(block.get("shard", True)),
            )
        raise TypeError(f"prefetch config must be a bool or mapping, got "
                        f"{type(block).__name__}")

    @classmethod
    def resolve(cls, trial: Any = None,
                expconf: Optional[Dict[str, Any]] = None) -> "PrefetchConfig":
        """Trial attribute wins over the experiment config block; both
        default to enabled (the opt-*out* contract)."""
        trial_attr = getattr(trial, "prefetch", None)
        if trial_attr is not None:
            return cls.from_block(trial_attr)
        if isinstance(expconf, dict) and expconf.get("prefetch") is not None:
            return cls.from_block(expconf.get("prefetch"))
        return cls()


def shard_batch(batch: Any, sharding) -> Any:
    """Device-put a host batch with the mesh's batch sharding.

    `sharding` is either a single `Sharding` applied to every leaf or a
    pytree of per-leaf shardings (the jitted step's exact input
    `NamedSharding`s — `train.step.step_input_shardings` — so batches
    arrive already in the step's declared in_shardings and XLA inserts no
    resharding copy on the hot path).

    Single-process: one `jax.device_put` over the whole pytree (non-blocking
    dispatch). Multi-host: per-leaf `make_array_from_process_local_data`, so
    each process transfers only its local shard of the global batch.
    """
    import jax
    import numpy as np
    from jax.sharding import Sharding

    if jax.process_count() > 1:
        if isinstance(sharding, Sharding):
            return jax.tree_util.tree_map(
                lambda x: jax.make_array_from_process_local_data(
                    sharding, np.asarray(x)),
                batch,
            )
        return jax.tree_util.tree_map(
            lambda x, s: jax.make_array_from_process_local_data(
                s, np.asarray(x)),
            batch, sharding,
        )
    return jax.device_put(batch, sharding)


class DevicePrefetcher:
    """Iterator: background thread stages device-resident batches.

    Wraps `iterable` (consumed exactly once, in order). When `sharding` is
    given, batches are device_put with it and blocked-until-ready in the
    producer thread before queuing — handing the consumer arrays already on
    HBM. Finite iterables raise StopIteration in the consumer when
    exhausted; producer exceptions re-raise in the consumer after any
    batches queued before the failure.

    Always `close()` (or use as a context manager): it is idempotent,
    unblocks a full queue, and joins the thread, so preemption and
    mid-epoch errors leave no orphaned threads.
    """

    THREAD_PREFIX = "data-prefetch"

    def __init__(
        self,
        iterable: Iterable[Any],
        sharding: Any = None,
        depth: int = 2,
        name: str = "train",
    ):
        self._it: Iterator[Any] = iter(iterable)
        self._sharding = sharding
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._exc: Optional[BaseException] = None
        self._closed = False
        # A batch the producer had fully staged when close()/detach() raced
        # its queue put — detach() hands it back so no batch is ever lost.
        self._overflow: Optional[Any] = None
        # metric window (drained by window_sums at report boundaries)
        self._mlock = threading.Lock()
        self._wait_ms_sum = 0.0
        self._h2d_ms_sum = 0.0
        self._depth_sum = 0.0
        self._n = 0
        self._thread = threading.Thread(
            target=self._produce, daemon=True,
            name=f"{self.THREAD_PREFIX}-{name}")
        self._thread.start()

    # -- producer ------------------------------------------------------

    def _produce(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    batch = next(self._it)
                except StopIteration:
                    break
                action = faultpoint.fire(FAULT_POINT_QUEUE)
                if action is faultpoint.Action.ERROR:
                    raise faultpoint.FaultInjected(FAULT_POINT_QUEUE)
                if action is faultpoint.Action.DROP:
                    continue
                t0 = time.perf_counter()
                if self._sharding is not None:
                    import jax

                    batch = shard_batch(batch, self._sharding)
                    # Block HERE, in the producer: the consumer must find
                    # the batch already resident on HBM, and the wait
                    # overlaps the previous step's compute.
                    jax.block_until_ready(batch)
                h2d_ms = (time.perf_counter() - t0) * 1e3
                if not self._put((batch, h2d_ms)):
                    # Closed/detached while the queue was full: stash the
                    # staged batch so detach() preserves data order.
                    self._overflow = batch
                    return
        except BaseException as e:  # re-raised in the consumer
            self._exc = e
        finally:
            self._put(_SENTINEL)

    def _put(self, item: Any) -> bool:
        """Bounded-queue put that aborts when close() is racing us."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    # -- consumer ------------------------------------------------------

    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self) -> Any:
        if self._closed:
            raise StopIteration
        t0 = time.perf_counter()
        item = self._q.get()
        wait_ms = (time.perf_counter() - t0) * 1e3
        if item is _SENTINEL:
            self._thread.join(timeout=5.0)
            self._closed = True
            if self._exc is not None:
                exc, self._exc = self._exc, None
                raise exc
            raise StopIteration
        batch, h2d_ms = item
        with self._mlock:
            self._wait_ms_sum += wait_ms
            self._h2d_ms_sum += h2d_ms
            self._depth_sum += self._q.qsize()
            self._n += 1
        return batch

    # -- metrics -------------------------------------------------------

    def window_sums(self) -> Tuple[float, float, float, int]:
        """(input_wait_ms_sum, h2d_ms_sum, queue_depth_sum, n_batches)
        since the last call; resets the window."""
        with self._mlock:
            out = (self._wait_ms_sum, self._h2d_ms_sum, self._depth_sum,
                   self._n)
            self._wait_ms_sum = self._h2d_ms_sum = self._depth_sum = 0.0
            self._n = 0
        return out

    def window_metrics(self) -> Dict[str, float]:
        """Per-batch means for the window ({} when no batches flowed)."""
        wait, h2d, depth, n = self.window_sums()
        if not n:
            return {}
        return {
            "input_wait_ms": wait / n,
            "h2d_ms": h2d / n,
            "prefetch_queue_depth": depth / n,
        }

    # -- lifecycle -----------------------------------------------------

    def close(self) -> None:
        """Idempotent teardown: stop the producer, unblock it if the queue
        is full, join. Safe from preemption / exception paths."""
        if self._closed and not self._thread.is_alive():
            return
        self._closed = True
        self._stop.set()
        while True:  # drain so a blocked _put observes _stop
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            logger.warning(
                "prefetch thread %s did not exit within 5s (host iterator "
                "stuck?); it is a daemon and will not block shutdown",
                self._thread.name)

    def detach(self) -> Tuple[list, Iterator[Any]]:
        """Stop prefetching WITHOUT losing position: returns
        (staged_batches, underlying_iterator) such that chaining the two
        reproduces exactly the stream a continued consumer would have
        seen. Used by elastic resize (docs/elasticity.md) to rebuild the
        pipeline around a new mesh's batch sharding while preserving data
        order; staged batches are device arrays sharded for the OLD mesh —
        re-device_put reshards them.

        The prefetcher is unusable afterwards (a fresh one wraps the
        returned stream)."""
        self._stop.set()
        self._thread.join(timeout=10.0)
        if self._thread.is_alive():
            raise RuntimeError(
                "prefetch producer did not stop; cannot detach without "
                "risking a lost batch")
        staged: list = []
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is _SENTINEL:
                continue
            staged.append(item[0])
        if self._overflow is not None:
            staged.append(self._overflow)
            self._overflow = None
        self._closed = True
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc
        return staged, self._it

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # pragma: no cover — belt and braces
        try:
            self.close()
        except Exception:
            pass
