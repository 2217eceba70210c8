"""ProfilerContext — system metrics + jax.profiler traces.

Reference: harness/determined/core/_profiler.py:23 (pynvml GPU collectors).
TPU re-design: per-host collector thread samples
  - TPU device memory (HBM) via jax.local_devices()[i].memory_stats()
  - host CPU/mem via /proc (no psutil dependency)
and ships them as metrics through TrainContext. `trace()` wraps a step range
in a jax.profiler trace written to the TensorBoard dir (the XLA-native
replacement for torch.profiler pass-through, reference _trainer.py:34).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Any, Dict, Optional

logger = logging.getLogger("determined_tpu.core")


def _read_proc_stat() -> tuple:
    with open("/proc/stat") as f:
        parts = f.readline().split()[1:8]
    vals = [int(p) for p in parts]
    idle = vals[3] + vals[4]
    return sum(vals), idle


def _read_meminfo() -> Dict[str, int]:
    out = {}
    with open("/proc/meminfo") as f:
        for line in f:
            k, v = line.split(":", 1)
            out[k] = int(v.strip().split()[0]) * 1024
    return out


def collect_system_metrics() -> Dict[str, Any]:
    metrics: Dict[str, Any] = {}
    try:
        mem = _read_meminfo()
        metrics["host_mem_used_bytes"] = mem["MemTotal"] - mem.get("MemAvailable", 0)
        metrics["host_mem_total_bytes"] = mem["MemTotal"]
    except Exception:
        pass
    try:
        import jax

        for i, d in enumerate(jax.local_devices()):
            stats = d.memory_stats() or {}
            if "bytes_in_use" in stats:
                metrics[f"tpu{i}_hbm_used_bytes"] = stats["bytes_in_use"]
            if "bytes_limit" in stats:
                metrics[f"tpu{i}_hbm_total_bytes"] = stats["bytes_limit"]
    except Exception:
        pass
    return metrics


# bf16 peak FLOP/s per chip by jax device_kind — used for the
# device-utilization (MFU) series. SURVEY §5 asks for TPU duty-cycle/MXU
# utilization in the profiler pipeline; on TPU the sound training-time
# utilization measure is model-FLOPs utilization (achieved/peak), which
# needs no hardware counters.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_per_device() -> Optional[float]:
    try:
        import jax

        kind = jax.local_devices()[0].device_kind
    except Exception:
        return None
    for name, peak in PEAK_BF16_FLOPS.items():
        if kind.startswith(name):
            return peak
    return None


class _Collector(threading.Thread):
    def __init__(self, train_context, interval: float, get_step, profiler):
        super().__init__(daemon=True, name="profiler-collector")
        self._train = train_context
        self._interval = interval
        self._get_step = get_step
        self._profiler = profiler
        # NOT named `_stop`: that would shadow threading.Thread._stop and
        # make join() blow up (the same bug class as the PR-5
        # _PreemptionWatcher fix).
        self._stop_event = threading.Event()

    def run(self) -> None:
        prev = None
        while not self._stop_event.wait(self._interval):
            m = collect_system_metrics()
            try:
                total, idle = _read_proc_stat()
                if prev is not None:
                    dt, di = total - prev[0], idle - prev[1]
                    if dt > 0:
                        m["host_cpu_util"] = 1.0 - di / dt
                prev = (total, idle)
            except Exception:
                pass
            m.update(self._profiler._utilization_window())
            try:
                self._train.report_metrics("profiling", self._get_step(), m)
            except Exception:
                logger.debug("profiler report failed", exc_info=True)

    def close(self) -> None:
        self._stop_event.set()


class ProfilerContext:
    def __init__(self, train_context, tensorboard_dir: Optional[str] = None):
        self._train = train_context
        self._collector: Optional[_Collector] = None
        self._step = 0
        self.tensorboard_dir = tensorboard_dir or os.environ.get(
            "DET_TENSORBOARD_PATH", "/tmp/determined_tpu/tb"
        )
        # device-utilization series (MFU): the Trainer feeds step counts +
        # wall time; the trial declares its FLOPs per optimizer step.
        self._lock = threading.Lock()
        self._flops_per_step: Optional[float] = None
        self._window_steps = 0
        self._window_seconds = 0.0
        self._n_devices = 1
        self._peak = peak_flops_per_device()
        # input-pipeline gauges (fed by the Trainer from DevicePrefetcher
        # window sums): how long each step waited on input, how long the
        # H2D copy took, and how full the prefetch queue ran.
        self._input_wait_ms = 0.0
        self._input_h2d_ms = 0.0
        self._input_depth = 0.0
        self._input_batches = 0
        self._collector_interval = 5.0
        self._trace_active = False

    def set_step(self, step: int) -> None:
        self._step = step

    def set_flops_per_step(self, flops: Optional[float],
                           n_devices: int = 1) -> None:
        """Model FLOPs per (global) optimizer step; enables the
        device_flops_util series (achieved / bf16-peak per chip)."""
        self._flops_per_step = flops
        self._n_devices = max(1, n_devices)

    def observe_steps(self, n_steps: int, seconds: float) -> None:
        """Called by the Trainer each metric flush with the window's step
        count and wall time."""
        with self._lock:
            self._window_steps += n_steps
            self._window_seconds += seconds

    def observe_input(self, wait_ms_sum: float, h2d_ms_sum: float,
                      depth_sum: float, n_batches: int) -> None:
        """Called by the Trainer each metric flush with the input
        pipeline's window sums (DevicePrefetcher.window_sums)."""
        if not n_batches:
            return
        with self._lock:
            self._input_wait_ms += wait_ms_sum
            self._input_h2d_ms += h2d_ms_sum
            self._input_depth += depth_sum
            self._input_batches += n_batches

    def _utilization_window(self) -> Dict[str, Any]:
        with self._lock:
            steps, secs = self._window_steps, self._window_seconds
            self._window_steps, self._window_seconds = 0, 0.0
            in_wait, in_h2d = self._input_wait_ms, self._input_h2d_ms
            in_depth, in_n = self._input_depth, self._input_batches
            self._input_wait_ms = self._input_h2d_ms = 0.0
            self._input_depth, self._input_batches = 0.0, 0
        out: Dict[str, Any] = {}
        if in_n:
            out["input_wait_ms"] = in_wait / in_n
            out["h2d_ms"] = in_h2d / in_n
            out["prefetch_queue_depth"] = in_depth / in_n
        if steps and secs > 0:
            sps = steps / secs
            out["steps_per_second"] = sps
            if self._flops_per_step and self._peak:
                out["device_flops_util"] = (
                    self._flops_per_step * sps / (self._peak * self._n_devices)
                )
        return out

    def on(self, sampling_interval: float = 5.0) -> None:
        if self._collector is None:
            self._collector_interval = sampling_interval
            self._collector = _Collector(
                self._train, sampling_interval, lambda: self._step, self
            )
            self._collector.start()

    def off(self) -> None:
        if self._collector is not None:
            collector = self._collector
            self._collector = None
            collector.close()
            # Bounded join: the collector sleeps up to one interval, and a
            # wedged report must not hold close()/Context.close() hostage.
            collector.join(timeout=self._collector_interval + 2.0)
            if collector.is_alive():
                logger.warning("profiler collector did not stop in time")

    @contextlib.contextmanager
    def trace(self, name: str = "train_step"):
        """jax.profiler trace for a region → TensorBoard trace viewer.

        Hardened (docs/observability.md): re-entry is refused without
        touching the profiler (a nested start_trace would wedge it), a
        failed start logs and runs the body untraced, and stop_trace is
        always attempted so a failure mid-body can't leave the profiler
        stuck for every later trace() call.
        """
        if self._trace_active:
            logger.warning(
                "profiler.trace(%s): a trace is already active; running "
                "untraced (jax.profiler does not nest)", name)
            yield
            return
        import jax

        started = False
        try:
            os.makedirs(self.tensorboard_dir, exist_ok=True)
            # The Python tracer is off: it slows the very host code whose
            # gaps a trace is read for, and the loops' phases
            # (common/trace.py phase()) name that code already.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.tensorboard_dir,
                                     profiler_options=options)
            started = True
        except Exception:
            # Profiler unavailability must not fail training: log, run
            # the body untraced.
            logger.warning("profiler.trace(%s): start_trace failed; "
                           "running untraced", name, exc_info=True)
        self._trace_active = started
        try:
            yield
        finally:
            self._trace_active = False
            if started:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    logger.warning("profiler.trace(%s): stop_trace failed",
                                   name, exc_info=True)

    def close(self) -> None:
        self.off()
