"""The two drivers: `train_steps` (Trainer.fit's own loop, watched from
where its metric reports land) and `closed_loop` (callers that wait for
each reply, against the replica's own objects).

Each returns a `run` dict of raw facts — stamps, counters, request
records, the reduced trace — that the metric readers and the output check
read. Nothing here computes a metric.
"""

from __future__ import annotations

import bisect
import contextlib
import itertools
import os
import shutil
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import cells, reference, traffic, trace_reduce

TRACE_DIR = ".bench_trace"      # inside the checkout, git-ignored
TRACE_SECONDS = 3.0             # serving: how much of the window is traced
DRAIN_SECONDS = 60.0            # an answer that comes late is late, not wrong


class WindowClosed(Exception):
    """Raised from the report sink to end `fit` once the window has
    closed: `fit` unwinds through its own `finally` (watchdog, prefetch
    thread) and saves nothing."""


def seed31(seed: int) -> int:
    """Seeds arrive up to a little over 2**31; the program's trainer adds
    1 to its seed and keeps it in 32 signed bits."""
    return int(seed) % (2 ** 31 - 2)


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


@contextlib.contextmanager
def profiler_trace(root: str):
    """jax's profiler writing under the checkout; yields the directory.
    The Python tracer is off: it multiplies the trace's size and slows
    the very host code whose gaps the trace is read for."""
    import jax

    path = os.path.join(root, TRACE_DIR)
    shutil.rmtree(path, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=options)
    try:
        yield path
    finally:
        with contextlib.suppress(RuntimeError):   # already stopped
            jax.profiler.stop_trace()


def read_trace(root: str) -> Optional[Dict[str, Any]]:
    path = os.path.join(root, TRACE_DIR)
    try:
        return trace_reduce.reduce_dir(path)
    finally:
        shutil.rmtree(path, ignore_errors=True)


# ----------------------------------------------------------------- training


def _find_adam_moment(opt_state):
    """The first-moment tree inside an optax chain's state."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = _find_adam_moment(part)
            if found is not None:
                return found
    return None


class FitSink:
    """Stands where `fit`'s metric reports land (`core.train.
    report_training_metrics`), on `fit`'s own thread, once per flush.

    A flush has fetched the newest step's metrics, so the device is
    synchronised there: the window opens at one flush and closes at the
    first flush `seconds` later. The first three flushes also keep what
    the output check compares: each step's loss, the first gradient as
    the optimizer got it (Adam's first moment after one step, over
    1 - b1: its leaf norms and its sketch), and the sketches of the
    parameters after step 1 and after step 3. A sketch is a few thousand
    numbers a leaf (reference.sketch), so nothing of the parameters' size
    is kept beside the program's own state."""

    def __init__(self, trainer, t0: float, seconds: float,
                 spec: Dict[str, Any], b1: float, trace: bool, root: str,
                 seed: int):
        self.trainer, self.t0, self.seconds = trainer, t0, seconds
        self.warm_steps = int(spec["warm_steps"])
        self.trace_steps = int(spec["trace_steps"]) if trace else 0
        self.b1, self.root, self.seed = b1, root, seed
        self.reports: List[Dict[str, Any]] = []
        self.losses: List[float] = []
        self.kept: Dict[str, Any] = {}
        self.open: Optional[Dict[str, Any]] = None
        self.close: Optional[Dict[str, Any]] = None
        self.setup_s = 0.0
        self.traced: Optional[Dict[str, Any]] = None
        self._stack = contextlib.ExitStack()
        self._span = None

    def __call__(self, step: int, metrics: Dict[str, Any]) -> None:
        import jax

        now = time.monotonic()
        if "loss" not in metrics:
            return
        state = self.trainer.state
        if step <= 3:
            self.losses.append(float(metrics["loss"]))
        if step == 1:
            mu = _find_adam_moment(state.opt_state)
            scale = 1.0 / (1.0 - self.b1)
            self.kept.update(
                grad1={k: v * scale for k, v in
                       reference.leaf_norms(mu).items()},
                grad1_sketch={k: v * scale for k, v in
                              reference.sketch(mu, self.seed).items()},
                params1_sketch=reference.sketch(state.params, self.seed))
        elif step == 3:
            self.kept["params3_sketch"] = reference.sketch(
                state.params, self.seed)
        if step < self.warm_steps:
            return
        record = {"step": step, "t": now,
                  "input_wait_ms": float(metrics.get("input_wait_ms", 0.0)),
                  "finite": float(metrics.get("all_finite", 1.0)) >= 1.0}
        if self.open is None:
            self.open = record
            self.setup_s = now - self.t0
            if self.trace_steps:
                self._stack.enter_context(profiler_trace(self.root))
                self._stack.enter_context(
                    jax.profiler.TraceAnnotation("bench.window"))
                self.traced = {"t_open": time.monotonic(), "step_open": step}
        else:
            self.reports.append(record)
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if self.traced is not None and "t_close" not in self.traced:
            if step - self.traced["step_open"] >= self.trace_steps:
                self.traced.update(t_close=time.monotonic(), step_close=step)
                self._stack.close()
                # Starting and stopping the profiler stalls the host for
                # seconds: a traced run's window opens anew behind it, so
                # that what it reads from the host's clock is a rate.
                self.open = dict(record, t=time.monotonic())
                self.reports.clear()
                now = self.open["t"]
            else:
                self._span = jax.profiler.TraceAnnotation("bench.fit_step")
                self._span.__enter__()
        if now - self.open["t"] >= self.seconds:
            self.close = record
            raise WindowClosed()


def train_steps(cell, seed: int, seconds: float, trace: bool, root: str,
                t0: float, devices) -> Dict[str, Any]:
    from determined_tpu import core
    from determined_tpu.train import Trainer
    from determined_tpu.train.trial import TrialContext

    config, spec = cell["config"], cell["traffic"]
    tr, opt = config["train"], config["optimizer"]
    file, _, name = tr["trial"].partition(" ")
    trial_class = getattr(cells.load_module(os.path.join(root, file)), name)
    n = len(devices)
    seq_len, batch = int(spec["seq_len"]), int(tr["batch_per_chip"]) * n
    vocab = config["vocab_size"]

    class SeededTrial(trial_class):
        """The configuration's trial with its rows drawn from --seed."""

        def build_training_data(self):
            for step in itertools.count():
                yield {"tokens": traffic.train_rows(
                    seed, step, batch, seq_len, vocab)}

    hparams = {
        **cell["model"].hparams(config), "seq_len": seq_len,
        "global_batch_size": batch, "attention_impl": tr["attention_impl"],
        "remat": tr["remat"], "scan_unroll": tr["scan_unroll"],
        "mesh": {k: v for k, v in tr["mesh"].items() if n > 1},
        "learning_rate": opt["learning_rate"],
        "warmup_steps": opt["warmup_steps"],
        "decay_steps": opt["decay_steps"],
        "weight_decay": opt["weight_decay"],
    }
    ctx = core.init(max_length=10 ** 9,
                    checkpoint_dir=os.path.join(root, ".bench_ckpt"))
    trainer = Trainer(
        SeededTrial(TrialContext(hparams=hparams, core_context=ctx,
                                 n_devices=n)),
        core_context=ctx, devices=list(devices))
    sink = FitSink(trainer, t0, seconds, spec, opt["b1"], trace, root,
                   seed31(seed))
    ctx.train.report_training_metrics = sink
    try:
        trainer.fit(report_period=int(spec["report_period"]),
                    seed=seed31(seed))
    except WindowClosed:
        pass
    finally:
        sink._stack.close()
    attention_impl = trainer._attention_impl
    peak = memory_peak_bytes(devices)
    # Free the program's state before the reference takes the chip.
    sink.trainer = None
    trainer.state = None
    del trainer
    ctx.close()
    steps = sink.close["step"] - sink.open["step"]
    window_s = sink.close["t"] - sink.open["t"]
    run = {
        "kind": "train_steps", "setup_s": sink.setup_s, "window_s": window_s,
        "steps": steps, "tokens": steps * batch * seq_len, "chips": n,
        "batch": batch, "seq_len": seq_len,
        "attempted": steps,
        "failed": sum(1 for r in sink.reports if not r["finite"]),
        "reports": sink.reports, "memory_peak_bytes": peak,
        "attention_impl": attention_impl, "traced": sink.traced,
        "program": dict(sink.kept, losses=sink.losses),
    }
    if trace:
        run["trace"] = read_trace(root)
    return run


def train_standin(cell, seed: int, devices,
                  quant: Optional[str] = None,
                  keep_rows: float = 1.0) -> Dict[str, Any]:
    """The first three steps by the plain reference, on the rows and from
    the seed the program had. With `quant` or `keep_rows` under 1 it is a
    stand-in for the program: the control (a precision lower), or the
    fault of a step that leaves part of its rows out and takes the mean
    over the rest — half of them, or all but one chip's share, which is
    what a step without the exchange between chips computes."""
    import jax

    config, spec, model = cell["config"], cell["traffic"], cell["model"]
    batch = int(config["train"]["batch_per_chip"]) * len(devices)
    rows = [traffic.train_rows(seed, step, batch, int(spec["seq_len"]),
                               config["vocab_size"]) for step in range(3)]
    if keep_rows < 1.0:
        rows = [r[:max(1, int(batch * keep_rows))] for r in rows]
    return reference.train_three_steps(
        model, jax.random.PRNGKey(seed31(seed)), model.dims(config),
        config["optimizer"], rows, seed31(seed), quant=quant,
        rows=int(config["train"].get("reference_rows", 2)),
        devices=list(devices))


# ------------------------------------------------------------------ serving


class EngineSpans:
    """The benchmark's spans around the two calls into the engine, placed
    on the engine object the batcher drives: host-clock time inside each,
    the tokens each processed, and a profiler annotation so that a traced
    run can say what the host was doing in a device gap."""

    def __init__(self, engine):
        import jax

        # (kind, start, end, tokens processed, live context tokens,
        #  tokens generated), on the host's monotonic clock
        self.calls: List[tuple] = []
        decode, prefill = engine.decode, engine.prefill_request
        annotate = jax.profiler.TraceAnnotation

        def timed_decode(tokens, positions, temperatures):
            t = time.monotonic()
            with annotate("bench.decode"):
                out = decode(tokens, positions, temperatures)
            live = np.asarray(positions)
            live = live[live > 0]        # a decoding lane is past its prompt
            self.calls.append(("decode", t, time.monotonic(), int(live.size),
                               int(live.sum() + live.size), int(live.size)))
            return out

        def timed_prefill(slot, tokens, *args, **kwargs):
            t = time.monotonic()
            with annotate("bench.prefill"):
                out = prefill(slot, tokens, *args, **kwargs)
            novel = int(len(tokens)) - int(kwargs.get("cached_len", 0))
            self.calls.append(("prefill", t, time.monotonic(), novel, 0, 1))
            return out

        engine.decode, engine.prefill_request = timed_decode, timed_prefill


def make_replica(serving: Dict[str, Any], serve: Dict[str, Any], params):
    """`serve.task.build_replica`'s objects, wired the same way, with the
    weights handed in from the device instead of a checkpoint; `serving`
    is the adapter's mapping for the program's `build_model`."""
    from determined_tpu.serve.engine import ServingEngine
    from determined_tpu.serve.kv_cache import BlockManager
    from determined_tpu.serve.scheduler import (AdmissionQueue,
                                                ContinuousBatcher)
    from determined_tpu.serve.task import build_model

    cfg = build_model(serving)
    engine = ServingEngine(
        params, cfg, slots=int(serve["max_batch_size"]),
        max_seq_len=int(serve["max_seq_len"]),
        prefill_buckets=serve["prefill_buckets"], seed=0,
        attention_impl=serve["attention_impl"],
        kv_block_size=int(serve["kv_block_size"]),
        kv_num_blocks=int(serve["kv_num_blocks"]))
    blocks = BlockManager(num_blocks=engine.num_blocks,
                          block_size=engine.block_size,
                          prefix_cache=bool(serve["prefix_cache"]))
    batcher = ContinuousBatcher(
        engine, queue=AdmissionQueue(maxsize=int(serve["queue_depth"])),
        block_manager=blocks)
    return engine, batcher


class Caller(threading.Thread):
    """One waiting client: submit, block on `Request.result()`, submit the
    next. It holds the interpreter only to build a request and to file the
    reply; while the replica works it sleeps on the request's event."""

    def __init__(self, index: int, loop: "ClosedLoop"):
        super().__init__(daemon=True, name=f"caller-{index}")
        self.index, self.loop = index, loop

    def run(self) -> None:
        from determined_tpu.serve.scheduler import Request

        loop = self.loop
        last_reply = None
        for turn in itertools.count():
            if loop.stop.is_set():
                return
            k = traffic.caller_index(self.index, turn, loop.callers)
            ids, new = traffic.prompt_at(loop.spec, loop.seed, k, loop.vocab)
            req = Request(ids, max_new_tokens=new,
                          temperature=float(loop.spec["temperature"]))
            record = {"k": k, "caller": self.index, "prompt": ids,
                      "new": new, "error": None}
            try:
                loop.batcher.submit(req)
                record["submitted"] = req.submitted_at
                if last_reply is not None:
                    record["late_ms"] = (req.submitted_at - last_reply) * 1e3
                req.result(timeout=loop.reply_timeout)
            except Exception as e:   # refused, failed or never answered
                record["error"] = f"{type(e).__name__}: {e}"
            # lateness is counted from the reply's own stamp, so it holds
            # this thread's wake-up too
            last_reply = req.finished_at or time.monotonic()
            record.update(
                finished=last_reply, tokens=list(req.out_tokens),
                ttft_ms=(req.first_token_us - req.submitted_us) / 1e3
                if req.first_token_us else None,
                queue_ms=(req.admitted_us - req.submitted_us) / 1e3
                if req.admitted_us else None,
                prefill_ms=(req.prefill_end_us - req.prefill_start_us) / 1e3
                if req.prefill_end_us else None,
                tpot_ms=(req.finished_us - req.first_token_us) / 1e3
                / (len(req.out_tokens) - 1)
                if req.first_token_us and req.finished_us
                and len(req.out_tokens) > 1 else None,
                cached_len=req.cached_len, decode_steps=req.decode_steps,
                occupancy_at_admit=req.occupancy_at_admit)
            with loop.lock:
                loop.records.append(record)
            if record["error"] is not None:
                time.sleep(0.05)      # a refusing replica is not hammered


class ClosedLoop:
    def __init__(self, batcher, spec, seed: int, vocab: int):
        self.batcher, self.spec, self.seed, self.vocab = \
            batcher, spec, seed, vocab
        self.callers = int(spec["callers"])
        self.reply_timeout = 300.0
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.records: List[Dict[str, Any]] = []
        self.threads = [Caller(i, self) for i in range(self.callers)]

    def start(self) -> None:
        for t in self.threads:
            t.start()

    def everyone_answered(self) -> bool:
        with self.lock:
            return len({r["caller"] for r in self.records}) == self.callers

    def finish(self) -> None:
        """No new requests; wait for what is in flight."""
        self.stop.set()
        deadline = time.monotonic() + DRAIN_SECONDS
        for t in self.threads:
            t.join(max(0.0, deadline - time.monotonic()))


def serve_params(cell, seed: int):
    """The served weights: float32, on the device, in one jitted call."""
    import jax

    return reference.draw_params(
        cell["model"], jax.random.PRNGKey(seed31(seed)),
        cell["model"].dims(cell["config"]))


def closed_loop(cell, seed: int, seconds: float, trace: bool, root: str,
                t0: float, devices) -> Dict[str, Any]:
    import jax

    config, spec = cell["config"], cell["traffic"]
    serve = config["serve"]
    engine, batcher = make_replica(cell["model"].serving(config, serve),
                                   serve, serve_params(cell, seed))
    spans = EngineSpans(engine)
    batcher.start()                       # AOT-compiles before admitting
    loop = ClosedLoop(batcher, spec, seed, config["vocab_size"])
    loop.start()
    traced = None
    try:
        while not loop.everyone_answered():
            if not any(t.is_alive() for t in loop.threads):
                raise RuntimeError("every caller died during warm-up")
            time.sleep(0.02)
        warm = time.monotonic()
        opener = _wait_for_finish(loop, warm)
        if trace:
            with profiler_trace(root), \
                    jax.profiler.TraceAnnotation("bench.window"):
                traced = {"t_open": time.monotonic()}
                time.sleep(min(TRACE_SECONDS, seconds))
                traced["t_close"] = time.monotonic()
        closer = _wait_for_finish(loop, opener + seconds)
    finally:
        loop.finish()
        batcher.stop()
    peak = memory_peak_bytes(devices)
    attention_impl = engine.attention_impl
    engine._cache = engine.params = None   # free the chip for the reference
    del engine, batcher

    t_open, t_close, inside, done = window_of(
        spans.calls, loop.records, opener, closer)
    late = sorted(r["late_ms"] for r in loop.records if "late_ms" in r)
    if late:
        print(f"callers: resubmission ran late by p50 "
              f"{late[len(late) // 2]:.3f} ms, max {late[-1]:.3f} ms over "
              f"{len(late)} resubmissions", file=sys.stderr)
    decodes = [c for c in inside if c[0] == "decode"]
    run = {
        "kind": "closed_loop", "setup_s": t_open - t0,
        "window_s": t_close - t_open, "t_open": t_open, "t_close": t_close,
        "generated_tokens": sum(c[5] for c in inside),
        "active_steps": len(decodes),
        "occupancy_sum": sum(c[3] for c in decodes),
        "requests": [r for r in done if r["error"] is None],
        "calls": spans.calls,
        "attempted": len(done),
        "failed": sum(1 for r in done if r["error"] is not None),
        "never_answered": sum(1 for t in loop.threads if t.is_alive()),
        "memory_peak_bytes": peak, "chips": 1,
        "attention_impl": attention_impl, "traced": traced,
    }
    if trace:
        run["trace"] = read_trace(root)
    return run


def window_of(calls, records, opener: float, closer: float):
    """The window between two finished requests, on whole engine calls.

    Its edges are the ends of the engine calls that finished the opening
    and the closing request (each call ends in a device-to-host copy, so
    the device is synchronised there). Work counts when the call that did
    it ended inside (t_open, t_close]; a request counts when the call
    that finished it did. → (t_open, t_close, calls inside, records
    inside)."""
    ends = [c[2] for c in calls]

    def call_end(stamp: float) -> float:
        return ends[max(0, bisect.bisect_right(ends, stamp) - 1)]

    t_open, t_close = call_end(opener), call_end(closer)
    inside = [c for c in calls if t_open < c[2] <= t_close]
    done = [r for r in records
            if t_open < call_end(r["finished"]) <= t_close]
    return t_open, t_close, inside, done


def _wait_for_finish(loop: "ClosedLoop", after: float) -> float:
    """Sleep until some request has finished later than `after`; returns
    the earliest such finish stamp."""
    while True:
        with loop.lock:
            later = [r["finished"] for r in loop.records
                     if r["finished"] > after]
        if later:
            return min(later)
        if not any(t.is_alive() for t in loop.threads):
            raise RuntimeError("every caller died inside the window")
        time.sleep(0.02)


def sample_requests(run: Dict[str, Any], cell, seed: int):
    """The requests the output check replays: a seeded sample of those
    the window finished, the longest among them."""
    done = sorted(run["requests"], key=lambda r: r["k"])
    want = int(cell["traffic"]["check_requests"])
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 19]).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:max(0, want - 1)]]


def serve_gaps(cell, seed: int, sample, control: Optional[str] = None):
    """Replay the sample through the plain reference (fresh weights from
    the seed) and return each served token's gap below the reference's
    best — or, for the control, the lower precision's first choice's."""
    config, spec, model = cell["config"], cell["traffic"], cell["model"]
    shapes = spec["shapes"]
    width = max(p + n for p, n in shapes)
    width = -(-width // 128) * 128 if width > 128 else width
    return reference.replay_gaps(
        model, serve_params(cell, seed), model.dims(config),
        [(r["prompt"], np.asarray(r["tokens"], np.int32)) for r in sample],
        width=width, max_new=max(n for _, n in shapes),
        rows=int(config["serve"].get("reference_rows", 4)), control=control)
