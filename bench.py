#!/usr/bin/env python
"""BASELINE.md benchmarks. Headline: GPT-2 (124M) pretraining throughput on
one TPU chip.

Prints one JSON line PER METRIC (gpt2 first — the headline — then
resnet50 samples/sec/chip and asha trials/hour):
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The reference publishes no training-throughput numbers (BASELINE.md), so
`vs_baseline` is measured MFU relative to the driver's 40% MFU target
(BASELINE.json north star): vs_baseline = MFU / 0.40. >1.0 beats the target.

GPT-2 config: small, bf16, remat, seq 1024, per-chip batch 16 — the
single-chip unit of the v5e-64 GPT-2 north-star workload.

`--only <section>` runs that section in this process. Without it every
section runs in a child process of its own (`bench.py --only <section>`),
one after another, and this parent never imports jax: a chip belongs to
one process at a time, so a parent that had run the gpt2 section
in-process would hold the chip while bench_asha's master, agent and trial
children needed it. A failing section prints an error line and the others
still run.
"""

import json
import sys
import time

import numpy as np


def gpt2_bench() -> None:
    import jax
    import optax

    from determined_tpu.compile.runtime import enable_compilation_cache
    from determined_tpu.models import gpt2
    from determined_tpu.train import create_train_state, make_multi_step

    enable_compilation_cache()

    # scan_unroll=0: fully unroll the layer scan (removes stacked-param
    # dynamic-slices + scan-carry stacking). remat=False: at 124M/B16/S1024
    # activations fit HBM (deviceless compile: 6.35 GB temp), so the
    # recompute is skipped. What either is worth on a v5e: not measured.
    cfg = gpt2.Config(scan_unroll=0, remat=False)
    B, S = 16, 1024
    # N optimizer steps per dispatch (lax.scan in one jit): amortizes the
    # host→device dispatch + sync latency.
    STEPS_PER_CALL = 10
    peak_flops = _peak_flops()

    tx = optax.adamw(3e-4)
    state = create_train_state(lambda r: gpt2.init(r, cfg), tx, jax.random.PRNGKey(0))
    step = make_multi_step(
        lambda p, b, r: gpt2.loss_fn(p, b, cfg), tx, STEPS_PER_CALL
    )
    batches = {
        "tokens": np.random.default_rng(0)
        .integers(0, cfg.vocab_size, size=(STEPS_PER_CALL, B, S + 1))
        .astype(np.int32)
    }

    # warmup / compile
    state, m = step(state, batches, jax.random.PRNGKey(0))
    float(m["loss"])  # device→host read: the step has finished

    n_calls = 3
    t0 = time.time()
    for i in range(n_calls):
        state, m = step(state, batches, jax.random.PRNGKey(100 + i))
    float(m["loss"])
    dt = (time.time() - t0) / (n_calls * STEPS_PER_CALL)

    tokens_per_sec = B * S / dt
    samples_per_sec = B / dt
    mfu = gpt2.flops_per_token(cfg, S) * tokens_per_sec / peak_flops

    print(
        json.dumps(
            {
                "metric": "gpt2_124m_samples_per_sec_per_chip",
                "value": round(samples_per_sec, 2),
                "unit": "samples/sec/chip (seq=1024)",
                "vs_baseline": round(mfu / 0.40, 3),
                "detail": {
                    "tokens_per_sec": round(tokens_per_sec),
                    "step_ms": round(dt * 1000, 1),
                    "mfu": round(mfu, 4),
                    "batch": B,
                    "seq": S,
                    "device": str(jax.devices()[0]),
                },
            }
        )
    )


def _peak_flops() -> float:
    """bf16 peak FLOP/s of the device this process runs on, from the one
    table keyed by `device_kind` (core/_profiler.py). A device the table
    does not know is an error: an MFU against a guessed peak is not a
    measurement."""
    import jax

    from determined_tpu.core._profiler import peak_flops_per_device

    peak = peak_flops_per_device()
    if peak is None:
        raise RuntimeError(
            f"no bf16 peak on record for device_kind "
            f"{jax.devices()[0].device_kind!r} (core/_profiler.py "
            "PEAK_BF16_FLOPS); MFU cannot be computed here")
    return peak


def train_attn_bench() -> None:
    """`make bench-train` (docs/training-perf.md): the four-leg training-
    attention A/B — dense → flash(f32) → flash(bf16) → flash+overlap.

    All four legs run interleaved on THIS machine's mesh (same devices,
    same init, same batches; only the `optimizations` knob changes):
    per-leg step_ms and one-step loss, gating the numerics contract
    (flash ≡ dense arithmetic; bf16 within tolerance). Off-TPU the section
    asks for the pallas interpreter, on one device (the interpreter cannot
    serve a multi-device shard_map), and says so in the JSON: step_ms of
    the flash legs then measures the interpreter, and only the wiring and
    numerics gates mean anything.
    """
    import os

    if "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import contextlib

    import jax
    import optax
    from jax.experimental.pallas import tpu as pltpu

    from determined_tpu.compile.runtime import enable_compilation_cache
    from determined_tpu.models import gpt2
    from determined_tpu.parallel.mesh import AXIS_ORDER, MeshConfig, on_tpu
    from determined_tpu.parallel.sharding import LogicalRules
    from determined_tpu.train import create_train_state, make_train_step

    enable_compilation_cache()
    # Small enough to finish under interpret-mode pallas on CPU, but with a
    # pallas-supported geometry (seq % 128 == 0, head dim 64).
    B, S = 8, 128
    n_dev = len(jax.devices())
    tpu = on_tpu(jax.devices())
    fsdp = n_dev if tpu and n_dev in (2, 4, 8) else 1
    kernel_mode = (contextlib.nullcontext if tpu
                   else pltpu.force_tpu_interpret_mode)
    shape = MeshConfig(data=1, fsdp=fsdp).resolve(fsdp).sizes()
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:fsdp]).reshape(shape), AXIS_ORDER)
    rules = LogicalRules()

    def leg_cfg(impl, bf16=False, overlap=False):
        return gpt2.Config(
            vocab_size=512, n_positions=S, d_model=256, n_layer=2, n_head=4,
            remat=False, attention_impl=impl, attention_bf16=bf16,
            overlap_allgather=overlap)

    legs = [
        ("dense", leg_cfg("dense")),
        ("flash_f32", leg_cfg("pallas")),
        ("flash_bf16", leg_cfg("pallas", bf16=True)),
        ("flash_bf16_overlap", leg_cfg("pallas", bf16=True, overlap=True)),
    ]
    batch = {"tokens": np.random.default_rng(0).integers(
        0, 512, size=(B, S + 1)).astype(np.int32)}

    def run_leg(cfg):
        tx = optax.adamw(3e-4)
        with jax.sharding.set_mesh(mesh), kernel_mode():
            state = create_train_state(
                lambda r: gpt2.init(r, cfg), tx, jax.random.PRNGKey(0))
            step = make_train_step(
                lambda p, b, r: gpt2.loss_fn(p, b, cfg, rules), tx,
                mesh=mesh, rules=rules)
            state, m = step(state, batch, jax.random.PRNGKey(1))  # compile
            first_loss = float(m["loss"])
            n_calls = 3
            t0 = time.time()
            for i in range(n_calls):
                state, m = step(state, batch, jax.random.PRNGKey(2 + i))
            float(m["loss"])
            return (time.time() - t0) / n_calls * 1e3, first_loss

    # Interleave two full rounds and keep each leg's best pass so process
    # warmup (allocator, caches) doesn't bias whichever leg runs first.
    measured = {name: {"step_ms": float("inf"), "loss": None}
                for name, _ in legs}
    for _ in range(2):
        for name, cfg in legs:
            ms, loss = run_leg(cfg)
            if ms < measured[name]["step_ms"]:
                measured[name] = {"step_ms": round(ms, 1),
                                  "loss": round(loss, 4)}

    d_loss = measured["dense"]["loss"]
    f32_delta = abs(measured["flash_f32"]["loss"] - d_loss)
    bf16_delta = abs(measured["flash_bf16"]["loss"] - d_loss)
    print(json.dumps({
        "metric": "train_attn_loss_parity",
        "value": round(f32_delta, 5),
        "unit": "|loss(flash_f32) - loss(dense)| one step, same init/batch "
                "(gate: < 0.05; bf16 leg < 0.1)",
        "vs_baseline": 1.0,
        "detail": {
            "legs": measured,
            "bf16_delta": round(bf16_delta, 5),
            "mesh": dict(zip(AXIS_ORDER, shape)),
            "platform": jax.devices()[0].platform,
            "caveat": (None if tpu else
                       "not a TPU: pallas legs run in interpret mode on one "
                       "device, so their step_ms measures the interpreter — "
                       "numerics and wiring are the only gates here"),
        },
    }))
    assert f32_delta < 0.05, measured
    assert bf16_delta < 0.10, measured
    assert (abs(measured["flash_bf16_overlap"]["loss"]
                - measured["flash_bf16"]["loss"]) < 0.05), measured


def input_pipeline_bench() -> None:
    """Async input pipeline A/B (`make bench-input`): the same slow-host
    loader + fixed-cost step, synchronous vs DevicePrefetcher. Reports the
    steady-state step-time speedup and the input_wait_ms collapse — the
    ISSUE-3 acceptance numbers, measured on this machine."""
    from determined_tpu.data.bench import ab_compare

    host_delay_s, step_s, n = 0.020, 0.050, 20

    def make_iter():
        rng = np.random.default_rng(0)
        def gen():
            for _ in range(n):
                time.sleep(host_delay_s)  # simulated host preprocessing
                yield {"x": rng.normal(size=(64, 256)).astype(np.float32)}
        return gen()

    def step_fn(batch):
        time.sleep(step_s)  # stands in for dispatched device compute

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = jax.devices()
    sharding = NamedSharding(
        Mesh(np.asarray(devs[:1]).reshape(1), ("data",)),
        PartitionSpec("data"))
    result = ab_compare(make_iter, step_fn, sharding=sharding, depth=2)
    print(json.dumps({
        "metric": "input_pipeline_speedup",
        "value": result["speedup"],
        "unit": "x vs synchronous feed (20ms host, 50ms step)",
        "vs_baseline": result["speedup"],  # sync feed IS the baseline
        "detail": {
            "sync_step_ms": result["sync"]["step_ms"],
            "prefetch_step_ms": result["prefetch"]["step_ms"],
            "sync_input_wait_ms": result["sync"]["input_wait_ms"],
            "prefetch_input_wait_ms": result["prefetch"]["input_wait_ms"],
            "input_wait_ms_delta": result["input_wait_ms_delta"],
            "h2d_ms": result["prefetch"].get("h2d_ms"),
            "depth": result["depth"],
        },
    }))


def elastic_bench() -> None:
    """`make bench-elastic`: resize downtime (signal -> first post-resize
    step) vs the restart-from-checkpoint requeue baseline, same drain
    scenario (docs/elasticity.md).

    Both paths take the same deadline-budgeted emergency checkpoint and
    end up training at the target size. The resize path reshards in
    process (abstract restore template, one retrace). The baseline pays
    what a PR-5 requeue actually pays: a FRESH task process (python + jax
    + orbax import, device init), full Trainer build at the target size,
    restore, recompile — measured by really spawning one. It is still
    CONSERVATIVE: a real requeue also waits in the scheduler queue, which
    is unbounded and excluded here. Resize must win even against the
    zero-queue-wait requeue."""
    import os
    import subprocess
    import tempfile
    import textwrap

    if "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from determined_tpu import core
    from determined_tpu.train import Trainer
    from determined_tpu.train.trial import JaxTrial, TrialContext
    from determined_tpu.parallel.mesh import MeshConfig

    import optax

    devices = jax.devices()
    src = min(4, len(devices))
    tgt = max(1, src // 2)
    dim, resize_at, total = 256, 8, 16

    class Elastic(JaxTrial):
        prefetch = False

        def __init__(self, ctx, start=0, action=None):
            super().__init__(ctx)
            self._start, self._action = start, action

        def init_params(self, rng):
            return {"w": jax.random.normal(rng, (dim, dim)) * 0.02}

        def param_logical_axes(self):
            return {"w": (None, None)}

        def loss(self, params, batch, rng):
            import jax.numpy as jnp

            return jnp.mean((batch["x"] @ params["w"]) ** 2)

        def optimizer(self):
            return optax.sgd(0.01)

        def mesh_config(self):
            return MeshConfig()

        def build_training_data(self):
            for i in range(self._start, 4096):
                if self._action is not None and i == resize_at:
                    self._action()
                rng = np.random.default_rng(100 + i)
                yield {"x": rng.normal(size=(8, dim)).astype(np.float32)}

    def timed_reports(ctx):
        """Wall timestamp per training report (report_period=1 => per
        step) — the 'first post-resize step' instant without touching the
        hot loop."""
        stamps = []
        orig = ctx.train.report_training_metrics

        def wrapped(steps_completed, metrics, **kw):
            stamps.append((time.monotonic(), steps_completed, dict(metrics)))
            return orig(steps_completed, metrics, **kw)

        ctx.train.report_training_metrics = wrapped
        return stamps

    tmp = tempfile.mkdtemp(prefix="bench_elastic_")
    signal_t = {}

    # Warmup: the first orbax save/restore in a process pays one-time
    # import/registry setup (~300ms) — absorb it here so neither measured
    # path carries it.
    ctx = core.init(max_length=2, checkpoint_dir=tmp + "/warm",
                    async_checkpointing=False)
    trainer = Trainer(Elastic(TrialContext()), core_context=ctx,
                      devices=devices[:src])
    trainer.fit(report_period=1, checkpoint_period=1)
    trainer._restore("trial0-step2")
    ctx.close()

    # --- resize path: in-process reshard, same allocation semantics.
    ctx = core.init(max_length=total, checkpoint_dir=tmp + "/a",
                    async_checkpointing=False)
    stamps = timed_reports(ctx)

    def fire():
        signal_t["t"] = time.monotonic()
        ctx.preempt.force_resize(tgt, deadline=60.0)

    trainer = Trainer(Elastic(TrialContext(), action=fire),
                      core_context=ctx, devices=devices[:src])
    trainer.fit(report_period=1, preempt_period=1)
    assert trainer.mesh.size == tgt
    resize_step = next(s for _, s, m in stamps if "resize_downtime_ms" in m)
    first_after = next(t for t, s, m in stamps
                       if s > resize_step and "loss" in m)
    resize_downtime_s = first_after - signal_t["t"]
    ctx.close()

    # --- requeue baseline: emergency checkpoint + a FRESH task process
    # restoring at the target size (what restart-from-checkpoint costs
    # with zero queue wait). CLOCK_MONOTONIC is machine-wide on Linux, so
    # the child's first-step stamp is directly comparable.
    ctx = core.init(max_length=resize_at + 1, checkpoint_dir=tmp + "/b",
                    async_checkpointing=False)

    def fire2():
        signal_t["t"] = time.monotonic()
        ctx.preempt.force(deadline=60.0)

    trainer = Trainer(Elastic(TrialContext(), action=fire2),
                      core_context=ctx, devices=devices[:src])
    state = trainer.fit(report_period=1, preempt_period=1)
    step = int(jax.device_get(state.step))
    ctx.close()  # the preempted container exits here

    child = os.path.join(tmp, "requeue_child.py")
    with open(child, "w") as f:
        f.write(textwrap.dedent(f"""
            import os, sys, time
            os.environ["XLA_FLAGS"] = (
                " --xla_force_host_platform_device_count={tgt}")
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax, numpy as np, optax
            from determined_tpu import core
            from determined_tpu.train import Trainer
            from determined_tpu.train.trial import JaxTrial, TrialContext
            from determined_tpu.parallel.mesh import MeshConfig

            dim, start, total = {dim}, {step}, {total}

            class Elastic(JaxTrial):
                prefetch = False
                def init_params(self, rng):
                    return {{"w": jax.random.normal(rng, (dim, dim)) * 0.02}}
                def param_logical_axes(self):
                    return {{"w": (None, None)}}
                def loss(self, params, batch, rng):
                    import jax.numpy as jnp
                    return jnp.mean((batch["x"] @ params["w"]) ** 2)
                def optimizer(self):
                    return optax.sgd(0.01)
                def mesh_config(self):
                    return MeshConfig()
                def build_training_data(self):
                    for i in range(start, 4096):
                        rng = np.random.default_rng(100 + i)
                        yield {{"x": rng.normal(size=(8, dim))
                               .astype(np.float32)}}

            ctx = core.init(max_length=total,
                            checkpoint_dir={tmp + "/b"!r},
                            async_checkpointing=False)
            orig = ctx.train.report_training_metrics
            done = []
            def wrapped(steps_completed, metrics, **kw):
                if "loss" in metrics and not done:
                    done.append(1)
                    print("FIRST_STEP", time.monotonic(), flush=True)
                return orig(steps_completed, metrics, **kw)
            ctx.train.report_training_metrics = wrapped
            trainer = Trainer(Elastic(TrialContext()), core_context=ctx,
                              devices=jax.devices())
            trainer.fit(report_period=1,
                        resume_from="trial0-step" + str(start))
            ctx.close()
        """))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, child], env=env,
                          capture_output=True, text=True, timeout=600)
    first_after = None
    for line in proc.stdout.splitlines():
        if line.startswith("FIRST_STEP"):
            first_after = float(line.split()[1])
    assert first_after is not None, proc.stdout + proc.stderr
    requeue_baseline_s = first_after - signal_t["t"]

    print(json.dumps({
        "metric": "elastic_resize_downtime_s",
        "value": round(resize_downtime_s, 3),
        "unit": f"s signal->first step after {src}->{tgt} slot resize",
        "vs_baseline": round(requeue_baseline_s / resize_downtime_s, 2),
        "detail": {
            "requeue_baseline_s": round(requeue_baseline_s, 3),
            "resize_beats_requeue": resize_downtime_s < requeue_baseline_s,
            "src_slots": src,
            "target_slots": tgt,
            "note": "baseline spawns a real fresh task process (restore + "
                    "recompile) but excludes scheduler queue wait, which "
                    "is unbounded in a real requeue",
        },
    }))


def compile_bench() -> None:
    """`make bench-compile` (docs/compile-farm.md): the compile-farm A/B on
    a real devcluster — nocache vs persistent-XLA-cache vs farm arms of
    sequential compile-bound GPT-2 trials. Headline:
    `cached_median_compile_s` (farm-arm warm trials; the acceptance gate is
    <= 0.5s) plus the farm on/off trials/hour delta."""
    import os
    import subprocess
    import tempfile

    REPO = os.path.dirname(os.path.abspath(__file__))
    subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                   check=True, capture_output=True)
    from bench_asha import run_compile_farm
    from tests.test_platform_e2e import Devcluster

    tmp = tempfile.mkdtemp(prefix="bench_compile_")
    cluster = Devcluster(tmp, os.path.join(REPO, "native", "bin"), slots=1)
    try:
        cluster.start_master()
        cluster.start_agent()
        token = cluster.login()
        detail = run_compile_farm(cluster, token, tmp)
    finally:
        cluster.stop()
    cached = detail.get("cached_median_compile_s")
    print(json.dumps({
        "metric": "cached_median_compile_s",
        "value": cached,
        "unit": "s (median first-step cost of warm farm trials)",
        # The gate: recompilation eliminated as a per-trial cost.
        "vs_baseline": round(0.5 / cached, 2) if cached else None,
        "detail": detail,
    }))
    assert cached is not None and cached <= 0.5, (
        f"cached_median_compile_s {cached} exceeds the 0.5s gate "
        f"({detail})")


def trace_bench() -> None:
    """`make bench-trace` (docs/observability.md): (a) step_ms with
    lifecycle tracing on vs off — the <1% overhead gate that keeps
    tracing always-on; (b) span-ingest throughput on the real master
    under concurrent batched POSTs, the `bench_asha.py`-shaped control-
    plane load."""
    import os
    import tempfile
    import threading

    import jax
    import optax

    from determined_tpu import core
    from determined_tpu.parallel.mesh import MeshConfig
    from determined_tpu.train import Trainer
    from determined_tpu.train.trial import JaxTrial, TrialContext

    class TinyTrial(JaxTrial):
        prefetch = False

        def init_params(self, rng):
            return {"w": jax.random.normal(rng, (256, 256)) * 0.02}

        def param_logical_axes(self):
            return {"w": (None, None)}

        def loss(self, params, batch, rng):
            import jax.numpy as jnp

            return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

        def optimizer(self):
            return optax.sgd(1e-3)

        def mesh_config(self):
            return MeshConfig()

        def build_training_data(self):
            rng = np.random.default_rng(0)
            for _ in range(4096):
                yield {"x": rng.normal(size=(32, 256)).astype(np.float32),
                       "y": rng.normal(size=(32, 256)).astype(np.float32)}

    def steady_sps(trace_off: bool):
        """Median steps/second across post-compile metric flushes for one
        local fit (tracing toggled via DET_TRACE_OFF)."""
        old = os.environ.get("DET_TRACE_OFF")
        os.environ["DET_TRACE_OFF"] = "1" if trace_off else "0"
        try:
            with tempfile.TemporaryDirectory() as tmp:
                ctx = core.init(max_length=400, checkpoint_dir=tmp,
                                async_checkpointing=False)
                trainer = Trainer(TinyTrial(TrialContext()),
                                  core_context=ctx)
                trainer.fit(report_period=20, checkpoint_period=100)
                flushes = [m["metrics"]["steps_per_second"]
                           for m in ctx.train.local_training_metrics
                           if "steps_per_second" in m["metrics"]]
                n_spans = len(ctx.tracer.local_spans)
                ctx.close()
            assert len(flushes) >= 5, flushes
            # Drop the compile-bearing first flush; median over the rest.
            return float(np.median(flushes[1:])), n_spans
        finally:
            if old is None:
                os.environ.pop("DET_TRACE_OFF", None)
            else:
                os.environ["DET_TRACE_OFF"] = old

    # Interleave on/off runs in one process AND alternate which goes
    # first each round: process warmup (allocator, caches) favors
    # whichever mode runs later, so a fixed order would bias the delta.
    on_runs, off_runs, spans_per_run = [], [], 0
    for i in range(4):
        for off_first in ([True, False] if i % 2 else [False, True]):
            sps, n_spans = steady_sps(trace_off=off_first)
            (off_runs if off_first else on_runs).append(sps)
            if not off_first:
                spans_per_run = n_spans
    sps_on = float(np.median(on_runs))
    sps_off = float(np.median(off_runs))
    overhead_pct = (sps_off / sps_on - 1.0) * 100.0

    print(json.dumps({
        "metric": "trace_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "% step_ms added by always-on tracing (gate: < 1%)",
        "vs_baseline": round(sps_on / sps_off, 4),
        "detail": {
            "steps_per_s_tracing_on": round(sps_on, 2),
            "steps_per_s_tracing_off": round(sps_off, 2),
            "spans_emitted_per_run": spans_per_run,
            "gate_passed": overhead_pct < 1.0,
        },
    }))

    # (b) span-ingest throughput on the real master.
    import shutil
    import subprocess
    import uuid

    repo = os.path.dirname(os.path.abspath(__file__))
    bindir = os.path.join(repo, "native", "bin")
    if not os.path.exists(os.path.join(bindir, "determined-master")):
        subprocess.run(["make", "-C", os.path.join(repo, "native")],
                       check=True, capture_output=True)
    sys.path.insert(0, repo)
    from tests.test_platform_e2e import Devcluster

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    cluster = Devcluster(tmp, bindir)
    try:
        cluster.start_master()
        token = cluster.login()
        eid = cluster.api("POST", "/api/v1/experiments",
                          {"unmanaged": True,
                           "config": {"name": "bench-trace"}},
                          token=token)["id"]
        tid = cluster.api("POST", f"/api/v1/experiments/{eid}/trials",
                          {"hparams": {}}, token=token)["id"]

        batch_size, n_threads, batches_per_thread = 100, 4, 25

        def make_batch():
            t0 = int(time.time() * 1e6)
            return {"spans": [
                {"trace_id": "bench", "span_id": uuid.uuid4().hex[:16],
                 "parent": "bench", "name": "harness.validate",
                 "start_us": t0 + i, "end_us": t0 + i + 1000,
                 "attrs": {"bench": True}}
                for i in range(batch_size)]}

        errors = []

        def pump():
            for _ in range(batches_per_thread):
                try:
                    cluster.api("POST", f"/api/v1/trials/{tid}/spans",
                                make_batch(), token=token)
                except Exception as e:  # noqa: BLE001 — report, don't hang
                    errors.append(e)

        threads = [threading.Thread(target=pump) for _ in range(n_threads)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.monotonic() - t0
        assert not errors, errors[0]
        total = batch_size * n_threads * batches_per_thread
        rows = None
        trace = cluster.api("GET", f"/api/v1/trials/{tid}/trace",
                            token=token)
        rows = len(trace["spans"])
        print(json.dumps({
            "metric": "span_ingest_spans_per_s",
            "value": round(total / dt, 1),
            "unit": f"spans/s ({n_threads} writers, {batch_size}/batch, "
                    "persisted + readable)",
            "vs_baseline": 1.0,
            "detail": {
                "total_spans": total,
                "rows_readable": rows,
                "wall_s": round(dt, 3),
                "all_persisted": rows == total,
            },
        }))
    finally:
        cluster.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def serve_bench() -> None:
    """`make bench-serve`: continuous batching vs the sequential
    one-request-at-a-time baseline on the same GPT-2 checkpoint.

    End-to-end through the real serving stack: a checkpoint is written,
    integrity-verified and loaded (engine.load_checkpoint_params), both
    engines AOT-compile, and the SAME 32-request burst (random prompt
    lengths, 32 new tokens each) runs through (a) a 1-slot batcher —
    requests strictly one at a time — and (b) the 8-slot continuous
    batcher. Emits serve_tokens_per_s / serve_p50_ms / serve_p99_ms; the
    ISSUE-6 acceptance bar is tokens/s >= 1.5x sequential.
    """
    import tempfile

    import jax

    from determined_tpu import core
    from determined_tpu.models import gpt2
    from determined_tpu.serve import (
        AdmissionQueue, BlockManager, ContinuousBatcher, Request,
        ServingEngine, load_checkpoint_params)

    # gpt2-small on a TPU (the flagship config at one-chip scale);
    # CPU-only environments drop to tiny so the section finishes inside a
    # CI budget. Override either way with DET_BENCH_SERVE_MODEL. The
    # metric's unit string names the model, so rounds stay comparable.
    import os

    from determined_tpu.parallel.mesh import on_tpu

    default_size = "small" if on_tpu(jax.devices()) else "tiny"
    size = os.environ.get("DET_BENCH_SERVE_MODEL", default_size)
    base = {"tiny": gpt2.Config.tiny, "small": gpt2.Config.small}[size]()
    cfg = gpt2.Config(
        vocab_size=base.vocab_size, n_positions=base.n_positions,
        d_model=base.d_model, n_layer=base.n_layer, n_head=base.n_head,
        remat=False, attention_impl="dot")
    slots, n_requests, max_new = 8, 32, 32
    max_seq = min(192, base.n_positions)
    buckets = [64]

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(8, 49))).astype(np.int32)
               for _ in range(n_requests)]

    # Serve from an actual committed checkpoint: load path included.
    with tempfile.TemporaryDirectory() as td:
        ctx = core.init(max_length=1, checkpoint_dir=td)
        params = gpt2.init(jax.random.PRNGKey(0), cfg)
        import jax.numpy as jnp

        ctx.checkpoint.save_state(
            {"step": jnp.asarray(1, jnp.int32), "params": params,
             "opt_state": {"count": jnp.zeros((), jnp.int32)}}, 1)
        ctx.checkpoint.wait()
        loaded = load_checkpoint_params(ctx.checkpoint, "trial0-step1")
        ctx.close()

    def run(n_slots, tracing=False):
        engine = ServingEngine(
            loaded, cfg, slots=n_slots, max_seq_len=max_seq,
            prefill_buckets=buckets)
        batcher = ContinuousBatcher(
            engine, queue=AdmissionQueue(n_requests),
            block_manager=BlockManager(
                num_blocks=n_slots * (max_seq // 16), block_size=16),
            idle_wait_s=0.002)
        tracer = None
        if tracing:
            # The production request tracer with its shipper thread
            # running (local sink: no master in this bench, the span
            # build + buffer cost is what's being measured).
            from determined_tpu.serve.tracing import RequestTracer

            tracer = RequestTracer(None, "", sample=1.0,
                                   flush_period_s=0.5).start()
            batcher.tracer = tracer
        batcher.start()  # compiles AOT; excluded from the timed window
        try:
            t0 = time.time()
            reqs = [batcher.submit(Request(p, max_new_tokens=max_new))
                    for p in prompts]
            results = [r.result(timeout=1800) for r in reqs]
            wall = time.time() - t0
            lats = sorted(r["latency_ms"] for r in results)
            stats = batcher.stats()
            return {
                "wall_s": wall,
                "tokens_per_s": stats["generated_tokens"] / wall,
                "p50_ms": lats[len(lats) // 2],
                "p99_ms": lats[min(len(lats) - 1,
                                   int(len(lats) * 0.99))],
                "mean_occupancy": stats["mean_occupancy"],
                "compile": engine.compile_stats,
                "latency": stats["latency"],
                "spans_recorded": tracer.recorded if tracer else 0,
            }
        finally:
            batcher.stop()
            if tracer is not None:
                tracer.stop()

    seq = run(1)        # sequential baseline: one slot = no batching
    cont = run(slots)   # continuous batching
    speedup = cont["tokens_per_s"] / seq["tokens_per_s"]

    detail = {
        "model": f"gpt2-{size}",
        "requests": n_requests,
        "max_new_tokens": max_new,
        "slots": slots,
        "mean_occupancy": cont["mean_occupancy"],
        "sequential_tokens_per_s": round(seq["tokens_per_s"], 1),
        "sequential_p50_ms": round(seq["p50_ms"], 1),
        "wall_s": round(cont["wall_s"], 2),
        "compile_total_s": cont["compile"].get("total_s"),
        "device": None,
    }
    import jax as _jax

    detail["device"] = str(_jax.devices()[0])
    print(json.dumps({
        "metric": "serve_tokens_per_s",
        "value": round(cont["tokens_per_s"], 1),
        "unit": f"tokens/s (gpt2-{size}, {n_requests}-burst x {max_new} "
                f"new tokens, {slots} slots)",
        "vs_baseline": round(speedup, 3),  # sequential feed IS the baseline
        "detail": detail,
    }))
    print(json.dumps({
        "metric": "serve_p50_ms",
        "value": round(cont["p50_ms"], 1),
        "unit": "ms request latency, p50 (lower is better)",
        "vs_baseline": round(seq["p50_ms"] / cont["p50_ms"], 3),
        "detail": {"sequential_p50_ms": round(seq["p50_ms"], 1)},
    }))
    print(json.dumps({
        "metric": "serve_p99_ms",
        "value": round(cont["p99_ms"], 1),
        "unit": "ms request latency, p99 (lower is better)",
        "vs_baseline": round(seq["p99_ms"] / cont["p99_ms"], 3),
        "detail": {"sequential_p99_ms": round(seq["p99_ms"], 1)},
    }))

    # ---- request tracing on/off A/B (ISSUE-12; docs/serving.md "Request
    # latency & SLOs"). Same burst through the 8-slot batcher with the
    # RequestTracer attached (sample=1.0, shipper thread live) vs without;
    # interleaved best-of-2 per arm debiases cache warmth. Gate: tracing
    # costs < 1% tokens/s — span trees are retire-time buffer appends, so
    # steady-state decode executes zero tracing code. The traced arm also
    # yields the TTFT/TPOT/e2e histograms recorded in BENCH.md.
    t_off = [run(slots, tracing=False)]
    t_on = [run(slots, tracing=True)]
    t_off.append(run(slots, tracing=False))
    t_on.append(run(slots, tracing=True))
    best_off = max(t_off, key=lambda r: r["tokens_per_s"])
    best_on = max(t_on, key=lambda r: r["tokens_per_s"])
    overhead_pct = (1.0 - best_on["tokens_per_s"]
                    / best_off["tokens_per_s"]) * 100.0
    lat = best_on["latency"]
    print(json.dumps({
        "metric": "serve_trace_overhead_pct",
        "value": round(overhead_pct, 2),
        "unit": "% tokens/s lost with request tracing on "
                "(gate < 1%; negative = within noise)",
        "vs_baseline": round(
            best_on["tokens_per_s"] / best_off["tokens_per_s"], 4),
        "detail": {
            "gate_passed": overhead_pct < 1.0,
            "on_tokens_per_s": round(best_on["tokens_per_s"], 1),
            "off_tokens_per_s": round(best_off["tokens_per_s"], 1),
            "spans_recorded": best_on["spans_recorded"],
            "ttft_p50_ms": lat["ttft"]["p50_ms"],
            "ttft_p99_ms": lat["ttft"]["p99_ms"],
            "tpot_p50_ms": lat["tpot"]["p50_ms"],
            "tpot_p99_ms": lat["tpot"]["p99_ms"],
            "e2e_p50_ms": lat["e2e"]["p50_ms"],
            "e2e_p99_ms": lat["e2e"]["p99_ms"],
            "queue_wait_p99_ms": lat["queue_wait"]["p99_ms"],
        },
    }))


def serve_fleet_bench() -> None:
    """`make bench-serve-fleet` (docs/serving.md "Deployments &
    autoscaling"): fleet serving through the REAL master router.

    Measures the FLEET TIER — deployment controller + /serve router — on
    a 2-agent devcluster: the SAME client burst runs against target=1 and
    target=2 of one deployment, gating 2-replica routed throughput >=
    1.8x single-replica, then a rolling drain (scale 2 -> 1 mid-burst)
    gates ZERO dropped accepted requests.

    The replicas are slot-capacity-bound with a FIXED per-request service
    time (tests/fixtures/serving/fake_replica.py, the same protocol as
    the real serve task): in production each replica owns its own TPU, so
    per-replica capacity is slots x service-time and replicas scale
    independently. Running two REAL engines on this bench host's shared
    CPU would measure core contention, not the router — `make
    bench-serve` already gates the real single-engine batcher on real
    tokens.
    """
    import os
    import subprocess
    import tempfile
    import threading
    import urllib.request

    REPO = os.path.dirname(os.path.abspath(__file__))
    subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                   check=True, capture_output=True)
    import sys as _sys

    if os.path.join(REPO, "tests") not in _sys.path:
        _sys.path.insert(0, os.path.join(REPO, "tests"))
    from tests.test_platform_e2e import Devcluster

    tmp = tempfile.mkdtemp(prefix="bench_serve_fleet_")
    # 4 slots x 250ms service time per replica = 16 req/s of per-replica
    # capacity, far above the ~10ms/request of Python/HTTP plumbing even
    # on a 1-core bench host — so capacity binds, not host CPU. 16
    # clients oversubscribe one replica ~4x; the only way to 1.8x is the
    # router actually spreading load over replica 2.
    gen_ms = 250
    config = {
        "name": "bench-fleet",
        "entrypoint": "python3 -m tests.fixtures.serving.fake_replica",
        "serving": {
            "model": "gpt2",
            "heartbeat_period_s": 0.3,
            # Autoscaling quiesced (threshold above the signal's ceiling):
            # this bench A/Bs replica counts MANUALLY — the burst's
            # backpressure would otherwise scale the "single" phase up
            # mid-measurement (the autoscaler doing its job).
            "replicas": {"min": 1, "max": 2, "target": 1,
                         "scale_up_threshold": 2.0,
                         "scale_up_after_s": 3600},
        },
        "resources": {"slots_per_trial": 0},
        "environment": {
            "DET_FAKE_GEN_MS": str(gen_ms),
            "DET_FAKE_SLOTS": "4",
            "DET_FAKE_HEARTBEAT_S": "0.3",
        },
    }

    n_requests, max_new, n_clients = 96, 16, 16

    cluster = Devcluster(tmp, os.path.join(REPO, "native", "bin"), slots=1)
    try:
        cluster.start_master()
        cluster.start_agent("fleet-a")
        cluster.start_agent("fleet-b")
        token = cluster.login()
        dep_id = cluster.api("POST", "/api/v1/deployments",
                             {"config": config}, token=token)["id"]

        def _detail():
            return cluster.api("GET", f"/api/v1/deployments/{dep_id}",
                               token=token)["deployment"]

        def _wait_ready(n, timeout=300.0):
            deadline = time.time() + timeout
            while time.time() < deadline:
                d = _detail()
                ready = [r for r in d["replicas"]
                         if r.get("allocation_state") == "RUNNING"
                         and r.get("proxy_address") and not r["retiring"]
                         and 0 <= (r.get("report_age_s") or -1) < 10]
                if len(ready) == n and len(d["replicas"]) == n:
                    return d
                time.sleep(0.3)
            raise TimeoutError(f"never reached {n} ready replicas: {d}")

        def _generate(timeout=120.0):
            req = urllib.request.Request(
                f"{cluster.master_url}/serve/{dep_id}/v1/generate",
                data=json.dumps({"tokens": [5, 9, 17, 3],
                                 "max_new_tokens": max_new,
                                 "delay_ms": gen_ms,
                                 "timeout_s": timeout}).encode(),
                headers={"Content-Type": "application/json",
                         "Authorization": f"Bearer {token}"},
                method="POST")
            with urllib.request.urlopen(req, timeout=timeout + 30) as resp:
                return json.loads(resp.read())

        def burst():
            """n_requests through the router from n_clients threads;
            returns (tokens_per_s, completed, dropped)."""
            done, errors = [], []
            counter = iter(range(n_requests))
            lock = threading.Lock()

            def _client():
                import urllib.error

                while True:
                    with lock:
                        if next(counter, None) is None:
                            return
                    deadline = time.time() + 300
                    while True:
                        try:
                            out = _generate()
                            if len(out.get("tokens", [])) == max_new:
                                done.append(out)
                            else:
                                errors.append(out)
                            break
                        except urllib.error.HTTPError as e:
                            if e.code in (429, 503) and \
                                    time.time() < deadline:
                                # Backpressure, not a drop: honor the
                                # Retry-After hint like the harness
                                # Session does.
                                ra = e.headers.get("Retry-After")
                                time.sleep(min(float(ra or 1), 5.0))
                                continue
                            errors.append(f"HTTP {e.code}")
                            break
                        except Exception as e:  # noqa: BLE001
                            errors.append(str(e)[:200])
                            break

            t0 = time.time()
            threads = [threading.Thread(target=_client)
                       for _ in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            wall = time.time() - t0
            return len(done) * max_new / wall, len(done), errors

        _wait_ready(1)
        burst()  # warm both the replica and the router once, untimed
        single_tps, single_done, single_err = burst()

        cluster.api("POST", f"/api/v1/deployments/{dep_id}/scale",
                    {"target": 2}, token=token)
        _wait_ready(2)
        fleet_tps, fleet_done, fleet_err = burst()

        # Rolling drain under load: scale 2 -> 1 mid-burst; every accepted
        # request must complete (zero dropped).
        drain_result = {}

        def _drain_burst():
            drain_result["r"] = burst()

        loader = threading.Thread(target=_drain_burst)
        loader.start()
        time.sleep(0.5)
        cluster.api("POST", f"/api/v1/deployments/{dep_id}/scale",
                    {"target": 1}, token=token)
        loader.join(timeout=600)
        _, drain_done, drain_err = drain_result["r"]
        deadline = time.time() + 120
        while time.time() < deadline:
            if len(_detail()["replicas"]) == 1:
                break
            time.sleep(0.5)
    finally:
        cluster.stop()

    speedup = fleet_tps / single_tps if single_tps else 0.0
    detail = {
        "replica": f"4 slots x {gen_ms}ms service time (fleet-tier bench; "
                   "see docstring)",
        "requests": n_requests,
        "max_new_tokens": max_new,
        "clients": n_clients,
        "single_tokens_per_s": round(single_tps, 1),
        "single_completed": single_done,
        "fleet_completed": fleet_done,
        "errors": [single_err, fleet_err][:2],
        "drain_completed": drain_done,
        "drain_dropped": len(drain_err),
    }
    print(json.dumps({
        "metric": "serve_fleet_tokens_per_s",
        "value": round(fleet_tps, 1),
        "unit": f"tokens/s routed through /serve (2 replicas, "
                f"{n_requests}-burst x {max_new} new tokens)",
        "vs_baseline": round(speedup, 3),  # single replica IS the baseline
        "detail": detail,
    }))
    print(json.dumps({
        "metric": "serve_fleet_drain_dropped",
        "value": len(drain_err),
        "unit": "requests dropped during a rolling drain under load "
                "(gate: 0)",
        "detail": {"drain_completed": drain_done,
                   "drain_errors": drain_err[:5]},
    }))
    assert not single_err and not fleet_err, (single_err, fleet_err)
    assert len(drain_err) == 0, f"rolling drain dropped: {drain_err[:5]}"
    assert speedup >= 1.8, (
        f"2-replica routed throughput only {speedup:.2f}x single replica "
        f"(gate: 1.8x; {detail})")


def lifecycle_bench() -> None:
    """`make bench-lifecycle` (docs/serving.md "Model lifecycle"): the
    train→serve delivery loop under load on the REAL master.

    Phase 1 — **rolling weight swap under sustained load**: a 2-replica
    deployment serves a continuous client burst while `update` rolls it
    from version 1 to version 2 (spawn-at-new before drain-at-old).
    Gate: ZERO dropped accepted requests, and the deployment ends with
    every replica at v2.

    Phase 2 — **canary fraction fidelity**: a 10% canary on version 3
    takes a counted 200-request burst; the router's deterministic debt
    split must put the OBSERVED canary fraction within ±5 points of the
    configured 0.10 (the acceptance gate), with canary-vs-stable p50/p99
    reported from the per-version latency aggregation.

    Replicas are the fake-replica fixture (slot-capacity-bound, fixed
    service time) for the same reason as bench-serve-fleet: the subsystem
    under test is the master's lifecycle controller + router, and `make
    bench-serve` already gates the real engine.
    """
    import os
    import subprocess
    import tempfile
    import threading
    import urllib.request

    REPO = os.path.dirname(os.path.abspath(__file__))
    subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                   check=True, capture_output=True)
    import sys as _sys

    if os.path.join(REPO, "tests") not in _sys.path:
        _sys.path.insert(0, os.path.join(REPO, "tests"))
    from tests.test_platform_e2e import Devcluster

    tmp = tempfile.mkdtemp(prefix="bench_lifecycle_")
    gen_ms = 100
    config = {
        "name": "bench-lifecycle",
        "entrypoint": "python3 -m tests.fixtures.serving.fake_replica",
        "serving": {
            "model": "gpt2",
            "model_version": "bench:1",
            "heartbeat_period_s": 0.3,
            # Autoscaling quiesced: replica counts move only through the
            # lifecycle verbs under measurement.
            "replicas": {"min": 1, "max": 4, "target": 2,
                         "scale_up_threshold": 2.0,
                         "scale_up_after_s": 3600},
        },
        "resources": {"slots_per_trial": 0},
        "environment": {
            "DET_FAKE_GEN_MS": str(gen_ms),
            "DET_FAKE_SLOTS": "4",
            "DET_FAKE_HEARTBEAT_S": "0.3",
        },
    }
    canary_fraction, canary_n = 0.10, 200

    cluster = Devcluster(tmp, os.path.join(REPO, "native", "bin"), slots=1)
    try:
        cluster.start_master()
        cluster.start_agent("lc-a")
        cluster.start_agent("lc-b")
        token = cluster.login()
        # Registry: three committed versions of model `bench`.
        cluster.api("POST", "/api/v1/models",
                    {"name": "bench", "metadata": {}, "labels": []},
                    token=token)
        for uuid in ("bench-ck-1", "bench-ck-2", "bench-ck-3"):
            cluster.api("POST", "/api/v1/checkpoints",
                        {"uuid": uuid, "state": "COMPLETED"}, token=token)
            cluster.api("POST", "/api/v1/models/bench/versions",
                        {"checkpoint_uuid": uuid}, token=token)
        dep_id = cluster.api("POST", "/api/v1/deployments",
                             {"config": config}, token=token)["id"]

        def _detail():
            return cluster.api("GET", f"/api/v1/deployments/{dep_id}",
                               token=token)["deployment"]

        def _wait(pred, timeout=300.0, what="condition"):
            deadline = time.time() + timeout
            while time.time() < deadline:
                d = _detail()
                if pred(d):
                    return d
                time.sleep(0.3)
            raise TimeoutError(f"never reached {what}: {d}")

        def _ready(d, n):
            live = [r for r in d["replicas"]
                    if r.get("allocation_state") == "RUNNING"
                    and r.get("proxy_address") and not r["retiring"]
                    and 0 <= (r.get("report_age_s") or -1) < 10]
            return len(live) >= n

        def _generate(timeout=120.0):
            req = urllib.request.Request(
                f"{cluster.master_url}/serve/{dep_id}/v1/generate",
                data=json.dumps({"tokens": [5, 9, 17, 3],
                                 "max_new_tokens": 8,
                                 "delay_ms": gen_ms,
                                 "timeout_s": timeout}).encode(),
                headers={"Content-Type": "application/json",
                         "Authorization": f"Bearer {token}"},
                method="POST")
            with urllib.request.urlopen(req, timeout=timeout + 30) as resp:
                return json.loads(resp.read())

        _wait(lambda d: _ready(d, 2), what="2 ready replicas")

        # --- Phase 1: rolling swap under sustained load ---------------
        stop_load = threading.Event()
        done, errors = [], []

        def _loader():
            import urllib.error

            while not stop_load.is_set():
                try:
                    out = _generate()
                    done.append(out.get("model_version", ""))
                except urllib.error.HTTPError as e:
                    if e.code in (429, 503):
                        ra = e.headers.get("Retry-After")
                        time.sleep(min(float(ra or 1), 5.0))
                        continue
                    errors.append(f"HTTP {e.code}")
                except Exception as e:  # noqa: BLE001
                    errors.append(str(e)[:200])

        threads = [threading.Thread(target=_loader) for _ in range(8)]
        for t in threads:
            t.start()
        time.sleep(2.0)  # load established on v1
        t_swap = time.time()
        cluster.api("POST", f"/api/v1/deployments/{dep_id}/update",
                    {"model": "bench", "version": 2}, token=token)
        d = _wait(
            lambda d: (len(d["replicas"]) == 2 and "swap" not in d
                       and all(r["model_version"] == "bench:2"
                               for r in d["replicas"])),
            what="swap complete")
        swap_s = time.time() - t_swap
        time.sleep(2.0)  # load continues on v2
        stop_load.set()
        for t in threads:
            t.join(timeout=120)
        served_v1 = sum(1 for v in done if v == "bench:1")
        served_v2 = sum(1 for v in done if v == "bench:2")

        # --- Phase 2: canary fraction fidelity ------------------------
        cluster.api("POST", f"/api/v1/deployments/{dep_id}/canary",
                    {"model": "bench", "version": 3,
                     "fraction": canary_fraction}, token=token)
        _wait(lambda d: any(
            r.get("canary") and r.get("allocation_state") == "RUNNING"
            and r.get("proxy_address")
            and 0 <= (r.get("report_age_s") or -1) < 10
            for r in d["replicas"]), what="canary replica ready")
        canary_hits = 0
        for _ in range(canary_n):
            out = _generate()
            if out.get("model_version") == "bench:3":
                canary_hits += 1
        observed = canary_hits / canary_n
        d = _detail()
        by_version = {}
        for version, lat in (d.get("latency_by_version") or {}).items():
            e2e = lat.get("e2e") or {}
            by_version[version] = {
                "p50_ms": e2e.get("p50_ms"), "p99_ms": e2e.get("p99_ms"),
                "requests": e2e.get("count")}
        cluster.api("POST", f"/api/v1/deployments/{dep_id}/canary",
                    {"abort": True}, token=token)
    finally:
        cluster.stop()

    detail = {
        "replica": f"4 slots x {gen_ms}ms service time (controller bench; "
                   "see docstring)",
        "swap_seconds": round(swap_s, 2),
        "swap_served_v1": served_v1,
        "swap_served_v2": served_v2,
        "swap_errors": errors[:5],
        "canary_requests": canary_n,
        "canary_hits": canary_hits,
        "latency_by_version_ms": by_version,
    }
    print(json.dumps({
        "metric": "lifecycle_swap_dropped",
        "value": len(errors),
        "unit": "requests dropped during a rolling weight swap under "
                "sustained load (gate: 0)",
        "detail": detail,
    }))
    print(json.dumps({
        "metric": "lifecycle_canary_observed_fraction",
        "value": round(observed, 3),
        "unit": f"observed canary traffic fraction over {canary_n} "
                f"requests (configured {canary_fraction}; gate: within "
                "±0.05)",
        "detail": {"by_version": by_version},
    }))
    assert len(errors) == 0, f"rolling swap dropped: {errors[:5]}"
    assert served_v1 > 0 and served_v2 > 0, detail
    assert abs(observed - canary_fraction) <= 0.05, (
        f"canary observed {observed:.3f} vs configured {canary_fraction} "
        f"(gate ±0.05; {detail})")


def capacity_bench() -> None:
    """`make bench-capacity` (docs/cluster-ops.md "Capacity loop"): the
    closed capacity loop under a diurnal traffic replay.

    One elastic fleet — master + GCP-shaped fake TPU API — where serving
    demand drives MACHINES: ramp up (autoscaler raises replica target →
    replica deficits summon nodes → this bench "boots" each created node
    as a real agent, spot-tiered), plateau, a SPOT-KILL wave (preemption
    notices on every spot agent + out-of-band node delete; replicas drain
    inside the deadline while replacements re-target on-demand), ramp
    down, idle (scale-to-zero drains the last replica, idle nodes are
    deleted — the fleet returns to zero), then a COLD-START burst (the
    router wakes target 0 -> 1, holds the first request within
    cold_start_budget_s, and its trace shows serve.cold_start with
    engine_source=deserialize — the warm-AOT path, never a re-trace).

    Gates: node count demonstrably rises and falls with the replayed
    demand, >= 1 spot agent drains inside its notice deadline, the
    scale-to-zero -> cold-start cycle completes within the budget on the
    warm AOT path, and dropped accepted requests == 0 across the whole
    replay (429/503-with-Retry-After shedding is backpressure, not a
    drop; anything else is)."""
    import os
    import subprocess
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    REPO = os.path.dirname(os.path.abspath(__file__))
    subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                   check=True, capture_output=True)
    import sys as _sys

    for p in (REPO, os.path.join(REPO, "tests")):
        if p not in _sys.path:
            _sys.path.insert(0, p)
    from tests.test_platform_e2e import Devcluster, _wait_http
    from tests.test_provisioner import FakeTpuApi

    tmp = tempfile.mkdtemp(prefix="bench_capacity_")
    fake = FakeTpuApi()
    cold_budget = 60.0
    master_cfg = {
        "agent_timeout_s": 15,
        "provisioner": {
            "type": "gcp",
            "api_base": fake.url + "/v2",
            "project": "p", "zone": "z",
            "slots_per_node": 1,
            "sustain_seconds": 0.4,
            "cooldown_seconds": 0.8,
            "idle_seconds": 3,
            "reconcile_seconds": 0.3,
            "demand_hysteresis_seconds": 2,
            "spot": True,
        },
    }
    gen_ms = 200
    dep_cfg = {
        "name": "diurnal",
        "entrypoint": "python3 -m tests.fixtures.serving.fake_replica",
        "serving": {
            "model": "gpt2",
            "heartbeat_period_s": 0.3,
            "replicas": {
                "min": 0, "max": 4, "target": 1,
                "on_demand_floor": 1,
                "cold_start_budget_s": cold_budget,
                "scale_up_after_s": 1.0,
                "scale_down_after_s": 2.5,
                "scale_up_threshold": 0.5,
                "scale_down_threshold": 0.1,
            },
        },
        "resources": {"slots": 1},
        "environment": {
            "DET_FAKE_GEN_MS": str(gen_ms),
            "DET_FAKE_SLOTS": "2",
            "DET_FAKE_HEARTBEAT_S": "0.3",
        },
    }

    cluster = Devcluster(tmp, os.path.join(REPO, "native", "bin"), slots=1)
    cfg_path = os.path.join(tmp, "master.json")
    with open(cfg_path, "w") as f:
        json.dump(master_cfg, f)
    cluster.master = subprocess.Popen(
        [os.path.join(cluster.binaries, "determined-master"),
         "--config", cfg_path, "--port", str(cluster.port),
         "--host", "127.0.0.1", "--db", cluster.db_path],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    _wait_http(cluster.master_url + "/api/v1/master")

    agents = {}          # node name -> Popen
    node_counts = []     # (t, tracked agents alive) samples
    dropped = []         # non-backpressure request failures
    completed = [0]
    stop_all = threading.Event()
    token = cluster.login()
    admin = cluster.login("admin")

    def boot_watcher():
        """Play the cloud: every node the provisioner creates 'boots' as
        a real agent a moment later. Every SECOND node is spot-tiered
        (preemptible), so the deployment floor has on-demand capacity to
        live on and the surplus has spot to be reclaimed from."""
        while not stop_all.is_set():
            for i, create in enumerate(list(fake.creates)):
                name = create["name"]
                if name in agents or name not in fake.node_names():
                    continue
                spot = i % 2 == 1
                env = dict(cluster.env)
                if spot:
                    env["DET_AGENT_PREEMPTIBLE"] = "1"
                agents[name] = subprocess.Popen(
                    [os.path.join(cluster.binaries, "determined-agent"),
                     "--master-url", cluster.master_url, "--id", name,
                     "--slots", "1", "--slot-type", "cpu",
                     "--addr", "127.0.0.1",
                     "--work-root", os.path.join(tmp, f"agent-{name}"),
                     "--token-file", cluster.db_path + ".agent_token"],
                    env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT)
            time.sleep(0.2)

    def sample_nodes():
        while not stop_all.is_set():
            node_counts.append((time.time(), len(fake.node_names())))
            time.sleep(0.5)

    def one_request(timeout=cold_budget + 30):
        req = urllib.request.Request(
            f"{cluster.master_url}/serve/diurnal/v1/generate",
            data=json.dumps({"tokens": [5, 9, 17],
                             "max_new_tokens": 8,
                             "delay_ms": gen_ms}).encode(),
            headers={"Content-Type": "application/json",
                     "Authorization": f"Bearer {token}"},
            method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            out = json.loads(resp.read())
            return resp.headers.get("X-Request-Id"), out

    def client_loop(rate_hz):
        """Closed-loop client at ~rate_hz; 429/503 honor Retry-After
        (backpressure), anything else counts as a DROP."""
        deadline_absent = object()
        while not stop_all.is_set() and rate_hz[0] > 0:
            t0 = time.time()
            try:
                one_request(timeout=30)
                completed[0] += 1
            except urllib.error.HTTPError as e:
                if e.code in (429, 503):
                    ra = e.headers.get("Retry-After", deadline_absent)
                    if ra is deadline_absent:
                        dropped.append(f"{e.code} without Retry-After")
                    else:
                        time.sleep(min(float(ra), 3.0))
                else:
                    dropped.append(f"HTTP {e.code}")
            except Exception as e:  # noqa: BLE001
                dropped.append(str(e)[:160])
            sleep = 1.0 / max(rate_hz[0], 0.1) - (time.time() - t0)
            if sleep > 0:
                time.sleep(sleep)

    threading.Thread(target=boot_watcher, daemon=True).start()
    threading.Thread(target=sample_nodes, daemon=True).start()

    phase_log = []
    spot_drained_in_deadline = False
    cold = {}
    try:
        dep = cluster.api("POST", "/api/v1/deployments",
                          {"config": dep_cfg}, token=token)
        assert dep["id"]

        def detail():
            return cluster.api("GET", f"/api/v1/deployments/{dep['id']}",
                               token=token)["deployment"]

        def live_replicas(d=None):
            d = d or detail()
            return [r for r in d["replicas"]
                    if not r["retiring"]
                    and r.get("allocation_state") == "RUNNING"
                    and r.get("proxy_address")]

        def wait_for(cond, timeout, what):
            deadline = time.time() + timeout
            while time.time() < deadline:
                v = cond()
                if v:
                    return v
                time.sleep(0.3)
            raise TimeoutError(f"capacity replay: {what}")

        # --- ramp up -------------------------------------------------
        phase_log.append(("ramp_up", time.time()))
        wait_for(lambda: live_replicas() or None, 90,
                 "first replica never came up")
        rate = [2.0]
        clients = [threading.Thread(target=client_loop, args=(rate,),
                                    daemon=True) for _ in range(8)]
        for c in clients:
            c.start()
        # Backpressure raises the target; deficits summon nodes.
        wait_for(lambda: len(live_replicas()) >= 3 or None, 120,
                 "autoscaler never grew the fleet under load")
        peak_nodes = len(fake.node_names())

        # --- plateau -------------------------------------------------
        phase_log.append(("plateau", time.time()))
        time.sleep(5)

        # --- spot-kill wave -----------------------------------------
        phase_log.append(("spot_kill", time.time()))
        spot_agents = [a["id"] for a in cluster.api(
            "GET", "/api/v1/agents", token=token)["agents"]
            if a["preemptible"] and a["alive"]]
        assert spot_agents, "replay never placed capacity on spot"
        kill_deadline_s = 20.0
        t_notice = time.time()
        for aid in spot_agents:
            cluster.api("POST", f"/api/v1/agents/{aid}/preempt_notice",
                        {"deadline_seconds": kill_deadline_s,
                         "reason": "spot_preemption"}, token=admin)

        def spot_drained():
            d = detail()
            draining = [r for r in d["replicas"]
                        if r.get("agent") in spot_agents
                        and r.get("allocation_state") == "RUNNING"]
            return not draining or None

        wait_for(spot_drained, kill_deadline_s + 10,
                 "spot replicas never finished draining")
        spot_drained_in_deadline = \
            time.time() - t_notice <= kill_deadline_s + 5
        # The nodes actually vanish (the cloud reclaims them).
        for aid in spot_agents:
            fake.interrupt(aid)
            p = agents.get(aid)
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
        # Service continues on on-demand capacity.
        wait_for(lambda: live_replicas() or None, 60,
                 "no live replica after the spot wave")

        # --- ramp down → idle → scale-to-zero ------------------------
        phase_log.append(("ramp_down", time.time()))
        rate[0] = 0
        stop_all_clients = time.time()
        for c in clients:
            c.join(timeout=40)

        def fleet_zero():
            d = detail()
            return (int(d["target_replicas"]) == 0 and not d["replicas"]
                    and not fake.node_names()) or None

        wait_for(fleet_zero, 150,
                 "fleet never scaled to zero (replicas + nodes)")
        phase_log.append(("zero", time.time()))
        trough_nodes = len(fake.node_names())

        # --- cold-start burst ---------------------------------------
        phase_log.append(("cold_burst", time.time()))
        t_cold = time.time()
        rid, out = one_request()   # held through the wake, never shed
        cold_wall_s = time.time() - t_cold
        completed[0] += 1
        trace = cluster.api(
            "GET",
            f"/api/v1/deployments/{dep['id']}/requests/{rid}/trace",
            token=token)
        spans = {s["name"]: s for s in trace["spans"]}
        cold_span = spans.get("serve.cold_start")
        cold = {
            "wall_s": round(cold_wall_s, 2),
            "within_budget": cold_wall_s <= cold_budget,
            "span_present": cold_span is not None,
            "engine_source": (cold_span or {}).get(
                "attrs", {}).get("engine_source"),
        }
        # A few follow-ups ride the now-warm deployment.
        for _ in range(4):
            one_request(timeout=30)
            completed[0] += 1
    finally:
        stop_all.set()
        for p in agents.values():
            if p.poll() is None:
                p.kill()
                p.wait()
        cluster.stop()
        fake.stop()

    counts = [n for _, n in node_counts]
    detail_out = {
        "phases": [(name, round(t - phase_log[0][1], 1))
                   for name, t in phase_log],
        "node_count_peak": max(counts) if counts else 0,
        "node_count_final": trough_nodes,
        "nodes_created_total": len(fake.creates),
        "completed_requests": completed[0],
        "dropped": dropped[:10],
        "spot_agents_killed": len(spot_agents),
        "spot_drained_in_deadline": spot_drained_in_deadline,
        "cold_start": cold,
        "idle_window_s": round(time.time() - stop_all_clients, 1),
    }
    print(json.dumps({
        "metric": "capacity_diurnal_dropped",
        "value": len(dropped),
        "unit": "accepted requests dropped across the replay (gate: 0)",
        "detail": detail_out,
    }))
    print(json.dumps({
        "metric": "capacity_cold_start_s",
        "value": cold.get("wall_s"),
        "unit": f"scale-from-zero wake to first response "
                f"(gate: <= {cold_budget}s, warm AOT)",
        "detail": cold,
    }))
    assert max(counts) >= 3, f"fleet never grew: peak={max(counts)}"
    assert trough_nodes == 0, "fleet never shrank back to zero nodes"
    assert spot_drained_in_deadline, "spot wave missed its drain deadline"
    assert not dropped, f"dropped accepted requests: {dropped[:5]}"
    assert cold["within_budget"] and cold["span_present"], cold
    assert cold["engine_source"] == "deserialize", cold
    assert peak_nodes >= 2


def pp_compile_check() -> None:
    """AOT-compile the bf16 pipeline-parallel train step against a v5e 2x2
    TPU topology (deviceless — libtpu alone suffices, no chip, any default
    backend).

    Why: on the CPU backend the bf16 partial-manual shard_map gradient trips
    an XLA partitioner crash, so CPU tests run the PP path in f32
    (models/gpt2.py apply_pipelined). This check runs the REAL TPU
    partitioner over the bf16 graph, closing that blind spot without
    needing 8 physical chips.
    """
    import jax
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from determined_tpu.models import gpt2
    from determined_tpu.parallel.mesh import AXIS_ORDER, MeshConfig
    from determined_tpu.train import create_train_state, make_train_step

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    shape = MeshConfig(data=2, pipeline=2).resolve(len(topo.devices)).sizes()
    mesh = Mesh(np.asarray(topo.devices).reshape(shape), AXIS_ORDER)

    cfg = gpt2.Config.tiny()
    # apply_pipelined picks its compute dtype from the mesh's devices
    # (on_tpu): the topology's TPU devices give the bf16 graph this check
    # exists for, whatever backend the host process defaults to.
    assert cfg.dtype == jax.numpy.bfloat16
    tx = optax.adamw(3e-4)

    def loss(p, b, r):
        return gpt2.loss_fn_pipelined(p, b, cfg, mesh, num_microbatches=4)

    step = make_train_step(loss, tx, mesh=mesh)
    key = jax.random.PRNGKey(0)
    # Ambient mesh must be the ABSTRACT one: a concrete topology mesh would
    # route eager ops at devices this host doesn't have.
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        state = jax.eval_shape(
            lambda r: create_train_state(lambda rr: gpt2.init(rr, cfg), tx, r),
            key,
        )
    repl = NamedSharding(mesh, PartitionSpec())
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl)
        if hasattr(x, "shape") else x,
        state,
    )
    batch = {
        "tokens": jax.ShapeDtypeStruct(
            (8, 17), np.int32,
            sharding=NamedSharding(mesh, PartitionSpec(("data", "fsdp"))),
        )
    }
    rng = jax.ShapeDtypeStruct((2,), np.uint32, sharding=repl)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        compiled = jax.jit(step).lower(state, batch, rng).compile()
    print(json.dumps({
        "check": "pp_bf16_tpu_compile",
        "ok": True,
        "topology": "v5e:2x2",
        "mesh": dict(zip(AXIS_ORDER, shape)),
        "flops": compiled.cost_analysis().get("flops", 0),
    }))


SECTIONS = {
    "gpt2": gpt2_bench,
    "resnet": lambda: __import__("bench_resnet").main(),
    "asha": lambda: __import__("bench_asha").main(),
    "input": input_pipeline_bench,
    "train_attn": train_attn_bench,
    "serve": serve_bench,
    "serve_fleet": serve_fleet_bench,
    "lifecycle": lifecycle_bench,
    "capacity": capacity_bench,
    "elastic": elastic_bench,
    "trace": trace_bench,
    "compile": compile_bench,
}


def main() -> int:
    if "--only" in sys.argv:
        name = sys.argv[sys.argv.index("--only") + 1]
        if name not in SECTIONS:
            print(f"unknown section {name!r}; one of {sorted(SECTIONS)}",
                  file=sys.stderr)
            return 2
        try:
            SECTIONS[name]()
        except Exception as e:
            print(json.dumps({"metric": name, "error": str(e)[:500]}))
            return 1
        return 0
    # Every section in a child of its own; this parent stays off jax, so
    # it never holds the chip a section (or that section's own children —
    # bench_asha's agent-launched trials) needs.
    import subprocess

    rc = 0
    for name in SECTIONS:
        # a broken section must not hide the others
        if subprocess.run([sys.executable, __file__, "--only", name]
                          ).returncode != 0:
            rc = 1
    return rc


if __name__ == "__main__":
    if "--pp-compile-check" in sys.argv:
        pp_compile_check()
        sys.exit(0)
    sys.exit(main())
