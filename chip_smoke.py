#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on a TPU.

Drives the platform's main path once, through the entry points a user
calls, at the full width of GPT-2 124M (d 768, 12 layers, 12 heads,
V 50257; random weights from a seed), on every chip jax finds:

  kernels  both Pallas kernels against their jnp references at the
           smoke's shapes (flash fwd+bwd; paged decode), on the device.
  train    `Trainer(GPT2Trial).fit` — the examples/gpt2 trial, seq 1024,
           batch 8 per chip — through `core.init` for a few optimizer
           steps, ending in a committed checkpoint.
  serve    `serve.task.build_replica` + `ServingServer` (what
           `python -m determined_tpu.serve` runs) on that checkpoint, bf16,
           `attention_impl: auto`, max_seq_len 1024: a handful of HTTP
           /v1/generate requests of which two share a prompt prefix, then
           the same requests replayed on `attention_impl: reference`.

One JSON line per phase with the facts that show the device path was
taken (platform, resolved attention impl, `tpu_custom_call` counts in the
compiled executables, losses, prefix hits, peak HBM, compile seconds and
whether the persistent cache was warm), then a summary line
`{"phase": "summary", "ok": ..., "phases": [...], "claim": null}`; the
last line of stdout is the result, with exactly these keys and the device
as jax reports it:

  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Exit 0 only when every phase passed. No accelerator → exit 3 and no
result. Everything runs in THIS process — a chip belongs to one process
at a time — and nothing is left running. No speed is claimed: the
compile and wall seconds printed are set-up facts, not benchmark numbers.

Other modes (builder's tools, not what the driver runs):
  --cpu-tiny        explicit sandbox dry run: tiny model on the CPU
                    (prints platform cpu, skips the kernel phase — nothing
                    here asks for the Pallas interpreter);
  --mesh data=2,fsdp=2   the train phase's mesh (default: fsdp over up to
                    four chips, data over the rest);
  --phases a,b      a subset of kernels,train,serve;
  --break PHASE     raise inside PHASE: shows a failed phase fails the run;
  --agent [N]       instead of the phases: N (default 1) concurrent
                    `det experiment create examples/gpt2/config.yaml
                    --follow` through a real master and an agent with
                    auto-detected tpu slots. This parent never imports jax
                    (the trials need the chips).
"""

import argparse
import functools
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "examples", "gpt2"))

STEPS = 6                     # optimizer steps of the train phase
BATCH_PER_CHIP = 8
# Both kernels keep fp32 softmax statistics and accumulate in fp32; what
# separates them from the references on bf16 inputs is where a bf16
# rounding lands. Error is measured against the reference's largest
# magnitude (max|a-b| / max|b|): bf16 has 8 bits of mantissa (2^-8 =
# 0.4%), and a few roundings deep that is ~1-2%.
FWD_TOL, BWD_TOL = 2e-2, 4e-2
# A fresh GPT-2 on uniform random tokens sits just above ln V (the tied
# embedding leans towards "repeat the current token": 10.98 = ln V + 0.16
# measured on the CPU at full width), and six warm-up steps at lr ~1e-5
# do not move it.
LOSS_BAND = (-0.5, 0.5)


class PhaseFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def device_facts():
    import importlib.metadata as md

    import jax
    import jaxlib

    devs = jax.devices()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu}


def memory_facts():
    """Per-device HBM from memory_stats() (None where the backend has
    none, i.e. the CPU)."""
    import jax

    out = []
    for d in jax.devices():
        s = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": s.get("bytes_in_use"),
                    "peak_bytes_in_use": s.get("peak_bytes_in_use"),
                    "bytes_limit": s.get("bytes_limit")})
    return out


def rel_err(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


# ------------------------------------------------------------------ kernels


def phase_kernels(args, out):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from determined_tpu.ops.flash_attention import (
        pallas_flash_attention, reference_attention)
    from determined_tpu.ops.paged_attention import (
        paged_attention_pallas, paged_attention_reference)

    if args.cpu_tiny:
        out["skipped"] = ("not a TPU: the kernels compile through Mosaic "
                          "only, and this script never asks for the "
                          "interpreter")
        return
    out["tolerance"] = {"fwd": FWD_TOL, "bwd": BWD_TOL,
                        "metric": "max|a-b| / max|b|, bf16 inputs"}
    # flash: the train phase's per-chip attention shape.
    b, s, h, d = BATCH_PER_CHIP, 1024, 12, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
               for kk in ks)

    def grads(attend):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    for bf16 in (False, True):
        kern = lambda q, k, v: pallas_flash_attention(  # noqa: E731
            q, k, v, True, bf16)
        ref = lambda q, k, v: reference_attention(  # noqa: E731
            q, k, v, causal=True, bf16=bf16)
        fwd = rel_err(jax.jit(kern)(q, k, v), jax.jit(ref)(q, k, v))
        bwd = max(rel_err(a, r) for a, r in
                  zip(grads(kern)(q, k, v), grads(ref)(q, k, v)))
        out[f"flash_bf16probs_{bf16}"] = {"fwd_rel_err": round(fwd, 5),
                                          "bwd_rel_err": round(bwd, 5)}
        check(fwd <= FWD_TOL and bwd <= BWD_TOL,
              f"flash kernel vs reference_attention q{(b, s, h, d)} "
              f"attention_bf16={bf16}: fwd {fwd:.4f} (tol {FWD_TOL}), "
              f"bwd {bwd:.4f} (tol {BWD_TOL})")

    # paged decode: the serve phase's geometry, ragged positions on both
    # sides of a 128-token span's edge, an inactive (all-trash) slot in
    # the middle and at the end, the second of three layers of a pool.
    slots, bs, mb = 8, 16, 1024 // 16
    rng = np.random.default_rng(1)
    pool = (3, slots * mb + 1, bs, h * d)
    q = jnp.asarray(rng.normal(size=(slots, h, d)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
    tbl = rng.permutation(slots * mb).reshape(slots, mb).astype(np.int32)
    idle = [3, 7]
    tbl[idle] = slots * mb
    pos = np.array([0, 5, 127, 0, 128, 777, 1023, 0], np.int32)
    layer = jnp.int32(1)
    got, want = (np.asarray(jax.jit(attend)(q, kp, vp, layer, tbl, pos),
                            np.float32)
                 for attend in (paged_attention_pallas,
                                paged_attention_reference))
    live = [lane for lane in range(slots) if lane not in idle]
    err = rel_err(got[live], want[live])
    out["paged_decode"] = {"rel_err": round(err, 5)}
    check(not got[idle].any(),
          "paged decode kernel: an idle lane's row is not zeros")
    check(err <= FWD_TOL,
          f"paged decode kernel vs paged_attention_reference slots {slots} "
          f"H {h} Dh {d} block {bs}: {err:.4f} (tol {FWD_TOL})")

    # paged decode with shared K/V heads and the recurrent-state update,
    # at serve-h1-decode's geometry: 64 lanes, 20 query heads over 4 K/V
    # heads of 128; 32 state heads of 256 x 128 in 2 groups, float32;
    # idle lanes between live ones and at the end, a middle layer.
    from determined_tpu.ops.ssm_state import (ssm_state_reference,
                                              ssm_state_update)

    slots, hq, hkv, d = 64, 20, 4, 128
    idle = [3, 17, 62, 63]
    live = np.ones(slots, bool)
    live[idle] = False
    pool = (3, slots * 40 + 1, bs, hkv * d)
    q = jnp.asarray(rng.normal(size=(slots, hq, d)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
    tbl = rng.permutation(slots * 40).reshape(slots, 40).astype(np.int32)
    tbl[idle] = slots * 40
    pos = np.where(live, rng.integers(0, 640, slots), 0).astype(np.int32)
    got, want = (np.asarray(jax.jit(attend)(q, kp, vp, layer, tbl, pos),
                            np.float32)
                 for attend in (paged_attention_pallas,
                                paged_attention_reference))
    err = rel_err(got[live], want[live])
    out["paged_decode_grouped"] = {"rel_err": round(err, 5)}
    check(not got[idle].any() and err <= FWD_TOL,
          f"grouped paged decode kernel vs reference, {hq} query heads "
          f"over {hkv} K/V heads of {d}: {err:.4f} (tol {FWD_TOL})")

    heads, groups, n = 32, 2, 256
    x = jnp.asarray(rng.normal(size=(slots, heads, d)), jnp.bfloat16)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (slots, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(slots, groups, n)), jnp.bfloat16)
            for _ in range(2))
    state = jnp.asarray(rng.normal(size=(3, slots, heads, n, d)),
                        jnp.float32)
    want_pool, want_y = jax.jit(ssm_state_reference)(
        x, dt, b, c, state, layer, live, a)
    before = np.asarray(state[:, idle])
    got_pool, got_y = jax.jit(ssm_state_update, donate_argnums=(4,))(
        x, dt, b, c, state, layer, live, a)
    err = max(rel_err(got_pool, want_pool), rel_err(got_y, want_y))
    out["ssm_state"] = {"rel_err": round(err, 7)}
    check(np.array_equal(np.asarray(got_pool[:, idle]), before),
          "state kernel: an idle lane's state changed")
    check(err <= 1e-5,
          f"state kernel vs ssm_state_reference, {slots} lanes of {heads} "
          f"x {n} x {d} float32: {err:.2e} (tol 1e-5)")

    # the latent decode kernel and the expert layer's grouped matmul at
    # GLM-4.7-Flash's published widths: 20 heads against a 640-lane latent row
    # (512 + 64) at contexts up to 3,584 tokens, idle lanes among live
    # ones; 256 assignments over 64 experts of 2,048 x 1,536, top-4.
    from determined_tpu.ops import mla_attention as mla
    from determined_tpu.ops import moe

    slots, heads, rank, rope, mb = 64, 20, 512, 64, 224
    row = mla.latent_row(rank, rope)
    idle = [3, 17, 62, 63]
    live = np.ones(slots, bool)
    live[idle] = False
    latents = np.zeros((2, slots * mb + 1, bs, row), np.float32)
    latents[..., :rank + rope] = rng.normal(
        size=latents.shape[:-1] + (rank + rope,))
    latents = jnp.asarray(latents, jnp.bfloat16)
    tbl = rng.permutation(slots * mb).reshape(slots, mb).astype(np.int32)
    tbl[idle] = slots * mb
    pos = np.where(live, rng.integers(2048, 3584, slots), 0).astype(np.int32)
    pos[:3] = (0, 127, 3583)
    q = mla.absorbed_query(
        jnp.asarray(rng.normal(size=(slots, heads, rank)), jnp.bfloat16),
        jnp.asarray(rng.normal(size=(slots, heads, rope)), jnp.bfloat16),
        row)
    scale = 256 ** -0.5
    got, want = (np.asarray(
        jax.jit(attend, static_argnums=(5, 6))(
            q, latents, layer, tbl, pos, rank, scale), np.float32)
        for attend in (mla.mla_decode_attention,
                       mla.mla_attention_reference))
    err = rel_err(got[live], want[live])
    out["mla_decode"] = {"rel_err": round(err, 5)}
    check(not got[idle].any() and err <= FWD_TOL,
          f"latent decode kernel vs mla_attention_reference, {heads} heads "
          f"x {row} lanes at contexts to 3,584: {err:.4f} (tol {FWD_TOL})")

    experts, d_model, width, top_k = 64, 2048, 1536, 4
    x = jnp.asarray(rng.normal(size=(slots, d_model)), jnp.bfloat16)
    layer_params = {
        "router": jnp.asarray(rng.normal(size=(d_model, experts)) * 0.02,
                              jnp.bfloat16),
        "router_bias": jnp.asarray(rng.normal(size=(experts,)) * 0.02,
                                   jnp.bfloat16),
        "w13": jnp.asarray(rng.normal(size=(2, experts, d_model, 2 * width))
                           * 0.02, jnp.bfloat16),
        "w2": jnp.asarray(rng.normal(size=(2, experts, width, d_model))
                          * 0.02, jnp.bfloat16)}
    got, want = (jax.jit(functools.partial(
        moe.dropless_moe, top_k=top_k, routed_scaling_factor=1.8,
        impl=impl))(x, layer_params, layer=layer)
        for impl in ("pallas", "reference"))
    err = rel_err(got[0], want[0])
    out["moe_grouped_matmul"] = {"rel_err": round(err, 5),
                                 "assignments": int(got[1].sum())}
    check(np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
          and int(got[1].sum()) == slots * top_k and err <= FWD_TOL,
          f"dropless expert layer, kernel vs lax.ragged_dot, {slots * top_k}"
          f" assignments over {experts} experts of {d_model} x {width}: "
          f"{err:.4f} (tol {FWD_TOL})")


# -------------------------------------------------------------------- train


def default_mesh(n):
    fsdp = 4 if n % 4 == 0 else n
    return {"data": n // fsdp, "fsdp": fsdp}


def phase_train(args, ckpt_dir, out):
    import jax
    from model_def import GPT2Trial

    from determined_tpu import core
    from determined_tpu.compile.runtime import snapshot_cache_dir
    from determined_tpu.train import Trainer
    from determined_tpu.train.trial import TrialContext

    n = len(jax.devices())
    mesh = args.mesh or default_mesh(n)
    hparams = {
        "model_size": "tiny" if args.cpu_tiny else "small",
        "seq_len": 128 if args.cpu_tiny else 1024,
        "global_batch_size": BATCH_PER_CHIP * n,
        "attention_impl": "flash",      # what examples/gpt2/*.yaml ship
        "mesh": mesh,
    }
    cache_before = len(snapshot_cache_dir(args.cache_dir))
    ctx = core.init(max_length=STEPS, checkpoint_dir=ckpt_dir)
    try:
        trial = GPT2Trial(TrialContext(hparams=hparams, core_context=ctx,
                                       n_devices=n))
        trainer = Trainer(trial, core_context=ctx)
        t0 = time.monotonic()
        state = trainer.fit(report_period=2)
        wall = time.monotonic() - t0
        reports = ctx.train.local_training_metrics
        losses = [float(r["metrics"]["loss"]) for r in reports
                  if "loss" in r["metrics"]]
        compile_ms = sum(float(r["metrics"].get("compile_ms", 0.0))
                         for r in reports)
        ckpts = ctx.checkpoint.lineage()
        # The compiled step, again: a persistent-cache hit by now.
        with jax.sharding.set_mesh(trainer.mesh):
            batch = next(iter(trial.build_training_data()))
            hlo = trainer._train_step.lower(
                trainer.state, batch, jax.random.PRNGKey(0)).compile(
                ).as_text()
        param_spec = str(state.params["blocks"]["qkv"]["kernel"].sharding.spec)
        mem = memory_facts()
    finally:
        ctx.close()
    vocab = trial.cfg.vocab_size
    out.update({
        "mesh": mesh, "hparams": {k: hparams[k] for k in
                                  ("model_size", "seq_len",
                                   "global_batch_size")},
        "steps": int(jax.device_get(state.step)),
        "attention_impl": trainer._attention_impl,
        "tpu_custom_calls": hlo.count("tpu_custom_call"),
        "losses": [round(x, 4) for x in losses],
        "ln_vocab": round(math.log(vocab), 4),
        "checkpoint": ckpts[0] if ckpts else None,
        "compile_s": round(compile_ms / 1e3, 2),
        "fit_wall_s": round(wall, 2),
        "cache_entries_before": cache_before,
        "qkv_kernel_spec": param_spec,
        "memory": mem,
    })
    check(out["steps"] == STEPS, f"ran {out['steps']} steps, wanted {STEPS}")
    check(losses and all(math.isfinite(x) for x in losses),
          f"non-finite loss: {losses}")
    lo, hi = (math.log(vocab) + d for d in LOSS_BAND)
    check(all(lo <= x <= hi for x in losses),
          f"loss outside [{lo:.2f}, {hi:.2f}] (ln V {math.log(vocab):.2f} "
          f"{LOSS_BAND[0]:+}/{LOSS_BAND[1]:+}): {losses}")
    check(ckpts, "no COMPLETED checkpoint after fit")
    if not args.cpu_tiny:
        check(out["attention_impl"] == "pallas",
              f"Trainer resolved attention_impl {out['attention_impl']!r}")
        check(out["tpu_custom_calls"] >= 3,
              f"{out['tpu_custom_calls']} tpu_custom_call in the compiled "
              "train step (fwd + dq + dkv kernels expected)")
        if n > 1:
            used = [m["bytes_in_use"] for m in mem]
            check(min(used) > 0.5 * max(used),
                  f"state not spread over the chips: bytes_in_use {used}")


# -------------------------------------------------------------------- serve


def post(url, body, timeout=300):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.load(r)


def get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.load(r)


def serve_once(args, ckpt_dir, attention_impl, prompts, max_new):
    """One replica, the task entrypoint's way; returns (tokens, stats,
    decode HLO text)."""
    from determined_tpu.serve.http import ServingServer
    from determined_tpu.serve.task import build_replica

    tiny = args.cpu_tiny
    config = {
        "serving": {
            "checkpoint": "latest", "trial_id": 0, "model": "gpt2",
            "model_config": {"model_size": "tiny" if tiny else "small",
                             "seq_len": 128 if tiny else 1024,
                             "dtype": "bfloat16"},
            "max_batch_size": 8,
            "max_seq_len": 128 if tiny else 1024,
            "prefill_buckets": [64, 128],
            "kv_block_size": 16,
            "prefix_cache": True,
            "attention_impl": attention_impl,
        },
        "checkpoint_storage": {"type": "shared_fs", "host_path": ckpt_dir},
    }
    engine, batcher = build_replica(config)
    batcher.start()                      # AOT-compiles before admitting
    server = ServingServer(batcher, port=0)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        tokens = [post(base + "/v1/generate",
                       {"tokens": p, "max_new_tokens": max_new,
                        "temperature": 0.0})["tokens"] for p in prompts]
        stats = get(base + "/v1/stats")
        hlo = engine._compiled_decode.as_text()
    finally:
        server.stop()
        batcher.stop()
    return tokens, stats, hlo


def phase_serve(args, ckpt_dir, out):
    import numpy as np

    vocab = 512 if args.cpu_tiny else 50257
    rng = np.random.default_rng(2)
    shared = rng.integers(0, vocab, 32).tolist()    # two full KV blocks
    prompts = [
        shared + rng.integers(0, vocab, 9).tolist(),
        shared + rng.integers(0, vocab, 14).tolist(),   # prefix hit
        rng.integers(0, vocab, 5).tolist(),
        rng.integers(0, vocab, 61).tolist(),
        rng.integers(0, vocab, 100).tolist(),
    ]
    max_new = 12
    tokens, stats, hlo = serve_once(args, ckpt_dir, "auto", prompts, max_new)
    ref_tokens, ref_stats, _ = serve_once(
        args, ckpt_dir, "reference", prompts, max_new)
    eng = stats["engine"]
    out.update({
        "attention_impl": eng["attention_impl"],
        "replay_attention_impl": ref_stats["engine"]["attention_impl"],
        "decode_tpu_custom_calls": hlo.count("tpu_custom_call"),
        "requests": len(prompts),
        "generated": [len(t) for t in tokens],
        "tokens_equal_reference": tokens == ref_tokens,
        "first_tokens": [t[:4] for t in tokens],
        "prefix_hit_tokens": stats["kv_blocks"]["prefix_hit_tokens"],
        "decode_steps": eng["decode_steps"],
        "compile_s": eng["compile"].get("total_s"),
        "replay_compile_s": ref_stats["engine"]["compile"].get("total_s"),
        "max_seq_len": eng["max_seq_len"],
        "memory": memory_facts(),
    })
    check(all(n == max_new for n in out["generated"]),
          f"generated {out['generated']} tokens, wanted {max_new} each")
    check(out["tokens_equal_reference"],
          f"kernel-served tokens differ from the reference replay: "
          f"{tokens} vs {ref_tokens}")
    check(out["prefix_hit_tokens"] > 0, "no prefix-cache hit")
    check(out["replay_attention_impl"] == "reference", "replay not reference")
    if not args.cpu_tiny:
        check(out["attention_impl"] == "pallas",
              f"/v1/stats attention_impl {out['attention_impl']!r}")
        check(out["decode_tpu_custom_calls"] >= 1,
              "no tpu_custom_call in the decode executable")


# -------------------------------------------------------------------- agent


def wait_http(url, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            return urllib.request.urlopen(url, timeout=2).read()
        except OSError:
            time.sleep(0.2)
    raise TimeoutError(url)


def agent_slots(url, agent_proc, timeout=30):
    """The slots the agent registered ([{id, type}, ...]); raises when it
    exits instead (--slot-type tpu with no chip found)."""
    token = post(url + "/api/v1/auth/login",
                 {"username": "determined", "password": ""})["token"]
    deadline = time.time() + timeout
    while time.time() < deadline:
        if agent_proc.poll() is not None:
            raise PhaseFailed(f"agent exited {agent_proc.returncode}")
        req = urllib.request.Request(
            url + "/api/v1/agents",
            headers={"Authorization": f"Bearer {token}"})
        with urllib.request.urlopen(req, timeout=10) as r:
            agents = json.load(r)["agents"]
        if agents and agents[0].get("alive"):
            return agents[0].get("slots")
        time.sleep(0.3)
    raise PhaseFailed("agent did not register")


def child_pids(pid):
    """Direct children of `pid` (Linux /proc)."""
    out = set()
    try:
        for tid in os.listdir(f"/proc/{pid}/task"):
            with open(f"/proc/{pid}/task/{tid}/children") as f:
                out.update(int(c) for c in f.read().split())
    except OSError:      # already gone
        pass
    return out


def run_agent_check(n_trials):
    """master + agent (auto-detected slots) + N concurrent 1-slot
    experiments through the CLI. The parent stays off jax."""
    import socket

    assert "jax" not in sys.modules
    bin_dir = os.path.join(REPO, "native", "bin")
    if not all(os.path.exists(os.path.join(bin_dir, b))
               for b in ("determined-master", "determined-agent")):
        subprocess.run(["make", "-C", os.path.join(REPO, "native"), "-j3"],
                       check=True, stdout=sys.stderr)
    tmp = tempfile.mkdtemp(prefix="chip_smoke_agent_")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    env = dict(os.environ, HOME=tmp, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    db = os.path.join(tmp, "master.db")
    procs, clis = [], []
    try:
        procs.append(subprocess.Popen(
            [os.path.join(bin_dir, "determined-master"), "--port", str(port),
             "--host", "127.0.0.1", "--db", db],
            env=env, stdout=open(os.path.join(tmp, "master.log"), "w"),
            stderr=subprocess.STDOUT))
        wait_http(url + "/api/v1/master")
        procs.append(subprocess.Popen(
            [os.path.join(bin_dir, "determined-agent"), "--master-url", url,
             "--slot-type", "tpu", "--addr", "127.0.0.1",
             "--work-root", os.path.join(tmp, "agent-work"),
             "--token-file", db + ".agent_token"],
            env=env, stdout=open(os.path.join(tmp, "agent.log"), "w"),
            stderr=subprocess.STDOUT))
        slots = agent_slots(url, procs[-1])
        cfg = os.path.join(REPO, "examples", "gpt2", "config.yaml")
        for _ in range(n_trials):
            clis.append(subprocess.Popen(
                [sys.executable, "-m", "determined_tpu.cli", "-m", url,
                 "experiment", "create", cfg,
                 os.path.join(REPO, "examples", "gpt2"), "--follow"],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        results = []
        for cli in clis:
            try:
                log, _ = cli.communicate(timeout=600)
            except subprocess.TimeoutExpired:
                cli.kill()
                log = (cli.communicate()[0] or "") + "\n[TIMEOUT]"
            devices = [ln for ln in log.splitlines()
                       if "device(s)" in ln and "mesh" in ln]
            results.append({
                "rc": cli.returncode,
                "completed": "COMPLETED" in log,
                "trainer_line": devices[-1] if devices else None,
                "log_tail": log.splitlines()[-6:],
            })
        ok = all(r["rc"] == 0 and r["completed"] and r["trainer_line"]
                 and " tpu device" in r["trainer_line"] for r in results)
        print(json.dumps({"phase": "agent", "ok": ok, "agent_slots": slots,
                          "trials": results}))
        if not ok:
            for name in ("agent.log", "master.log"):
                with open(os.path.join(tmp, name)) as f:
                    sys.stderr.write(f"---- {name}\n" + f.read()[-6000:])
        return 0 if ok else 1
    finally:
        # The agent puts every task tree in a process group of its own:
        # collect them before it dies, so nothing this script started
        # (directly or not) outlives it.
        task_groups = child_pids(procs[-1].pid) if len(procs) > 1 else []
        for p in clis + procs[::-1]:
            if p.poll() is None:
                p.kill()
                p.wait()
        for pgid in task_groups:
            try:
                os.killpg(pgid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        shutil.rmtree(tmp, ignore_errors=True)


# --------------------------------------------------------------------- main


def parse_mesh(text):
    return {k: int(v) for k, v in
            (item.split("=") for item in text.split(","))}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-tiny", action="store_true")
    ap.add_argument("--mesh", type=parse_mesh, default=None)
    ap.add_argument("--phases", default="kernels,train,serve")
    ap.add_argument("--break", dest="break_phase", default=None)
    ap.add_argument("--agent", nargs="?", const=1, type=int, default=None)
    args = ap.parse_args()

    if args.agent is not None:
        return run_agent_check(args.agent)

    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from determined_tpu.compile.runtime import enable_compilation_cache

    facts = device_facts()
    if facts["platform"] != "tpu" and not args.cpu_tiny:
        print(f"chip_smoke: no TPU (jax found {facts['platform']} "
              f"{facts['device_kind']}); --cpu-tiny is the sandbox dry run",
              file=sys.stderr)
        return 3
    args.cache_dir = enable_compilation_cache()
    facts["cache_dir"] = args.cache_dir
    print(json.dumps({"phase": "device", "ok": True, **facts}), flush=True)

    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    wanted = args.phases.split(",")
    phases = {"kernels": lambda out: phase_kernels(args, out),
              "train": lambda out: phase_train(args, ckpt_dir, out),
              "serve": lambda out: phase_serve(args, ckpt_dir, out)}
    results, failed = [], []
    try:
        for name in wanted:
            t0 = time.monotonic()
            line = {"phase": name, "platform": facts["platform"],
                    "device_kind": facts["device_kind"],
                    "device_count": facts["device_count"]}
            try:
                if name == "serve" and "train" in failed:
                    raise PhaseFailed("no checkpoint: the train phase failed")
                if name == args.break_phase:
                    raise PhaseFailed("--break asked for this failure")
                phases[name](line)      # fills in its facts as it goes
                line["ok"] = True
            except Exception as e:  # a caught phase still fails the run
                traceback.print_exc(file=sys.stderr)
                failed.append(name)
                line.update(ok=False, error=f"{type(e).__name__}: {e}"[:600])
            line["wall_s"] = round(time.monotonic() - t0, 1)
            results.append({"phase": name, "ok": line["ok"]})
            print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    print(json.dumps({"phase": "summary", "ok": not failed,
                      "phases": results, "claim": None}), flush=True)
    # The result line: these keys and no others (the driver parses it).
    print(json.dumps({
        "ok": not failed,
        "device": {"platform": facts["platform"],
                   "kind": facts["device_kind"],
                   "count": facts["device_count"]},
    }), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
