#!/usr/bin/env python
"""ResNet-50 training throughput on one TPU chip (BASELINE.md:
"samples/sec/chip — track & report ... GPT-2 & ResNet-50").

Prints ONE JSON line like bench.py (also callable via `bench.py` which
emits all three BASELINE metrics). ResNet-50, ImageNet shapes (224x224x3),
bf16 compute, BatchNorm stats carried through a scanned multi-step with
donated buffers. vs_baseline is MFU over the 40% target for cross-bench
comparability.

BN runs as f32-accumulated reductions + a fused bf16 affine
(models/resnet.py _bn). Where this model sits against a v5e's conv
roofline is not measured; `_roofline_probe` below measures the chip's
stream and matmul ceilings next to the headline number so the run says.
"""

import json
import sys
import time

import numpy as np


def _input_pipeline_detail(step_s: float) -> dict:
    """Prefetch on/off over ResNet-shaped host batches (real np generation
    + real H2D), stepped at this chip's measured step time: the
    `input_wait_ms` the synchronous loop would pay vs the prefetched one.
    ResNet moves the most input bytes per step of the benches, so the
    on/off delta lives here, next to the number it explains."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from determined_tpu.data.bench import ab_compare

    B, HW, n = 64, 224, 6

    def make_iter():
        rng = np.random.default_rng(1)

        def gen():
            for _ in range(n):
                # real host preprocessing cost: generate + cast per batch
                yield {
                    "images": rng.random(
                        size=(B, HW, HW, 3), dtype=np.float32),
                    "labels": rng.integers(0, 1000, size=(B,)).astype(
                        np.int32),
                }
        return gen()

    sharding = NamedSharding(
        Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",)),
        PartitionSpec("data"))
    step_s = min(max(step_s, 0.01), 0.2)

    result = ab_compare(make_iter, lambda b: time.sleep(step_s),
                        sharding=sharding, depth=2)
    return {
        "prefetch_speedup": result["speedup"],
        "sync_input_wait_ms": result["sync"]["input_wait_ms"],
        "prefetch_input_wait_ms": result["prefetch"]["input_wait_ms"],
        "input_wait_ms_delta": result["input_wait_ms_delta"],
        "h2d_ms": result["prefetch"].get("h2d_ms"),
    }


def _roofline_probe() -> dict:
    """Measure THIS chip's two conv-relevant ceilings and derive the
    attainable conv throughput (VERDICT item 9 — makes the "ResNet is at
    the roofline" claim self-verifying instead of a docstring assertion):

      - **HBM bandwidth**: a donated bf16 copy-scale kernel over a
        ~256 MB buffer (reads + writes every byte once; convs below
        C≈512 on this chip are bandwidth-bound, so stream rate is the
        binding ceiling);
      - **matmul peak**: a big square bf16 matmul (the MXU ceiling the
        highest-C convs approach).

    The conv roofline is `min(matmul_peak, bw × AI)` with AI =
    flops/byte of ResNet-50's conv mix, and `pct_of_ceiling` =
    achieved_flops / attainable — ≥0.95 verifies the ceiling claim,
    lower exposes a real optimization target.
    """
    import jax
    import jax.numpy as jnp

    def _best_of(f, n=3):
        best = float("inf")
        for _ in range(n):
            t0 = time.time()
            f()
            best = min(best, time.time() - t0)
        return best

    # HBM stream: read + write ~256MB of bf16 through a donated scale.
    # The factor must be exactly representable and != 1.0 in bf16 —
    # x * 1.0 donated is an XLA no-op and "measures" TB/s.
    n_elems = 128 * 1024 * 1024  # 256 MB in bf16
    buf = jnp.ones((n_elems,), jnp.bfloat16)
    scale = jax.jit(lambda x: x * jnp.bfloat16(1.0078125),
                    donate_argnums=0)
    buf = scale(buf)  # compile + first touch
    jax.block_until_ready(buf)

    def _stream():
        nonlocal buf
        buf = scale(buf)
        jax.block_until_ready(buf)

    stream_s = _best_of(_stream)
    hbm_gbps = 2 * n_elems * 2 / stream_s / 1e9  # read + write, bf16

    # Matmul peak: 4096^3 bf16 (big enough to saturate the MXU, small
    # enough to finish fast on CPU fallbacks).
    m = 4096
    a = jnp.ones((m, m), jnp.bfloat16)
    b = jnp.ones((m, m), jnp.bfloat16)
    mm = jax.jit(lambda x, y: (x @ y).astype(jnp.bfloat16))
    jax.block_until_ready(mm(a, b))
    mm_s = _best_of(lambda: jax.block_until_ready(mm(a, b)))
    matmul_tflops = 2 * m ** 3 / mm_s / 1e12

    # ResNet-50 conv arithmetic intensity at batch 256, bf16: total
    # train conv flops over the HBM bytes the conv inputs/outputs/weights
    # move. The fwd activation footprint of ResNet-50 at 224² is
    # ~38 MB/image in bf16 across conv layers; train ≈ 3 passes, each
    # reading + writing it once -> ~6x activation traffic + weights.
    flops_per_image = 3 * 4.1e9
    act_bytes_per_image = 38e6 * 2 * 3  # bf16, fwd+dgrad+wgrad passes
    ai = flops_per_image / act_bytes_per_image  # ~54 flops/byte
    attainable_tflops = min(matmul_tflops, hbm_gbps * ai / 1e3)
    return {
        "hbm_bandwidth_gbps": round(hbm_gbps, 1),
        "matmul_peak_tflops": round(matmul_tflops, 2),
        "conv_arith_intensity_flops_per_byte": round(ai, 1),
        "conv_attainable_tflops": round(attainable_tflops, 2),
    }


def run() -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from bench import _peak_flops
    from determined_tpu.compile.runtime import enable_compilation_cache
    from determined_tpu.models import resnet

    enable_compilation_cache()
    cfg = resnet.Config.resnet50()
    B, HW = 256, 224
    STEPS_PER_CALL = 10
    # ResNet-50 fwd ≈ 4.1 GFLOP/image at 224²; train ≈ 3× fwd.
    train_flops_per_image = 3 * 4.1e9
    peak = _peak_flops()

    tx = optax.sgd(0.1, momentum=0.9)
    params, stats = resnet.init(jax.random.PRNGKey(0), cfg)
    opt_state = tx.init(params)

    def one_step(carry, batch):
        params, stats, opt_state = carry

        def lfn(p):
            loss, metrics, new_stats = resnet.loss_fn(
                p, stats, batch, cfg=cfg, train=True)
            return loss.astype(jnp.float32), (metrics, new_stats)

        (loss, (metrics, new_stats)), grads = jax.value_and_grad(
            lfn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, new_stats, opt_state), loss

    def multi_step(params, stats, opt_state, batches):
        (params, stats, opt_state), losses = jax.lax.scan(
            one_step, (params, stats, opt_state), batches)
        return params, stats, opt_state, losses.mean()

    # Donate the state buffers: params/stats/opt_state round-trip through
    # every call, and donation avoids ~300 MB/step of copy traffic.
    multi_step = jax.jit(multi_step, donate_argnums=(0, 1, 2))

    rng = np.random.default_rng(0)
    # Device-resident batch (transferred once, before timing): this bench
    # measures the chip's training throughput; input-pipeline cost is a
    # host/IO concern and would be hidden by double-buffering in the real
    # loop anyway.
    batches = jax.device_put({
        "images": rng.normal(size=(STEPS_PER_CALL, B, HW, HW, 3)).astype(
            jnp.bfloat16),
        "labels": rng.integers(0, cfg.n_classes,
                               size=(STEPS_PER_CALL, B)).astype(np.int32),
    })

    params, stats, opt_state, loss = multi_step(params, stats, opt_state, batches)
    float(loss)  # compile + sync

    n_calls = 3
    t0 = time.time()
    for _ in range(n_calls):
        params, stats, opt_state, loss = multi_step(
            params, stats, opt_state, batches)
    float(loss)
    dt = (time.time() - t0) / (n_calls * STEPS_PER_CALL)

    samples_per_sec = B / dt
    mfu = train_flops_per_image * samples_per_sec / peak
    try:
        input_pipeline = _input_pipeline_detail(dt)
    except Exception as e:  # the headline number must not depend on this
        input_pipeline = {"error": str(e)[:200]}
    # Measured roofline (VERDICT item 9): how close the achieved conv
    # throughput sits to what THIS chip's measured bandwidth + matmul
    # peak make attainable — >= 0.95 verifies the "at the roofline"
    # claim; lower is a real optimization target, not a chip excuse.
    try:
        roofline = _roofline_probe()
        achieved_tflops = train_flops_per_image * samples_per_sec / 1e12
        roofline["achieved_tflops"] = round(achieved_tflops, 2)
        pct_of_ceiling = round(
            achieved_tflops / roofline["conv_attainable_tflops"], 4)
    except Exception as e:  # the headline number must not depend on this
        roofline = {"error": str(e)[:200]}
        pct_of_ceiling = None
    return {
        "metric": "resnet50_samples_per_sec_per_chip",
        "value": round(samples_per_sec, 1),
        "unit": "samples/sec/chip (224x224)",
        "vs_baseline": round(mfu / 0.40, 3),
        "detail": {
            "step_ms": round(dt * 1000, 1),
            "mfu": round(mfu, 4),
            "pct_of_ceiling": pct_of_ceiling,
            "roofline": roofline,
            "batch": B,
            "device": str(jax.devices()[0]),
            # prefetch on/off A/B over ResNet-shaped host batches at this
            # chip's measured step time (determined_tpu/data/bench.py)
            "input_pipeline": input_pipeline,
        },
    }


def main() -> None:
    print(json.dumps(run()))


if __name__ == "__main__":
    sys.exit(main())
