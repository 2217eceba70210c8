"""What the CPU sandbox can say about the chip: the real XLA:TPU + Mosaic
compilers run without one.

libtpu is installed here, so `jax.experimental.topologies` describes a
v5e 2x2 host and `jit(f).lower(<ShapeDtypeStructs sharded on that
mesh>).compile()` compiles for it under JAX_PLATFORMS=cpu — no device, no
execution, seconds per kernel. These tests hold the two Pallas kernels
and their multi-device placement to "Mosaic accepts this"; whether the
numbers are right on the chip is chip_smoke.py's job. Also here: where
the persistent compile cache lives (`enable_compilation_cache`).

Budget: < 30 s for the file (tier-1 is time-boxed).
"""

import collections
import functools
import importlib.util
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from determined_tpu.parallel.mesh import AXIS_ORDER, MeshConfig, on_tpu
from determined_tpu.serve.engine import _tree_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    importlib.util.find_spec("libtpu") is None,
    reason="deviceless TPU compilation needs libtpu")


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices


def _mesh(devices, **axes):
    shape = MeshConfig(**axes).resolve(len(devices)).sizes()
    return Mesh(np.asarray(devices).reshape(shape), AXIS_ORDER)


def _sds(mesh, shape, dtype, *spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P(*spec)))


def test_on_tpu_follows_the_devices_compiled_for(v5e, devices):
    """One answer to "is this a TPU", from the target devices — not the
    host's default backend (cpu here)."""
    assert not on_tpu()
    assert on_tpu(v5e) and not on_tpu(devices)
    with jax.sharding.use_abstract_mesh(_mesh(v5e, data=4).abstract_mesh):
        assert on_tpu()
    with jax.sharding.set_mesh(_mesh(devices, data=8)):
        assert not on_tpu()


@pytest.mark.parametrize("bf16", [False, True], ids=["f32probs", "bf16probs"])
def test_flash_fwd_bwd_lowers_through_mosaic(v5e, bf16):
    from determined_tpu.ops.flash_attention import pallas_flash_attention

    mesh = _mesh(v5e[:1], data=1)
    q = _sds(mesh, (2, 256, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(pallas_flash_attention(q, k, v, True, bf16)
                       .astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 3  # fwd, dq, dk/dv


@pytest.mark.parametrize("geometry", [
    pytest.param((4, 2, 128, 16, 4), id="small-aligned"),
    pytest.param((8, 12, 64, 16, 64), id="gpt2-small"),
    pytest.param((32, 20, 64, 16, 64), id="served-gpt2-large"),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_paged_decode_lowers_through_mosaic(v5e, geometry, dtype):
    """The kernel Mosaic refused before PR 21 (a 2-D lhs with a batch dim
    and no non-contracting dim), at the served geometries: its operand is
    the whole pool `[L, blocks, bs, H*Dh]` plus a layer index, left where
    it rests and copied by hand a span of a lane's blocks at a time, and
    heads are static 128-lane slices of the span's rows."""
    from determined_tpu.ops.paged_attention import paged_attention_pallas

    slots, heads, dh, bs, mb = geometry
    mesh = _mesh(v5e[:1], data=1)
    pool = _sds(mesh, (2, slots * mb + 1, bs, heads * dh), dtype)
    hlo = jax.jit(paged_attention_pallas).lower(
        _sds(mesh, (slots, heads, dh), dtype), pool, pool,
        _sds(mesh, (), jnp.int32), _sds(mesh, (slots, mb), jnp.int32),
        _sds(mesh, (slots,), jnp.int32)).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1


def test_paged_decode_with_shared_kv_heads_lowers_through_mosaic(v5e):
    """serve-h1-decode's attention: 20 query heads over 4 K/V heads of
    128, the pool's row 512 lanes; a group is a K/V head and its five
    query heads are its rows."""
    from determined_tpu.ops.paged_attention import paged_attention_pallas

    slots, hq, hkv, dh, bs, mb = 64, 20, 4, 128, 16, 64
    mesh = _mesh(v5e[:1], data=1)
    pool = _sds(mesh, (6, 2561, bs, hkv * dh), jnp.bfloat16)
    hlo = jax.jit(paged_attention_pallas).lower(
        _sds(mesh, (slots, hq, dh), jnp.bfloat16), pool, pool,
        _sds(mesh, (), jnp.int32), _sds(mesh, (slots, mb), jnp.int32),
        _sds(mesh, (slots,), jnp.int32)).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert "bf16[64,4,5,128]" in hlo


def test_state_kernel_lowers_through_mosaic_and_moves_no_pool(v5e):
    """The recurrent-state update at serve-h1-decode's geometry (64 lanes
    of 32 x 256 x 128 float32, 6 layers: a 1.61 GB pool): Mosaic takes
    it, the pool is aliased onto the output, and the call keeps nothing
    of the pool's size beside it. The custom call bears the name the
    benchmark's reader looks for."""
    from determined_tpu.ops.ssm_state import ssm_state_update

    slots, heads, groups, p, n = 64, 32, 2, 128, 256
    mesh = _mesh(v5e[:1], data=1)
    pool = _sds(mesh, (6, slots, heads, n, p), jnp.float32)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        compiled = jax.jit(ssm_state_update, donate_argnums=(4,)).lower(
            _sds(mesh, (slots, heads, p), jnp.bfloat16),
            _sds(mesh, (slots, heads), jnp.float32),
            _sds(mesh, (slots, groups, n), jnp.bfloat16),
            _sds(mesh, (slots, groups, n), jnp.bfloat16), pool,
            _sds(mesh, (), jnp.int32), _sds(mesh, (slots,), jnp.bool_),
            _sds(mesh, (heads,), jnp.float32)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert re.search(r"%ssm_state_update[.\d]* = \(f32\[64,4,8,128\]", hlo)
    memory = compiled.memory_analysis()
    pool_bytes = 6 * slots * heads * n * p * 4
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 1000


def test_latent_decode_kernel_lowers_through_mosaic(v5e):
    """GLM-4.7-Flash's attention as configs/glm-4.7-flash.json serves it: 20 heads as the rows of one matmul
    against a 640-lane latent row (512 + 64, padded), 64 lanes over a
    pool of 14,336 blocks under a 256-entry table. The custom call bears
    the name the benchmark's reader looks for."""
    from determined_tpu.ops.mla_attention import mla_decode_attention

    slots, heads, rank, row, bs, mb = 64, 20, 512, 640, 16, 256
    mesh = _mesh(v5e[:1], data=1)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        hlo = jax.jit(mla_decode_attention,
                      static_argnames=("rank", "scale")).lower(
            _sds(mesh, (slots, heads, row), jnp.bfloat16),
            _sds(mesh, (8, 14337, bs, row), jnp.bfloat16),
            _sds(mesh, (), jnp.int32), _sds(mesh, (slots, mb), jnp.int32),
            _sds(mesh, (slots,), jnp.int32), rank=rank,
            scale=256 ** -0.5).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert re.search(r"%mla_decode_attention[.\d]* = bf16\[64,20,512\]", hlo)


@pytest.mark.parametrize("tokens", [64, 160, 3200],
                         ids=["decode", "turn", "first-turn"])
def test_dropless_expert_layer_lowers_through_mosaic(v5e, tokens):
    """GLM-4.7-Flash's expert layer at the published widths (64
    experts of 2,048 x 1,536, top-4) for a decode step's 64 tokens and
    for both prefill buckets: two grouped matmuls under the name the
    reader looks for, and no [tokens, experts, capacity] tensor."""
    from determined_tpu.ops import moe

    e, d, f, k = 64, 2048, 1536, 4
    mesh = _mesh(v5e[:1], data=1)

    def layer(x, router, bias, w13, w2):
        return moe.dropless_moe(
            x, {"router": router, "router_bias": bias, "w13": w13,
                "w2": w2}, top_k=k, routed_scaling_factor=1.8,
            impl="pallas")

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        compiled = jax.jit(layer).lower(
            _sds(mesh, (tokens, d), jnp.bfloat16),
            _sds(mesh, (d, e), jnp.bfloat16), _sds(mesh, (e,), jnp.bfloat16),
            _sds(mesh, (e, d, 2 * f), jnp.bfloat16),
            _sds(mesh, (e, f, d), jnp.bfloat16)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert len(re.findall(r"%moe_grouped_matmul[.\d]* = ", hlo)) == 2
    # nothing of tokens x experts x width: the rows are the assignments'
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 6 * tokens * k * (d + 2 * f) * 4


def _opcodes_with_result(hlo, shapes):
    """Instructions of a compiled module (fused computations' bodies too)
    whose result has one of `shapes`, by opcode → count."""
    dims = "|".join(",".join(map(str, shape)) for shape in shapes)
    found = collections.Counter()
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.-]+ = (\S+) ([\w-]+)\(", line)
        if m and re.search(rf"\[(?:1,)?(?:{dims})\]", m.group(1)):
            found[m.group(2)] += 1
    return found


def _pool_shaped(hlo, cache):
    """Instructions whose result is the whole pool or one layer of it
    (parameters, tuples and the loop that carries the pool apart: they
    move nothing)."""
    found = _opcodes_with_result(
        hlo, [cache["k"].shape, cache["k"].shape[1:]])
    for moves_nothing in ("parameter", "get-tuple-element", "tuple",
                          "bitcast", "while"):
        found.pop(moves_nothing, None)
    return found


def _compiled_serving_call(v5e, call, resident):
    """The decode step or a prefill bucket compiled for one v5e chip at
    the served head shape, pool donated → (compiled, params, cache); the
    parameters as a float32 checkpoint gives them, or as the engine keeps
    them (`smodel.resident_params`)."""
    from determined_tpu.models import gpt2
    from determined_tpu.serve import model as smodel

    # The served pool's own 1281 blocks, four layers of them: nothing here
    # is allocated, and a pool under the chip's 128 MiB of fast memory is
    # prefetched there whole and copied back, which is another program.
    cfg = gpt2.Config(vocab_size=512, n_positions=256, d_model=1280,
                      n_layer=4, n_head=20, dtype=jnp.bfloat16, remat=False,
                      attention_impl="dot")
    slots, bs, mb, pool_blocks = 4, 16, 256 // 16, 1281
    mesh = _mesh(v5e[:1], data=1)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(mesh, x.shape, x.dtype), tree)

    params = jax.eval_shape(lambda: gpt2.init(jax.random.PRNGKey(0), cfg))
    if resident:
        params = jax.eval_shape(
            lambda p: smodel.resident_params(p, cfg), params)
    cache = jax.eval_shape(
        lambda: smodel.init_paged_cache(cfg, pool_blocks, bs))
    i32 = jnp.int32
    if call == "decode":
        fn = functools.partial(smodel.paged_decode_step, cfg=cfg,
                               attention_impl="pallas")
        args = (_sds(mesh, (slots,), i32), _sds(mesh, (slots,), i32),
                _sds(mesh, (slots, mb), i32))
    else:
        fn = functools.partial(smodel.paged_prefill, cfg=cfg)
        args = (_sds(mesh, (128,), i32), _sds(mesh, (), i32),
                _sds(mesh, (), i32), _sds(mesh, (mb,), i32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), *args).compile()
    return compiled, params, cache


@pytest.mark.parametrize("call", ["decode", "prefill"])
def test_serving_calls_update_the_pool_in_place(v5e, call):
    """No serving call copies or re-lays-out the KV pool (PERF.md PR 26):
    at the served head shape, with the pool donated, the compiled decode
    step and a prefill bucket alias both pool leaves onto their outputs,
    hold no second pool in scratch, and touch pool-shaped buffers only
    through the token-sized scatters."""
    compiled, _, cache = _compiled_serving_call(v5e, call, resident=False)
    hlo = compiled.as_text()
    pool_bytes = _tree_bytes(cache)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == pool_bytes
    header = hlo.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 2, header
    assert memory.temp_size_in_bytes < pool_bytes / 4, memory
    moved = _pool_shaped(hlo, cache)
    for opcode in ("copy", "dynamic-slice", "dynamic-update-slice",
                   "AllocateBuffer", "custom-call", "transpose"):
        assert opcode not in moved, (opcode, moved)
    # What is left writes a token's rows: the K and the V scatter (each
    # once as the fusion's root and once as the fusion).
    assert set(moved) <= {"scatter", "fusion"}, moved
    assert hlo.count("tpu_custom_call") == (1 if call == "decode" else 0)


@pytest.mark.parametrize("call", ["decode", "prefill"])
def test_serving_calls_cast_no_weight_stack(v5e, call):
    """Against the tree the engine keeps resident (PERF.md PR 29), no call
    converts a stack of weights and none holds a second copy of them.
    Read here, against 159.4 MB of resident weights: 0.29 MB of temp for
    the decode step and 1.0 MB for the 128 bucket; compiled against the
    float32 tree they hold 52.8 and 53.7 MB, bfloat16 copies of weight
    stacks made by six converts in every call. At gpt2-large's own size
    (scratch compile, PR 29): 0.29 MB decode, 0.29 / 2.2 / 2.3 MB the
    256 / 512 / 1024 buckets, against 1.55 GB."""
    compiled, params, _ = _compiled_serving_call(v5e, call, resident=True)
    blocks = params["blocks"]
    stacks = [blocks[name]["kernel"].shape
              for name in ("mlp_up", "mlp_down", "qkv", "attn_out")]
    stacks += [params["wte"].shape, params["wpe"].shape]
    assert "convert" not in _opcodes_with_result(compiled.as_text(), stacks)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < _tree_bytes(params) / 4, memory


@pytest.mark.parametrize("axes", [
    dict(data=4), dict(data=1, fsdp=4), dict(data=2, fsdp=2),
    dict(data=2, tensor=2),
], ids=["data4", "fsdp4", "data2xfsdp2", "data2xtensor2"])
def test_flash_on_four_devices_lowers(v5e, axes):
    """GSPMD cannot partition a Mosaic call ("wrap the call in a
    shard_map"): the dispatcher places it by the logical rules, with no
    collective around the kernel."""
    from determined_tpu.ops.flash_attention import flash_attention

    mesh = _mesh(v5e, **axes)
    q = _sds(mesh, (8, 256, 4, 64), jnp.bfloat16,
             ("data", "fsdp"), None, "tensor", None)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, impl="auto")
                       .astype(jnp.float32) ** 2)

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 3
    assert "all-gather" not in hlo and "all-to-all" not in hlo


def test_kernel_refusals_name_the_reason(v5e):
    """Where the kernel cannot serve a call, explicit `pallas` raises and
    says why; it never hands back the reference under the kernel's name."""
    from determined_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((4, 256, 4, 64), jnp.bfloat16)
    seq_sharded = _mesh(v5e, data=2, context=2)
    with jax.sharding.use_abstract_mesh(seq_sharded.abstract_mesh):
        with pytest.raises(ValueError, match="context"):
            jax.eval_shape(functools.partial(
                flash_attention, impl="pallas"), q, q, q)
        # auto may choose: the reference path, announced in the log.
        out = jax.eval_shape(functools.partial(
            flash_attention, impl="auto"), q, q, q)
    assert out.shape == q.shape


# ------------------------------------------------ where the cache lives


@pytest.fixture()
def cache_config():
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_compilation_cache_max_size")}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_dir_placed_from_outside_is_left_alone(
        monkeypatch, cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, jax owns the directory: no code
    path sets one (thresholds may still be tuned)."""
    from determined_tpu.compile import runtime

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    monkeypatch.setenv("DET_XLA_CACHE_DIR", "/agent/xla_cache")
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1])
    before = jax.config.jax_compilation_cache_dir
    runtime.enable_compilation_cache()
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == before
    assert "jax_persistent_cache_min_compile_time_secs" in updates


def test_cache_dir_defaults_to_the_checkout(
        monkeypatch, cache_config, checkout_cache_dir, tmp_path):
    from determined_tpu.compile import runtime

    # (conftest points the session's own default at a temp dir)
    assert checkout_cache_dir == os.path.join(REPO, ".jax_cache")
    monkeypatch.setattr(runtime, "DEFAULT_CACHE_DIR", str(tmp_path / "dflt"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("DET_XLA_CACHE_DIR", raising=False)
    assert runtime.enable_compilation_cache() == str(tmp_path / "dflt")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "dflt")
    # DET_XLA_CACHE_DIR= (empty) keeps meaning "off".
    monkeypatch.setenv("DET_XLA_CACHE_DIR", "")
    assert runtime.enable_compilation_cache() == ""
    assert runtime.compilation_cache_dir() == ""


# ------------------------------------------------------ the smoke itself


def test_chip_smoke_refuses_without_a_chip():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 3 and r.stdout == "", (r.returncode, r.stdout)


@pytest.mark.slow
def test_chip_smoke_cpu_tiny_passes_and_a_broken_phase_fails_it():
    """The sandbox dry run (explicit, platform cpu) keeps the script
    itself from rotting; `--break` shows a failed phase fails the run."""
    import json

    cmd = [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--cpu-tiny"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=900)
    summary, last = map(json.loads, r.stdout.strip().splitlines()[-2:])
    assert r.returncode == 0 and last["ok"], r.stdout
    assert summary["phase"] == "summary" and summary["claim"] is None
    # The result line's contract: exactly these keys, nothing more.
    assert set(last) == {"ok", "device"}, last
    assert set(last["device"]) == {"platform", "kind", "count"}, last
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["count"], int)
    r = subprocess.run(cmd + ["--break", "serve"], capture_output=True,
                       text=True, timeout=900)
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert r.returncode == 1 and last["ok"] is False, r.stdout
    assert set(last) == {"ok", "device"}, last
