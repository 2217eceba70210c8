"""Persistent XLA compilation cache (SURVEY hard part b / VERDICT r4
next #5): core.init enables jax's disk cache from the agent-injected
DET_XLA_CACHE_DIR so identical-shape ASHA rung trials skip compile.
(Where the cache lives when nothing or JAX_COMPILATION_CACHE_DIR is set:
tests/test_chip_lowering.py.)"""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = textwrap.dedent("""
    import json, time
    import jax, jax.numpy as jnp
    from determined_tpu.compile.runtime import enable_compilation_cache
    enable_compilation_cache()

    @jax.jit
    def f(x):
        for _ in range(8):
            x = jnp.tanh(x @ x.T) @ x
        return x.sum()

    x = jnp.ones((173, 211))  # odd shapes: this test's cache entry only
    t0 = time.time()
    f(x).block_until_ready()
    print(json.dumps({"compile_s": time.time() - t0}))
""")


def _run_probe(cache_dir, env_extra=None):
    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        DET_XLA_CACHE_DIR=str(cache_dir),
    )
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    r = subprocess.run([sys.executable, "-c", _PROBE],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_populated_and_reused(tmp_path):
    cache = tmp_path / "xla_cache"
    cold = _run_probe(cache)
    files = sorted(os.listdir(cache))
    assert files, "first run must write cache entries"

    warm = _run_probe(cache)
    files2 = sorted(os.listdir(cache))
    assert files2 == files, "identical program must HIT, not re-write"
    # The warm process loads the compiled executable instead of running
    # XLA optimization; require a real win but keep slack for CI noise.
    assert warm["compile_s"] < cold["compile_s"] * 0.7, (cold, warm)


def test_empty_env_disables_cache(tmp_path):
    """The expconf `DET_XLA_CACHE_DIR=` override must really disable the
    cache: nothing may be written to the dir the env no longer names."""
    cache = tmp_path / "would_be_cache"
    _run_probe(cache, env_extra={"DET_XLA_CACHE_DIR": ""})
    assert not os.path.exists(cache)


def test_core_init_enables_cache(tmp_path, monkeypatch):
    """core.init is the harness-wide hook: after it runs under
    DET_XLA_CACHE_DIR, jax's config points at the dir."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("DET_XLA_CACHE_DIR", str(tmp_path / "cc"))
        from determined_tpu.compile.runtime import enable_compilation_cache

        enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cc")
        assert os.path.isdir(tmp_path / "cc")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
