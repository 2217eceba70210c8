"""Test bootstrap: run everything on a virtual 8-device CPU "slice".

Mirrors the reference's threads-as-ranks / artificial-slots testing ideas
(SURVEY.md §4): shardings and collectives are exercised for real, on CPU.
Must run before jax initialises any backend, hence top of conftest.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("KERAS_BACKEND", "jax")  # Keras 3 on the JAX backend

import atexit  # noqa: E402
import contextlib  # noqa: E402
import fcntl  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from determined_tpu.compile import runtime as _runtime  # noqa: E402

# Every session compiles from a cold cache of its own, like a fresh
# checkout does. Not only for hermeticity: on XLA:CPU (jax 0.9.0) an
# executable LOADED from the persistent cache cannot be re-serialized —
# the AOT round trip (compile farm tests) then dies with "Function
# wrapped_* not found" — so a warm <checkout>/.jax_cache from an earlier
# session fails tests a cold one passes. (TPU executables round-trip fine:
# checked on the chip, PR 21.) Children decide for themselves: agents
# inject their own dir, other subprocesses use the checkout's.
_CHECKOUT_CACHE_DIR = _runtime.DEFAULT_CACHE_DIR
_runtime.DEFAULT_CACHE_DIR = tempfile.mkdtemp(prefix="det-test-jax-cache-")
atexit.register(shutil.rmtree, _runtime.DEFAULT_CACHE_DIR, ignore_errors=True)


@pytest.fixture()
def checkout_cache_dir():
    """What `DEFAULT_CACHE_DIR` is outside the test session."""
    return _CHECKOUT_CACHE_DIR


NATIVE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")


@contextlib.contextmanager
def native_build_lock():
    """Hold the one lock every `make -C native` of a test session runs
    under. Each xdist worker is a session of its own, so without it six
    builds write `native/bin/` at once, each linker over binaries the
    others already exec. The file lives in `native/bin/` (git-ignored,
    removed by `make clean`); closing it releases the lock."""
    os.makedirs(os.path.join(NATIVE, "bin"), exist_ok=True)
    with open(os.path.join(NATIVE, "bin", ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        yield


@pytest.fixture(scope="session")
def native_binaries():
    """`native/bin/` with master, agent and searcher_sim built: one worker
    compiles (by objects, on every core), the others find a no-op."""
    with native_build_lock():
        subprocess.run(
            ["make", "-C", NATIVE, f"-j{os.cpu_count()}"], check=True,
            capture_output=True)
    return os.path.join(NATIVE, "bin")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {devs}"
    return devs


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def np_rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running e2e (excluded from the tier-1 time budget)",
    )
