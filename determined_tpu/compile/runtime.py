"""Run-time side of the compile farm (docs/compile-farm.md).

Two artifact kinds live under one signature in the master's
content-addressed blob store:

- ``aot-<executable>-<runtime_tag>.bin`` — a pickled
  `jax.experimental.serialize_executable` payload. Loading one skips
  trace + lowering + compile entirely: the first step of a warm trial
  costs a deserialize (tens of ms) instead of seconds. This is what takes
  `cached_median_compile_s` to ~0.
- everything else — files from the persistent XLA compilation cache dir
  (`enable_compilation_cache`), uploaded verbatim under XLA's own
  content-hash names. Pre-warming a node with them is always SAFE regardless of
  signature precision: XLA only ever hits a cache entry whose key (full
  HLO + compile options + versions) matches exactly; a stray file is
  wasted bytes, never a wrong executable.

`FarmClient` resolves artifacts local-first (the agent pre-warms
`DET_COMPILE_AOT_DIR/<signature>/` before the container starts, overlapped
with image setup) and falls back to `GET /api/v1/compile_cache/{sig}`.
Fresh compiles upload their serialized executables + new cache files in a
background thread — never on the step path. Every failure here degrades to
the plain jit path: the farm is an accelerator, not a dependency.
"""

from __future__ import annotations

import base64
import logging
import os
import pickle
import threading
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from determined_tpu.compile.signature import runtime_tag

logger = logging.getLogger("determined_tpu.compile")

AOT_PREFIX = "aot-"


def aot_artifact_name(executable: str) -> str:
    return f"{AOT_PREFIX}{executable}-{runtime_tag()}.bin"


def serialize_compiled(compiled: Any) -> bytes:
    """Pickle a jax Compiled (payload + in/out treedefs + the ids of the
    devices it was compiled for) for the store."""
    from jax.experimental import serialize_executable as se

    device_ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps(se.serialize(compiled) + (device_ids,))


def load_compiled(data: bytes) -> Callable:
    """Inverse of serialize_compiled. Raises on any incompatibility
    (platform, jax version, a device the executable names that this host
    lacks; an aval mismatch surfaces at first call) — callers catch and
    fall back to jit.

    The executable is loaded onto the devices it was compiled for:
    `deserialize_and_load` otherwise assumes every device of the backend,
    and a one-chip serving executable on a four-chip host then demands
    four shards of each argument."""
    import jax
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree, device_ids = pickle.loads(data)
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


# <checkout>/.jax_cache: fixed (the directory is part of every cache key,
# so a temp name, pid or time in it could never hit twice) and git-ignored.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache for this process; the
    one switch `core.init`, `serve.task.main`, the compile worker, the
    bench scripts and `chip_smoke.py` all call. Returns the directory in
    effect ("" when off).

    Where the cache lives is decided from outside, in this order:
      - `DET_XLA_CACHE_DIR=` (set but empty — the documented expconf
        override): off;
      - `JAX_COMPILATION_CACHE_DIR`: jax reads it itself, and no directory
        is set in code — whoever placed it (the machine, the agent) owns
        the location;
      - `DET_XLA_CACHE_DIR` (agent-injected, one dir per host shared by
        every trial it runs);
      - else `DEFAULT_CACHE_DIR`, so a plain local `Trainer.fit` or
        `det serve --local` pays a cold compile once per checkout.

    min_compile_time 0: ASHA rung trials are many and SMALL; the default
    1 s floor would skip exactly the compiles they repeat most. The size
    bound matters on long-lived hosts (jax only evicts when one is set).
    """
    import jax

    if os.environ.get("DET_XLA_CACHE_DIR") == "":
        jax.config.update("jax_enable_compilation_cache", False)
        return ""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = os.environ.get("DET_XLA_CACHE_DIR") or DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", int(os.environ.get(
        "DET_XLA_CACHE_MAX_BYTES", str(4 << 30))))
    return compilation_cache_dir()


def compilation_cache_dir() -> str:
    """The persistent-cache directory jax is using ("" when off)."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return ""
    return jax.config.jax_compilation_cache_dir or ""


def snapshot_cache_dir(cache_dir: Optional[str]) -> Set[str]:
    if not cache_dir or not os.path.isdir(cache_dir):
        return set()
    try:
        return set(os.listdir(cache_dir))
    except OSError:
        return set()


def new_cache_files(cache_dir: Optional[str],
                    before: Set[str]) -> Dict[str, bytes]:
    """Files added to the persistent XLA cache since `before` — exactly the
    entries this process compiled fresh."""
    out: Dict[str, bytes] = {}
    for name in snapshot_cache_dir(cache_dir) - before:
        try:
            with open(os.path.join(cache_dir, name), "rb") as f:
                out[name] = f.read()
        except OSError:
            continue
    return out


class FarmClient:
    """Fetch/upload compile artifacts for ONE signature.

    `signature` comes from DET_COMPILE_SIGNATURE (master-minted) in managed
    mode; local/bench runs pass their own. A falsy signature disables the
    client (every method becomes a cheap no-op)."""

    def __init__(
        self,
        session: Any = None,
        signature: Optional[str] = None,
        aot_dir: Optional[str] = None,
        xla_cache_dir: Optional[str] = None,
    ):
        self.signature = signature if signature is not None else \
            os.environ.get("DET_COMPILE_SIGNATURE", "")
        self._session = session
        self.aot_dir = aot_dir if aot_dir is not None else \
            os.environ.get("DET_COMPILE_AOT_DIR", "")
        self.xla_cache_dir = xla_cache_dir if xla_cache_dir is not None else \
            compilation_cache_dir()
        self._cache_before = snapshot_cache_dir(self.xla_cache_dir)
        self._threads: List[threading.Thread] = []

    @property
    def enabled(self) -> bool:
        return bool(self.signature)

    # -- fetch ---------------------------------------------------------

    def _local_path(self, name: str) -> Optional[str]:
        if not self.aot_dir or not self.signature:
            return None
        path = os.path.join(self.aot_dir, self.signature, name)
        return path if os.path.isfile(path) else None

    def fetch(self, name: str) -> Optional[bytes]:
        """Artifact bytes: agent-prewarmed local dir first, then master."""
        if not self.enabled:
            return None
        path = self._local_path(name)
        if path is not None:
            try:
                with open(path, "rb") as f:
                    return f.read()
            except OSError:
                pass
        if self._session is None:
            return None
        try:
            resp = self._session.get(
                f"/api/v1/compile_cache/{self.signature}",
                params={"name": name})
        except Exception:
            logger.debug("compile_cache fetch failed", exc_info=True)
            return None
        for f in (resp or {}).get("files", []):
            if f.get("name") == name and f.get("b64"):
                return base64.b64decode(f["b64"])
        return None

    def load_executable(self, executable: str) -> Optional[Callable]:
        """Deserialize the signature's AOT artifact for `executable`
        (train_step/eval_step), or None. Never raises."""
        data = self.fetch(aot_artifact_name(executable))
        if data is None:
            return None
        try:
            return load_compiled(data)
        except Exception:
            logger.warning(
                "AOT artifact for %s/%s failed to load; falling back to jit",
                self.signature[:12], executable, exc_info=True)
            return None

    # -- upload --------------------------------------------------------

    def upload(self, files: Dict[str, bytes],
               compile_ms: Optional[float] = None,
               fingerprint: str = "") -> bool:
        if not self.enabled or self._session is None or not files:
            return False
        body: Dict[str, Any] = {
            "files": {n: base64.b64encode(b).decode()
                      for n, b in files.items()},
        }
        if compile_ms is not None:
            body["compile_ms"] = float(compile_ms)
        if fingerprint:
            body["fingerprint"] = fingerprint
        try:
            self._session.post(
                f"/api/v1/compile_cache/{self.signature}", body=body,
                idempotent=True)
            return True
        except Exception:
            # Best-effort by contract, like span flushes: a dead artifact
            # sink must never hurt the trial.
            logger.warning("compile artifact upload failed", exc_info=True)
            return False

    def upload_async(self, files: Dict[str, bytes],
                     compile_ms: Optional[float] = None) -> None:
        t = threading.Thread(
            target=self.upload, args=(files,),
            kwargs={"compile_ms": compile_ms},
            name="det-compile-upload", daemon=True)
        t.start()
        self._threads.append(t)

    def save_local(self, files: Dict[str, bytes]) -> bool:
        """Write artifacts into the node-local AOT dir (the same place the
        agent pre-warms into), so the NEXT process on this node warm-loads
        them even without a master round-trip — serving replicas use this
        for scale-from-zero cold starts. Best-effort like upload."""
        if not self.signature or not self.aot_dir or not files:
            return False
        try:
            d = os.path.join(self.aot_dir, self.signature)
            os.makedirs(d, exist_ok=True)
            for name, data in files.items():
                tmp = os.path.join(d, name + ".tmp")
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, os.path.join(d, name))
            return True
        except OSError:
            logger.debug("local AOT save failed", exc_info=True)
            return False

    def collect_new_cache_files(self) -> Dict[str, bytes]:
        return new_cache_files(self.xla_cache_dir, self._cache_before)

    def export_and_upload_async(self, jit_fn: Callable, args: Tuple,
                                executable: str,
                                compile_ms: Optional[float] = None) -> None:
        """After a fresh in-trial compile: re-lower the step abstractly in
        the background, serialize the (persistent-cache-hit) compiled
        executable and upload it with the new XLA cache files. Off the step
        path; abstract args only (no buffers pinned)."""
        if not self.enabled or self._session is None:
            return
        import jax

        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if hasattr(x, "shape") and hasattr(x, "dtype") else x, args)

        def work():
            files: Dict[str, bytes] = {}
            try:
                compiled = jit_fn.lower(*abstract).compile()
                files[aot_artifact_name(executable)] = \
                    serialize_compiled(compiled)
            except Exception:
                logger.debug("AOT export failed; uploading cache files only",
                             exc_info=True)
            files.update(self.collect_new_cache_files())
            if files:
                self.upload(files, compile_ms=compile_ms)

        t = threading.Thread(target=work, name="det-compile-export",
                             daemon=True)
        t.start()
        self._threads.append(t)

    def wait(self, timeout: float = 30.0) -> None:
        """Join outstanding uploads (tests + clean trial exit)."""
        for t in self._threads:
            t.join(timeout)
        self._threads = [t for t in self._threads if t.is_alive()]
