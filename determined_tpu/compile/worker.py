"""Compile-farm worker: one background AOT compile job (docs/compile-farm.md).

Dispatched by the master to an IDLE agent (action type "compile"), so queued
time becomes compile time instead of allocation time. The worker:

  1. downloads the experiment's model-def context and instantiates the trial
     (same loader as `det preflight`),
  2. traces the trial's step fingerprint; if an already-DONE job has the
     same fingerprint it LINKS that job's artifacts to this signature and
     exits without compiling (executable sharing, fingerprint-verified —
     this is how an `inject_hyperparams` lr sweep ends up with one
     executable for N signatures),
  3. otherwise AOT-compiles the jitted train step (and eval step when the
     trial has one) under the declared mesh via `jit().lower().compile()`,
     serializes the executables, and uploads them plus the new persistent
     XLA-cache entries to `POST /api/v1/compile_cache/{signature}`.

The worker also runs with `DET_XLA_CACHE_DIR` pointing at the agent's
shared cache dir, so the compiling node itself is warm before any artifact
round-trips.

Environment contract (set by the master's dispatch, master_compile.cc):
  DET_MASTER, DET_SESSION_TOKEN, DET_COMPILE_SIGNATURE,
  DET_COMPILE_HPARAMS (json), DET_COMPILE_SLOTS, DET_EXPERIMENT_ID,
  DET_EXPERIMENT_CONFIG (json), DET_XLA_CACHE_DIR.
"""

from __future__ import annotations

import base64
import io
import json
import logging
import os
import sys
import tarfile
import tempfile
import time
from typing import Any, Dict, Optional

logger = logging.getLogger("determined_tpu.compile.worker")


def _extract_model_def(b64: str, workdir: str) -> None:
    raw = base64.b64decode(b64)
    with tarfile.open(fileobj=io.BytesIO(raw), mode="r:gz") as tar:
        for member in tar.getmembers():
            target = os.path.realpath(os.path.join(workdir, member.name))
            if not target.startswith(os.path.realpath(workdir)):
                raise RuntimeError(
                    f"unsafe path in context tar: {member.name}")
        tar.extractall(workdir)


def _load_trial(workdir: str, hparams: Dict[str, Any], slots: int):
    from determined_tpu.analysis._preflight import (
        find_trial_classes,
        load_trial,
    )

    classes = find_trial_classes(workdir)
    if not classes:
        raise RuntimeError("no JaxTrial subclass in the model definition; "
                           "only Trainer-based trials are farm-compilable")
    path, class_name = classes[0]
    return load_trial(path, class_name, hparams, slots)


def run_job(session, signature: str, hparams: Dict[str, Any], slots: int,
            experiment_id: int, config: Dict[str, Any],
            workdir: Optional[str] = None) -> Dict[str, Any]:
    """Execute one compile job; returns a summary dict. Raises on failure
    (the caller reports FAILED)."""
    import jax

    from determined_tpu.compile.bucketing import CompileConfig
    from determined_tpu.compile.runtime import (
        FarmClient,
        aot_artifact_name,
        enable_compilation_cache,
        serialize_compiled,
    )
    from determined_tpu.compile.signature import step_fingerprint
    from determined_tpu.parallel.mesh import create_mesh
    from determined_tpu.train.state import abstract_train_state
    from determined_tpu.train.step import make_eval_step, make_train_step

    enable_compilation_cache()
    t_start = time.time()

    if workdir is None:
        workdir = tempfile.mkdtemp(prefix="det-compile-")
        resp = session.get(f"/api/v1/experiments/{experiment_id}/model_def")
        b64 = (resp or {}).get("b64_tgz") or ""
        if not b64:
            raise RuntimeError(
                f"experiment {experiment_id} has no model definition")
        _extract_model_def(b64, workdir)

    trial = _load_trial(workdir, hparams, slots)
    cfg = CompileConfig.resolve(trial, config)
    client = FarmClient(session, signature)

    # Fingerprint first: a trace is ~100x cheaper than a compile, and an
    # identical program may already be compiled under another signature.
    fingerprint, detail = step_fingerprint(trial, slots, cfg=cfg)
    try:
        done = session.get("/api/v1/compile_jobs",
                           params={"state": "DONE",
                                   "fingerprint": fingerprint})
    except Exception:
        done = {}
    for job in (done or {}).get("jobs", []):
        other = job.get("signature", "")
        if other and other != signature:
            session.post(f"/api/v1/compile_jobs/{signature}/link",
                         body={"from": other, "fingerprint": fingerprint},
                         idempotent=True)
            return {"signature": signature, "linked_from": other,
                    "fingerprint": fingerprint,
                    "wall_s": round(time.time() - t_start, 2)}

    devices = jax.devices()
    if slots > len(devices):
        raise RuntimeError(
            f"compile job needs {slots} devices, worker host has "
            f"{len(devices)} (set --xla_force_host_platform_device_count "
            "via the launcher on CPU hosts)")
    mesh = create_mesh(trial.mesh_config().resolve(slots), devices[:slots])
    tx = trial.optimizer()
    axes = trial.param_logical_axes()
    rules = trial.sharding_rules()
    state_sds = abstract_train_state(
        trial.init_params, tx, mesh, axes, rules, extra=trial.init_extra())

    from determined_tpu.compile.signature import _abstract_batch

    import numpy as np

    batch_sds = _abstract_batch(trial, None, cfg)
    rng_sds = jax.ShapeDtypeStruct((2,), np.uint32)

    files: Dict[str, bytes] = {}
    t0 = time.time()
    with jax.sharding.set_mesh(mesh):
        train_jit = make_train_step(
            trial.loss, tx, mesh=mesh, rules=rules,
            donate_state=trial.donate_state, stateful=trial.stateful)
        compiled = train_jit.lower(state_sds, batch_sds, rng_sds).compile()
        files[aot_artifact_name("train_step")] = serialize_compiled(compiled)
        # Eval step: best effort — validation shapes may be undrawable
        # without real data; the trial's jit path covers it either way.
        try:
            from determined_tpu.train.trial import JaxTrial

            if type(trial).evaluate is not JaxTrial.evaluate:
                val_batch = next(iter(trial.build_validation_data()), None)
                if val_batch is not None:
                    vb_sds = _abstract_batch(trial, val_batch, cfg)
                    eval_jit = make_eval_step(
                        trial.evaluate, mesh=mesh, rules=rules,
                        stateful=trial.stateful)
                    files[aot_artifact_name("eval_step")] = \
                        serialize_compiled(
                            eval_jit.lower(state_sds, vb_sds).compile())
        except Exception:
            logger.debug("eval step AOT skipped", exc_info=True)
    compile_ms = (time.time() - t0) * 1000.0

    files.update(client.collect_new_cache_files())
    client.upload(files, compile_ms=compile_ms, fingerprint=fingerprint)
    session.post(f"/api/v1/compile_jobs/{signature}",
                 body={"state": "DONE", "fingerprint": fingerprint,
                       "compile_ms": compile_ms},
                 idempotent=True)
    return {"signature": signature, "fingerprint": fingerprint,
            "compile_ms": round(compile_ms, 1), "artifacts": len(files),
            "bytes": sum(len(b) for b in files.values()),
            "wall_s": round(time.time() - t_start, 2)}


def main() -> int:
    logging.basicConfig(level=logging.INFO)
    from determined_tpu.common.api import Session

    master = os.environ.get("DET_MASTER", "")
    token = os.environ.get("DET_SESSION_TOKEN", "")
    signature = os.environ.get("DET_COMPILE_SIGNATURE", "")
    if not master or not signature:
        print("compile worker: DET_MASTER and DET_COMPILE_SIGNATURE required",
              file=sys.stderr)
        return 2
    hparams = json.loads(os.environ.get("DET_COMPILE_HPARAMS", "{}"))
    slots = int(os.environ.get("DET_COMPILE_SLOTS", "1"))
    experiment_id = int(os.environ.get("DET_EXPERIMENT_ID", "0"))
    config = json.loads(os.environ.get("DET_EXPERIMENT_CONFIG", "{}"))
    session = Session(master, token)
    try:
        summary = run_job(session, signature, hparams, slots, experiment_id,
                          config)
    except Exception as e:
        logger.exception("compile job %s failed", signature[:12])
        try:
            session.post(f"/api/v1/compile_jobs/{signature}",
                         body={"state": "FAILED",
                               "error": f"{type(e).__name__}: {e}"},
                         idempotent=True)
        except Exception:
            pass
        return 1
    print(json.dumps(summary))
    return 0
