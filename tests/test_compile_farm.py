"""Compile farm (docs/compile-farm.md): signatures, bucketing, AOT
executable round-trips, the Trainer's warm-start path, DTL205, the master's
job queue + artifact store, and the blob-sweep refcount regression.

The acceptance contract lives in test_trainer_warm_start_bit_identity: a
warm-cache trial's training trajectory is BIT-identical to a cold-compile
run of the same config — the deserialized executable is the same XLA
program, not an approximation of it.
"""

import base64
import json
import os
import sqlite3
import subprocess
import sys
import time

import numpy as np
import optax
import pytest

from test_platform_e2e import (
    Devcluster,
    _wait_experiment,
)

import jax

from determined_tpu import core as core_mod
from determined_tpu.analysis._preflight import preflight
from determined_tpu.analysis.config_rules import check_config
from determined_tpu.compile import (
    CompileConfig,
    FarmClient,
    aot_artifact_name,
    bucket_size,
    bucketed_iter,
    config_signature,
    pad_batch,
    step_fingerprint,
)
from determined_tpu.compile.runtime import load_compiled, serialize_compiled
from determined_tpu.train.step import make_train_step
from determined_tpu.train.trial import JaxTrial, TrialContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FARM_FIXTURES = os.path.join(REPO, "tests", "fixtures", "compile_farm")


class TinyTrial(JaxTrial):
    """Small but non-trivial: deterministic data, hparam-invariant lr."""

    prefetch = False

    def init_params(self, rng):
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (16, 32)) * 0.1,
                "w2": jax.random.normal(k2, (32, 4)) * 0.1}

    def loss(self, params, batch, rng):
        h = jax.numpy.tanh(batch["x"] @ params["w1"])
        pred = h @ params["w2"]
        return ((pred - batch["y"]) ** 2).mean()

    def optimizer(self):
        return optax.inject_hyperparams(optax.adamw)(
            learning_rate=float(self.context.hparams.get("lr", 1e-2)))

    def build_training_data(self):
        rng = np.random.default_rng(42)
        bs = int(self.context.hparams.get("global_batch_size", 8))
        while True:
            yield {"x": rng.normal(size=(bs, 16)).astype(np.float32),
                   "y": rng.normal(size=(bs, 4)).astype(np.float32)}


# ---------------------------------------------------------------- bucketing


def test_bucket_size_pow2_and_explicit():
    assert bucket_size(1) == 1
    assert bucket_size(5) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(5, [4, 16, 64]) == 16
    assert bucket_size(64, [4, 16, 64]) == 64
    # above the largest explicit bucket: exact (no silent mega-padding)
    assert bucket_size(65, [4, 16, 64]) == 65


def test_pad_batch_wraps_rows():
    b = {"x": np.arange(10, dtype=np.float32).reshape(5, 2),
         "y": np.arange(5), "scalar": np.float32(3.0)}
    p = pad_batch(b, 8)
    assert p["x"].shape == (8, 2) and p["y"].shape == (8,)
    # wrap-around: pad rows repeat real rows, never zeros
    assert (p["x"][5] == b["x"][0]).all() and (p["x"][7] == b["x"][2]).all()
    assert p["scalar"] == b["scalar"]
    # already at/above target: untouched
    assert pad_batch(b, 5)["x"] is b["x"]


def test_bucketed_iter_consistent_shapes():
    cfg = CompileConfig(bucket_batch_sizes=True)
    batches = [{"x": np.ones((n, 3), np.float32)} for n in (5, 6, 8, 9)]
    out = list(bucketed_iter(iter(batches), cfg))
    assert [b["x"].shape[0] for b in out] == [8, 8, 8, 16]


def test_compile_config_resolve_precedence():
    cfg = CompileConfig.resolve(None, {"compile": {"bucket_batch_sizes": True,
                                                   "max_executables": 4}})
    assert cfg.bucket_batch_sizes and cfg.max_executables == 4

    class T(TinyTrial):
        compile = {"enabled": False}

    t = T(TrialContext())
    assert not CompileConfig.resolve(t, {"compile": {"enabled": True}}).enabled
    assert CompileConfig.from_block(False).enabled is False
    assert CompileConfig.from_block(None).enabled is True


# --------------------------------------------------------------- signatures


def test_config_signature_key_properties():
    cfg = CompileConfig(bucket_batch_sizes=True)
    s1 = config_signature({"lr": 0.1, "global_batch_size": 48},
                          "python3 t.py", "h1", 1, cfg)
    # order-insensitive, bucket-merged
    s2 = config_signature({"global_batch_size": 60, "lr": 0.1},
                          "python3 t.py", "h1", 1, cfg)
    assert s1 == s2
    # every hparam value matters (no lossy shape guessing on this key)
    assert s1 != config_signature({"lr": 0.2, "global_batch_size": 48},
                                  "python3 t.py", "h1", 1, cfg)
    # entrypoint / model-def / slots all matter
    assert s1 != config_signature({"lr": 0.1, "global_batch_size": 48},
                                  "python3 other.py", "h1", 1, cfg)
    assert s1 != config_signature({"lr": 0.1, "global_batch_size": 48},
                                  "python3 t.py", "h2", 1, cfg)
    assert s1 != config_signature({"lr": 0.1, "global_batch_size": 48},
                                  "python3 t.py", "h1", 2, cfg)
    # without bucketing the raw batch size separates the keys
    s3 = config_signature({"lr": 0.1, "global_batch_size": 48},
                          "python3 t.py", "h1", 1)
    s4 = config_signature({"lr": 0.1, "global_batch_size": 60},
                          "python3 t.py", "h1", 1)
    assert s3 != s4


_FP_PROBE = """
import json, sys
sys.path.insert(0, {repo!r})
import numpy as np
sys.path.insert(0, {testdir!r})
from test_compile_farm import TinyTrial
from determined_tpu.compile import step_fingerprint
from determined_tpu.train.trial import TrialContext
fp, detail = step_fingerprint(TinyTrial(TrialContext({hp})), 1)
print(json.dumps({{"fp": fp}}))
"""


def _probe_fingerprint(hparams: dict) -> str:
    code = _FP_PROBE.format(repo=REPO,
                            testdir=os.path.join(REPO, "tests"),
                            hp=repr(hparams))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])["fp"]


def test_fingerprint_stable_across_processes():
    """Same config => identical signature across processes — the property
    that lets artifacts compiled on one host serve trials on another."""
    fp1 = _probe_fingerprint({"lr": 0.01})
    fp2 = _probe_fingerprint({"lr": 0.01})
    assert fp1 == fp2
    # and matches this process too
    fp3, _ = step_fingerprint(TinyTrial(TrialContext({"lr": 0.01})), 1)
    assert fp3 == fp1


def test_fingerprint_sensitivity():
    base, _ = step_fingerprint(TinyTrial(TrialContext({"lr": 0.01})), 1)

    # inject_hyperparams lr is optimizer STATE: hparam-invariant program
    same, _ = step_fingerprint(TinyTrial(TrialContext({"lr": 0.5})), 1)
    assert same == base

    # a BAKED lr is a jaxpr constant: the fingerprint must differ
    class Baked(TinyTrial):
        def optimizer(self):
            return optax.adamw(float(self.context.hparams.get("lr", 1e-2)))

    b1, _ = step_fingerprint(Baked(TrialContext({"lr": 0.01})), 1)
    b2, _ = step_fingerprint(Baked(TrialContext({"lr": 0.5})), 1)
    assert b1 != b2 and b1 != base

    # batch shape changes it...
    big, _ = step_fingerprint(
        TinyTrial(TrialContext({"global_batch_size": 16})), 1)
    assert big != base

    # ...unless bucketing folds the sizes into one bucket
    cfg = CompileConfig(bucket_batch_sizes=True)
    f6, _ = step_fingerprint(
        TinyTrial(TrialContext({"global_batch_size": 6})), 1, cfg=cfg)
    f8, _ = step_fingerprint(
        TinyTrial(TrialContext({"global_batch_size": 8})), 1, cfg=cfg)
    f9, _ = step_fingerprint(
        TinyTrial(TrialContext({"global_batch_size": 9})), 1, cfg=cfg)
    assert f6 == f8 and f9 != f8

    # donation pattern changes it
    class NoDonate(TinyTrial):
        donate_state = False

    nd, _ = step_fingerprint(NoDonate(TrialContext()), 1)
    assert nd != base

    # mesh shape changes it (2-device dp over the same program)
    class Mesh2(TinyTrial):
        def mesh_config(self):
            from determined_tpu.parallel.mesh import MeshConfig

            return MeshConfig(data=2)

    m2, _ = step_fingerprint(Mesh2(TrialContext()), 2)
    assert m2 != base

    # dtype changes it
    class F16(TinyTrial):
        def init_params(self, rng):
            p = TinyTrial.init_params(self, rng)
            return jax.tree_util.tree_map(
                lambda x: x.astype(jax.numpy.bfloat16), p)

    f16, _ = step_fingerprint(F16(TrialContext()), 1)
    assert f16 != base


def test_fingerprint_attention_impl_sensitivity():
    """`optimizations.attention_impl` is program identity (docs/
    training-perf.md): dense and reference trace to the SAME jaxpr (same
    arithmetic — the farm shares one executable), while the pallas kernel
    (and its bf16 variant) are different XLA programs and must fingerprint
    apart, or a warm farm would serve a dense executable to a flash trial."""
    from determined_tpu.models import gpt2

    def make_trial(impl, bf16=False):
        # pallas-supported geometry: d_model/n_head = 64, s % 128 == 0
        cfg = gpt2.Config(vocab_size=128, n_positions=128, d_model=256,
                          n_layer=1, n_head=4, remat=False,
                          attention_impl=impl, attention_bf16=bf16)

        class AttnTrial(JaxTrial):
            prefetch = False

            def init_params(self, rng):
                return gpt2.init(rng, cfg)

            def loss(self, params, batch, rng):
                return gpt2.loss_fn(params, batch, cfg)

            def optimizer(self):
                return optax.adamw(1e-3)

            def build_training_data(self):
                drng = np.random.default_rng(0)
                while True:
                    yield {"tokens": drng.integers(0, 128, size=(2, 129))
                           .astype(np.int32)}

        return AttnTrial(TrialContext())

    dense, _ = step_fingerprint(make_trial("dense"), 1)
    reference, _ = step_fingerprint(make_trial("reference"), 1)
    assert reference == dense  # identical arithmetic => shared executable

    pallas, _ = step_fingerprint(make_trial("pallas"), 1)
    assert pallas != dense

    pallas_bf16, _ = step_fingerprint(make_trial("pallas", bf16=True), 1)
    assert pallas_bf16 != pallas


# -------------------------------------------------------------- AOT runtime


def _fresh_state_and_step(trial):
    from determined_tpu.train.state import create_train_state

    tx = trial.optimizer()
    state = create_train_state(trial.init_params, tx, jax.random.PRNGKey(0))
    step = make_train_step(trial.loss, tx)
    return state, step


def test_aot_roundtrip_bit_identity():
    """serialize -> deserialize -> N steps must be bit-identical to the
    jit-dispatch path: a deserialized executable IS the same XLA program."""
    trial = TinyTrial(TrialContext())
    batch = next(iter(trial.build_training_data()))

    state_a, step = _fresh_state_and_step(trial)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state_a)
    batch_sds = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    rng_sds = jax.ShapeDtypeStruct((2,), np.uint32)
    blob = serialize_compiled(step.lower(abstract, batch_sds, rng_sds)
                              .compile())
    loaded = load_compiled(blob)

    state_b, _ = _fresh_state_and_step(trial)
    losses_a, losses_b = [], []
    for i in range(3):
        rng = jax.random.PRNGKey(i)
        state_a, ma = step(state_a, batch, rng)
        state_b, mb = loaded(state_b, batch, rng)
        losses_a.append(float(ma["loss"]))
        losses_b.append(float(mb["loss"]))
    assert losses_a == losses_b
    pa = jax.device_get(state_a.params)
    pb = jax.device_get(state_b.params)
    for la, lb in zip(jax.tree_util.tree_leaves(pa),
                      jax.tree_util.tree_leaves(pb)):
        assert np.array_equal(la, lb)


class _FakeSession:
    """Capture FarmClient round-trips without a master."""

    def __init__(self):
        self.store = {}  # signature -> {name: bytes}
        self.posts = []

    def get(self, path, params=None, **kw):
        sig = path.rsplit("/", 1)[-1]
        files = self.store.get(sig, {})
        name = (params or {}).get("name")
        return {"signature": sig, "files": [
            {"name": n, "b64": base64.b64encode(b).decode(), "size": len(b)}
            for n, b in files.items() if name is None or n == name]}

    def post(self, path, body=None, **kw):
        self.posts.append((path, body))
        if "/compile_cache/" in path:
            sig = path.rsplit("/", 1)[-1]
            dest = self.store.setdefault(sig, {})
            for n, b64 in (body or {}).get("files", {}).items():
                dest[n] = base64.b64decode(b64)
        return {}


def _run_trainer(tmp_path, run_name, farm_client=None, steps=4):
    """One local Trainer run; returns (final_state, training metrics)."""
    from determined_tpu.train.trainer import Trainer

    ctx = core_mod.init(
        max_length=steps,
        checkpoint_dir=os.path.join(str(tmp_path), f"ckpt-{run_name}"),
        async_checkpointing=False)
    try:
        trainer = Trainer(TinyTrial(TrialContext({"lr": 0.01})),
                          core_context=ctx)
        if farm_client is not None:
            trainer._farm = farm_client
        state = trainer.fit(report_period=steps, seed=7)
        return state, list(ctx.train.local_training_metrics)
    finally:
        ctx.close()


def test_trainer_warm_start_bit_identity(tmp_path, monkeypatch):
    """ACCEPTANCE: cold-compile run vs warm-cache run of the same config —
    identical loss series and bit-identical final params, with the warm
    run's first flush reporting compile_cache_hit=1."""
    monkeypatch.delenv("DET_COMPILE_SIGNATURE", raising=False)
    monkeypatch.delenv("DET_COMPILE_AOT_DIR", raising=False)
    sig = "farmtest-" + "0" * 8
    session = _FakeSession()

    # Cold run: fresh compile; the farm client exports + uploads the
    # serialized executable in the background (fit() joins the thread).
    cold_client = FarmClient(session, signature=sig, aot_dir="",
                             xla_cache_dir="")
    state_cold, metrics_cold = _run_trainer(tmp_path, "cold", cold_client)
    aot_name = aot_artifact_name("train_step")
    assert aot_name in session.store.get(sig, {}), (
        "fresh compile must upload its serialized executable")
    cold_flush = next(m["metrics"] for m in metrics_cold
                      if "compile_ms" in m["metrics"])
    assert cold_flush["compile_cache_hit"] == 0.0

    # Pre-warm the local AOT dir the way the agent does, then run warm
    # WITHOUT a session — artifacts come from disk alone.
    aot_dir = tmp_path / "aot_cache"
    (aot_dir / sig).mkdir(parents=True)
    (aot_dir / sig / aot_name).write_bytes(session.store[sig][aot_name])
    warm_client = FarmClient(None, signature=sig, aot_dir=str(aot_dir),
                             xla_cache_dir="")
    state_warm, metrics_warm = _run_trainer(tmp_path, "warm", warm_client)
    warm_flush = next(m["metrics"] for m in metrics_warm
                      if "compile_ms" in m["metrics"])
    assert warm_flush["compile_cache_hit"] == 1.0

    # Bit-identical trajectory: loss series and final params.
    assert [m["metrics"].get("loss") for m in metrics_cold] == \
        [m["metrics"].get("loss") for m in metrics_warm]
    for la, lb in zip(
            jax.tree_util.tree_leaves(jax.device_get(state_cold.params)),
            jax.tree_util.tree_leaves(jax.device_get(state_warm.params))):
        assert np.array_equal(la, lb)


def test_trainer_bad_artifact_falls_back(tmp_path):
    """A corrupt/mismatched AOT artifact must cost a fallback, never the
    trial: the run completes with cache_hit=0."""
    sig = "farmtest-bad"
    aot_dir = tmp_path / "aot"
    (aot_dir / sig).mkdir(parents=True)
    (aot_dir / sig / aot_artifact_name("train_step")).write_bytes(
        b"not a pickled executable")
    client = FarmClient(None, signature=sig, aot_dir=str(aot_dir),
                        xla_cache_dir="")
    state, metrics = _run_trainer(tmp_path, "bad", client)
    flush = next(m["metrics"] for m in metrics
                 if "compile_ms" in m["metrics"])
    assert flush["compile_cache_hit"] == 0.0
    assert state is not None


def test_farm_client_disabled_and_dead_sink():
    # no signature: every surface is a no-op
    c = FarmClient(None, signature="", aot_dir="", xla_cache_dir="")
    assert not c.enabled
    assert c.fetch("x") is None and c.load_executable("train_step") is None
    assert c.upload({"a": b"b"}) is False

    # a raising session must never propagate (farm is best-effort)
    class Dead:
        def get(self, *a, **k):
            raise ConnectionError("down")

        def post(self, *a, **k):
            raise ConnectionError("down")

    d = FarmClient(Dead(), signature="s", aot_dir="", xla_cache_dir="")
    assert d.fetch("x") is None
    assert d.upload({"a": b"b"}) is False


# ------------------------------------------------------------------- DTL205


def _sweep_config(**over):
    cfg = {
        "searcher": {"name": "random", "metric": "loss",
                     "max_length": {"batches": 8}, "max_trials": 32},
        "hyperparameters": {
            "lr": {"type": "log", "minval": -4, "maxval": -1},
            "global_batch_size": {"type": "int", "minval": 16,
                                  "maxval": 256},
        },
        "resources": {"slots_per_trial": 1},
        "entrypoint": "python3 t.py",
    }
    cfg.update(over)
    return cfg


def test_dtl205_fires_on_raw_batch_sweep():
    d = [x for x in check_config(_sweep_config()) if x.code == "DTL205"]
    assert len(d) == 1 and d[0].level == "warning"
    assert "global_batch_size" in d[0].message
    assert "bucket_batch_sizes" in d[0].message  # the actionable hint


def test_dtl205_bucketing_silences():
    cfg = _sweep_config(compile={"bucket_batch_sizes": True})
    assert not [x for x in check_config(cfg) if x.code == "DTL205"]


def test_dtl205_quiet_cases():
    # single searcher: one executable regardless
    cfg = _sweep_config(searcher={"name": "single", "metric": "loss",
                                  "max_length": {"batches": 8}})
    assert not [x for x in check_config(cfg) if x.code == "DTL205"]
    # non-shape sweep only
    cfg = _sweep_config(hyperparameters={
        "lr": {"type": "log", "minval": -4, "maxval": -1}})
    assert not [x for x in check_config(cfg) if x.code == "DTL205"]
    # max_trials bounds the executable count
    cfg = _sweep_config()
    cfg["searcher"]["max_trials"] = 4
    assert not [x for x in check_config(cfg) if x.code == "DTL205"]
    # raised ceiling
    cfg = _sweep_config(compile={"max_executables": 1000})
    assert not [x for x in check_config(cfg) if x.code == "DTL205"]


def test_dtl205_shape_categorical_and_unbounded_double():
    cfg = _sweep_config(hyperparameters={
        "d_model": {"type": "categorical",
                    "vals": [64 * i for i in range(1, 13)]}})
    assert [x for x in check_config(cfg) if x.code == "DTL205"]
    # double-sweeping a shape hparam without count: unbounded
    cfg = _sweep_config(hyperparameters={
        "hidden_size": {"type": "double", "minval": 64, "maxval": 1024}})
    d = [x for x in check_config(cfg) if x.code == "DTL205"]
    assert d and "unbounded" in d[0].message


def test_dtl205_suppressible():
    cfg = _sweep_config(preflight={"suppress": ["DTL205"]})
    report = preflight(cfg, context_dir=None)
    d = [x for x in report.diagnostics if x.code == "DTL205"]
    assert d and all(x.suppressed for x in d)


# ------------------------------------------------------------------ expconf


def test_expconf_compile_block():
    from determined_tpu import expconf

    base = {"entrypoint": "python3 t.py",
            "searcher": {"name": "single", "metric": "m",
                         "max_length": {"batches": 1}}}
    assert not expconf.validate(dict(base, compile={
        "enabled": True, "background": True, "bucket_batch_sizes": True,
        "buckets": [8, 16], "max_executables": 4, "upload": False}))
    assert not expconf.validate(dict(base, compile=True))
    assert expconf.validate(dict(base, compile={"bogus": 1}))
    assert expconf.validate(dict(base, compile={"max_executables": 0}))
    assert expconf.validate(dict(base, compile={"buckets": []}))
    assert expconf.validate(dict(base, compile={"buckets": [0]}))
    assert expconf.validate(dict(base, compile={"background": "yes"}))
    assert expconf.validate(dict(base, compile=3))
    c = expconf.apply_defaults(dict(base))
    assert c["compile"] == {"enabled": True, "background": False,
                            "bucket_batch_sizes": False,
                            "max_executables": 8, "upload": True}


# ------------------------------------------- master: queue + artifact store


@pytest.fixture()
def master_only(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    yield c
    c.stop()


def _upload_artifacts(cluster, token, sig, files, **extra):
    body = {"files": {n: base64.b64encode(b).decode()
                      for n, b in files.items()}}
    body.update(extra)
    return cluster.api("POST", f"/api/v1/compile_cache/{sig}", body,
                       token=token)


def test_master_compile_cache_roundtrip(master_only):
    cluster = master_only
    token = cluster.login()
    sig = "a" * 64
    files = {"aot-train_step-deadbeef.bin": b"\x00\x01exec",
             "xlacache-entry": b"cachedata"}
    out = _upload_artifacts(cluster, token, sig, files, compile_ms=1234.0,
                            fingerprint="fp1")
    assert out["stored"] == 2

    got = cluster.api("GET", f"/api/v1/compile_cache/{sig}", token=token)
    assert {f["name"] for f in got["files"]} == set(files)
    for f in got["files"]:
        assert base64.b64decode(f["b64"]) == files[f["name"]]

    # ?name= filter
    got = cluster.api(
        "GET", f"/api/v1/compile_cache/{sig}?name=xlacache-entry",
        token=token)
    assert [f["name"] for f in got["files"]] == ["xlacache-entry"]

    # artifact arrival marked the job DONE with the fingerprint
    jobs = cluster.api("GET", "/api/v1/compile_jobs?state=DONE",
                       token=token)["jobs"]
    row = next(j for j in jobs if j["signature"] == sig)
    assert row["fingerprint"] == "fp1"
    assert row["compile_ms"] == 1234.0

    # idempotent re-upload: no duplicate rows, no double blob claims
    out = _upload_artifacts(cluster, token, sig, files)
    assert out["stored"] == 0


def test_master_compile_jobs_link_and_fingerprint_query(master_only):
    cluster = master_only
    token = cluster.login()
    sig_a, sig_b = "b" * 64, "c" * 64
    _upload_artifacts(cluster, token, sig_a,
                      {"aot-train_step-t.bin": b"exec-a"},
                      fingerprint="sharedfp")
    # worker's pre-compile lookup: DONE jobs by fingerprint
    jobs = cluster.api(
        "GET", "/api/v1/compile_jobs?state=DONE&fingerprint=sharedfp",
        token=token)["jobs"]
    assert [j["signature"] for j in jobs] == [sig_a]

    out = cluster.api("POST", f"/api/v1/compile_jobs/{sig_b}/link",
                      {"from": sig_a, "fingerprint": "sharedfp"},
                      token=token)
    assert out["linked"] == 1
    got = cluster.api("GET", f"/api/v1/compile_cache/{sig_b}", token=token)
    assert [f["name"] for f in got["files"]] == ["aot-train_step-t.bin"]
    assert base64.b64decode(got["files"][0]["b64"]) == b"exec-a"


def test_master_enqueue_on_trial_create(master_only, tmp_path):
    """compile.background experiments enumerate one QUEUED job per
    distinct signature at trial creation; no-block experiments enqueue
    nothing."""
    import determined_tpu.cli as cli

    cluster = master_only
    token = cluster.login()
    model_def = cli._tar_context(FARM_FIXTURES)

    def config(name, background):
        c = {
            "name": name,
            "entrypoint": "python3 train_farm.py",
            "searcher": {"name": "random", "metric": "val_loss",
                         "max_length": {"batches": 2}, "max_trials": 3},
            "hyperparameters": {"lr": 0.01, "global_batch_size": 8},
            "resources": {"slots_per_trial": 1},
            "checkpoint_storage": {
                "type": "shared_fs",
                "host_path": os.path.join(str(tmp_path), "ckpts")},
        }
        if background:
            c["compile"] = {"background": True}
        return c

    cluster.api("POST", "/api/v1/experiments",
                {"config": config("no-farm", False),
                 "model_definition": model_def, "activate": True},
                token=token)
    jobs = cluster.api("GET", "/api/v1/compile_jobs", token=token)["jobs"]
    assert jobs == []

    eid = cluster.api("POST", "/api/v1/experiments",
                      {"config": config("farm", True),
                       "model_definition": model_def, "activate": True},
                      token=token)["id"]
    jobs = cluster.api("GET", "/api/v1/compile_jobs", token=token)["jobs"]
    # 3 trials, identical (const) hparams -> exactly one signature
    assert len(jobs) == 1
    assert jobs[0]["state"] == "QUEUED"  # no agent: nothing to dispatch to
    assert jobs[0]["experiment_id"] == eid
    assert jobs[0]["slots"] == 1

    # prometheus sees the queue
    import urllib.request

    req = urllib.request.Request(
        cluster.master_url + "/metrics",
        headers={"Authorization": f"Bearer {token}"})
    with urllib.request.urlopen(req, timeout=10) as resp:
        text = resp.read().decode()
    assert 'det_compile_jobs{state="QUEUED"} 1' in text
    assert "det_compile_artifact_uploads_total" in text


def test_blob_sweep_respects_compile_artifacts(master_only):
    """REGRESSION (docs/compile-farm.md): the blob sweep must not GC a
    blob a live compile-artifact row references, even at refcount 0 —
    linked signatures reference blobs without fresh claims."""
    cluster = master_only
    token = cluster.login()
    sig = "d" * 64
    _upload_artifacts(cluster, token, sig, {"aot-x.bin": b"payload"})

    db = sqlite3.connect(cluster.db_path)
    try:
        (blob_hash,) = db.execute(
            "SELECT blob_hash FROM compile_artifacts WHERE signature=?",
            (sig,)).fetchone()
        # Simulate every claim draining away (task/experiment releases).
        db.execute("UPDATE model_defs SET refcount=0 WHERE hash=?",
                   (blob_hash,))
        # Control: an unreferenced zero-refcount blob must still be swept.
        db.execute(
            "INSERT INTO model_defs (hash, blob, refcount) "
            "VALUES ('unreferenced-hash', 'x', 0)")
        db.commit()
    finally:
        db.close()

    admin_token = cluster.login("admin")
    cluster.api("POST", "/api/v1/master/cleanup_blobs", {},
                token=admin_token)

    db = sqlite3.connect(cluster.db_path)
    try:
        assert db.execute(
            "SELECT COUNT(*) FROM model_defs WHERE hash=?",
            (blob_hash,)).fetchone()[0] == 1, "artifact blob was GC'd"
        assert db.execute(
            "SELECT COUNT(*) FROM model_defs WHERE hash='unreferenced-hash'"
        ).fetchone()[0] == 0, "control blob should have been swept"
    finally:
        db.close()

    # and the artifact still serves
    got = cluster.api("GET", f"/api/v1/compile_cache/{sig}", token=token)
    assert base64.b64decode(got["files"][0]["b64"]) == b"payload"


def test_compile_artifact_ttl_eviction(tmp_path, native_binaries):
    """Retention (compile_cache.ttl_days, ROADMAP item 5 leftover): the
    blob sweep evicts artifact rows past the TTL — INCLUDING rows holding
    a blob only through a fingerprint link — so their blobs get swept,
    while fresh artifacts survive untouched. Default-off: artifacts on a
    master without the flag persist forever (the pre-TTL behavior)."""
    cluster = Devcluster(str(tmp_path), native_binaries)
    cluster.start_master(extra_args=["--compile-ttl-days", "7"])
    try:
        token = cluster.login()
        sig_old, sig_linked, sig_fresh = "e" * 64, "f" * 64, "0" * 64
        _upload_artifacts(cluster, token, sig_old,
                          {"aot-old.bin": b"old-exec"},
                          fingerprint="ttlfp")
        # Linked signature: holds the SAME blob through the link only.
        cluster.api("POST", f"/api/v1/compile_jobs/{sig_linked}/link",
                    {"from": sig_old, "fingerprint": "ttlfp"}, token=token)
        _upload_artifacts(cluster, token, sig_fresh,
                          {"aot-fresh.bin": b"fresh-exec"})

        db = sqlite3.connect(cluster.db_path)
        try:
            (old_hash,) = db.execute(
                "SELECT blob_hash FROM compile_artifacts WHERE signature=?",
                (sig_old,)).fetchone()
            # Age the original AND the linked rows past the 7-day TTL;
            # drain the upload's task claim so only compile_artifacts
            # holds the blob (the linked-row scenario).
            db.execute(
                "UPDATE compile_artifacts SET "
                "created_at = datetime('now', '-10 days') "
                "WHERE signature IN (?, ?)", (sig_old, sig_linked))
            db.execute("UPDATE model_defs SET refcount=0 WHERE hash=?",
                       (old_hash,))
            db.commit()
        finally:
            db.close()

        admin = cluster.login("admin")
        out = cluster.api("POST", "/api/v1/master/cleanup_blobs", {},
                          token=admin)
        assert out["compile_artifacts_evicted"] == 2, out

        db = sqlite3.connect(cluster.db_path)
        try:
            # Expired rows gone (both the original and the linked one),
            # their job rows re-enqueueable, their blob swept.
            assert db.execute(
                "SELECT COUNT(*) FROM compile_artifacts WHERE "
                "signature IN (?, ?)", (sig_old, sig_linked)
            ).fetchone()[0] == 0
            assert db.execute(
                "SELECT COUNT(*) FROM compile_jobs WHERE "
                "signature IN (?, ?)", (sig_old, sig_linked)
            ).fetchone()[0] == 0
            assert db.execute(
                "SELECT COUNT(*) FROM model_defs WHERE hash=?",
                (old_hash,)).fetchone()[0] == 0, "expired blob not swept"
            # The fresh artifact and its blob survive.
            assert db.execute(
                "SELECT COUNT(*) FROM compile_artifacts WHERE signature=?",
                (sig_fresh,)).fetchone()[0] == 1
        finally:
            db.close()
        got = cluster.api("GET", f"/api/v1/compile_cache/{sig_fresh}",
                          token=token)
        assert base64.b64decode(got["files"][0]["b64"]) == b"fresh-exec"
        got = cluster.api("GET", f"/api/v1/compile_cache/{sig_old}",
                          token=token)
        assert got["files"] == []
    finally:
        cluster.stop()


def test_compile_artifact_ttl_off_by_default(master_only):
    """No ttl flag → aged artifacts persist through the sweep."""
    cluster = master_only
    token = cluster.login()
    sig = "9" * 64
    _upload_artifacts(cluster, token, sig, {"aot-keep.bin": b"keep"})
    db = sqlite3.connect(cluster.db_path)
    try:
        db.execute(
            "UPDATE compile_artifacts SET "
            "created_at = datetime('now', '-400 days') WHERE signature=?",
            (sig,))
        db.commit()
    finally:
        db.close()
    out = cluster.api("POST", "/api/v1/master/cleanup_blobs", {},
                      token=cluster.login("admin"))
    assert out["compile_artifacts_evicted"] == 0
    got = cluster.api("GET", f"/api/v1/compile_cache/{sig}", token=token)
    assert base64.b64decode(got["files"][0]["b64"]) == b"keep"


def test_worker_run_job_compiles_and_uploads(master_only, tmp_path,
                                             monkeypatch):
    """The farm worker end to end against a real master: download the
    model-def, trace the fingerprint, AOT-compile, upload artifacts, mark
    the job DONE — then a second signature with the same fingerprint LINKS
    instead of recompiling."""
    import determined_tpu.cli as cli
    from determined_tpu.common.api import Session
    from determined_tpu.compile.worker import run_job

    # Tiny model: the worker compiles a real GPT-2 step; keep it fast.
    monkeypatch.setenv("FARM_D_MODEL", "64")
    monkeypatch.setenv("FARM_N_LAYER", "1")
    monkeypatch.setenv("DET_XLA_CACHE_DIR",
                       os.path.join(str(tmp_path), "xla"))

    cluster = master_only
    token = cluster.login()
    model_def = cli._tar_context(FARM_FIXTURES)
    config = {
        "name": "worker-test",
        "entrypoint": "python3 train_farm.py",
        "searcher": {"name": "single", "metric": "val_loss",
                     "max_length": {"batches": 2}},
        "hyperparameters": {"lr": 0.01, "global_batch_size": 4},
        "resources": {"slots_per_trial": 1},
    }
    eid = cluster.api("POST", "/api/v1/experiments",
                      {"config": config, "model_definition": model_def,
                       "activate": False}, token=token)["id"]
    session = Session(cluster.master_url, token)

    sig = "e" * 64
    summary = run_job(session, sig, {"lr": 0.01, "global_batch_size": 4}, 1,
                      eid, config)
    assert summary["artifacts"] >= 1 and summary["compile_ms"] > 0

    got = cluster.api("GET", f"/api/v1/compile_cache/{sig}", token=token)
    names = {f["name"] for f in got["files"]}
    assert any(n.startswith("aot-train_step-") for n in names)
    jobs = cluster.api("GET", "/api/v1/compile_jobs?state=DONE",
                       token=token)["jobs"]
    row = next(j for j in jobs if j["signature"] == sig)
    assert row["fingerprint"] == summary["fingerprint"]

    # Same program under a different signature (e.g. a different lr with
    # inject_hyperparams): the worker links, no second compile.
    sig2 = "f" * 64
    summary2 = run_job(session, sig2, {"lr": 0.5, "global_batch_size": 4},
                       1, eid, config)
    assert summary2.get("linked_from") == sig
    got2 = cluster.api("GET", f"/api/v1/compile_cache/{sig2}", token=token)
    assert {f["name"] for f in got2["files"]} == names


# ------------------------------------------------------------- slow e2e


@pytest.mark.slow
def test_e2e_background_compile_on_idle_agent(tmp_path, native_binaries):
    """Queued time becomes compile time: an unplaceable trial (needs 2
    slots on a 1-slot agent) leaves the agent idle; the master dispatches
    the compile job to it; the worker compiles and uploads while the trial
    is still waiting."""
    import determined_tpu.cli as cli

    cluster = Devcluster(str(tmp_path), native_binaries, slots=1)
    try:
        cluster.start_master()
        cluster.start_agent()
        token = cluster.login()
        model_def = cli._tar_context(FARM_FIXTURES)
        config = {
            "name": "farm-bg",
            "entrypoint": "python3 train_farm.py",
            "searcher": {"name": "single", "metric": "val_loss",
                         "max_length": {"batches": 2}},
            "hyperparameters": {"lr": 0.01, "global_batch_size": 4},
            "resources": {"slots_per_trial": 2},  # never places on 1 slot
            "compile": {"background": True},
            "environment": {"environment_variables":
                            ["FARM_D_MODEL=64", "FARM_N_LAYER=1"]},
            "checkpoint_storage": {
                "type": "shared_fs",
                "host_path": os.path.join(str(tmp_path), "ckpts")},
        }
        eid = cluster.api("POST", "/api/v1/experiments",
                          {"config": config, "model_definition": model_def,
                           "activate": True}, token=token)["id"]
        deadline = time.time() + 240
        row = None
        while time.time() < deadline:
            jobs = cluster.api("GET", "/api/v1/compile_jobs",
                               token=token)["jobs"]
            row = next((j for j in jobs if j["experiment_id"] == eid), None)
            if row and row["state"] in ("DONE", "FAILED"):
                break
            time.sleep(2)
        assert row is not None and row["state"] == "DONE", row
        sig = row["signature"]
        got = cluster.api("GET", f"/api/v1/compile_cache/{sig}",
                          token=token)
        assert any(f["name"].startswith("aot-train_step-")
                   for f in got["files"])
        cluster.api("POST", f"/api/v1/experiments/{eid}/kill", token=token)
    finally:
        cluster.stop()


@pytest.mark.slow
def test_e2e_warm_trial_cache_hit(tmp_path, native_binaries):
    """The full loop on a devcluster: trial 1 compiles fresh and uploads;
    the agent pre-warms trial 2's caches before its container starts;
    trial 2 reports cache_hit with a near-zero compile span."""
    import determined_tpu.cli as cli

    cluster = Devcluster(str(tmp_path), native_binaries, slots=1)
    try:
        cluster.start_master()
        cluster.start_agent()
        token = cluster.login()
        model_def = cli._tar_context(FARM_FIXTURES)
        config = {
            "name": "farm-warm",
            "entrypoint": "python3 train_farm.py",
            # const hparams: both trials share one signature
            "searcher": {"name": "random", "metric": "val_loss",
                         "max_length": {"batches": 2}, "max_trials": 2,
                         "max_concurrent_trials": 1},
            "hyperparameters": {"lr": 0.01, "global_batch_size": 4},
            "resources": {"slots_per_trial": 1},
            "environment": {"environment_variables":
                            ["FARM_D_MODEL=256", "FARM_N_LAYER=2"]},
            "checkpoint_storage": {
                "type": "shared_fs",
                "host_path": os.path.join(str(tmp_path), "ckpts")},
            "max_restarts": 0,
        }
        eid = cluster.api("POST", "/api/v1/experiments",
                          {"config": config, "model_definition": model_def,
                           "activate": True}, token=token)["id"]
        _wait_experiment(cluster, eid, token, timeout=600)
        trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                             token=token)["trials"]
        assert len(trials) == 2
        per_trial = {}
        for t in trials:
            for m in cluster.api("GET",
                                 f"/api/v1/trials/{t['id']}/metrics",
                                 token=token)["metrics"]:
                mm = m["metrics"]
                if "compile_ms" in mm:
                    per_trial[t["id"]] = (float(mm["compile_ms"]),
                                          float(mm["compile_cache_hit"]))
        assert len(per_trial) == 2, per_trial
        ordered = [per_trial[t["id"]] for t in
                   sorted(trials, key=lambda x: x["id"])]
        (cold_ms, cold_hit), (warm_ms, warm_hit) = ordered
        assert cold_hit == 0.0 and warm_hit == 1.0, ordered
        # the headline: warm compile is a deserialize, not a compile
        assert warm_ms < cold_ms / 3, ordered

        # spans: trial 2 has agent.cache_warm with files>0 and a
        # harness.compile span with cache_hit true
        t2 = sorted(trials, key=lambda x: x["id"])[1]
        spans = cluster.api("GET", f"/api/v1/trials/{t2['id']}/trace",
                            token=token)["spans"]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        warm_spans = by_name.get("agent.cache_warm", [])
        assert warm_spans and any(
            int(s["attrs"].get("files", 0)) > 0 for s in warm_spans), spans
        compile_spans = by_name.get("harness.compile", [])
        assert any(s["attrs"].get("cache_hit") for s in compile_spans)
        assert all(s["attrs"].get("signature") for s in compile_spans)
    finally:
        cluster.stop()
