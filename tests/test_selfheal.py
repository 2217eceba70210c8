"""Self-healing training loop: checkpoint integrity, lineage rollback,
divergence sentinel, step watchdog (docs/checkpointing.md).

Fast tier-1 tests cover the two-phase commit protocol (manifest ± COMMIT,
every corruption mode), the remote-metadata fix, lineage resolution, the
three `on_nan` policies, watchdog fire/no-fire, and the stale-PARTIAL GC.
The `-m slow` chaos tests SIGKILL a real trial process mid-async-save and
assert the resume falls back to the previous COMPLETED checkpoint with
bit-identical state, and drive a `step.hang` through a real devcluster to
a watchdog stack dump + scheduler restart.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from determined_tpu import core
from determined_tpu.common import faultpoint
from determined_tpu.core import CorruptCheckpoint, _integrity
from determined_tpu.train import DivergenceError, StepWatchdog, Trainer
from determined_tpu.train.health import HealthConfig
from determined_tpu.train.trial import TrialContext
from determined_tpu.train.watchdog import WATCHDOG_EXIT_CODE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SELFHEAL_FIXTURES = os.path.join(REPO, "tests", "fixtures", "selfheal")
sys.path.insert(0, SELFHEAL_FIXTURES)

from trial_def import LinearTrial  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_faults():
    faultpoint.disarm_all()
    yield
    faultpoint.disarm_all()


def _local_core(tmp_path, max_length, async_save=False):
    return core.init(
        max_length=max_length,
        checkpoint_dir=str(tmp_path / "ckpts"),
        async_checkpointing=async_save,
    )


def _tree_equal(a, b) -> bool:
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    return len(leaves_a) == len(leaves_b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(leaves_a, leaves_b)
    )


# ---------------------------------------------------------------------------
# Integrity protocol unit tests (manifest + COMMIT).
# ---------------------------------------------------------------------------


def _fake_checkpoint(tmp_path, name="ck"):
    path = tmp_path / name
    (path / "state").mkdir(parents=True)
    (path / "state" / "shard-0").write_bytes(b"x" * 4096)
    (path / "state" / "shard-1").write_bytes(b"y" * 1024)
    (path / "metadata.json").write_text('{"steps_completed": 2}')
    return str(path)


def test_commit_then_verify_roundtrip(tmp_path):
    path = _fake_checkpoint(tmp_path)
    _integrity.commit(path, "ck")
    assert os.path.exists(os.path.join(path, "manifest.json"))
    assert os.path.exists(os.path.join(path, "COMMIT"))
    assert _integrity.verify(path, "ck") is True
    manifest = json.load(open(os.path.join(path, "manifest.json")))
    # every data file manifested with checksum; protocol files excluded
    assert set(manifest["files"]) == {
        "state/shard-0", "state/shard-1", "metadata.json"}
    assert all("sha256" in e for e in manifest["files"].values())


def test_verify_catches_truncation(tmp_path):
    path = _fake_checkpoint(tmp_path)
    _integrity.commit(path, "ck")
    with open(os.path.join(path, "state", "shard-0"), "r+b") as f:
        f.truncate(100)
    with pytest.raises(CorruptCheckpoint, match="size mismatch"):
        _integrity.verify(path, "ck")


def test_verify_catches_bitflip(tmp_path):
    path = _fake_checkpoint(tmp_path)
    _integrity.commit(path, "ck")
    # same size, different bytes: only the checksum can catch it
    with open(os.path.join(path, "state", "shard-1"), "r+b") as f:
        f.write(b"Z")
    with pytest.raises(CorruptCheckpoint, match="checksum mismatch"):
        _integrity.verify(path, "ck")


def test_verify_missing_commit_is_corrupt(tmp_path):
    path = _fake_checkpoint(tmp_path)
    _integrity.commit(path, "ck")
    os.unlink(os.path.join(path, "COMMIT"))
    with pytest.raises(CorruptCheckpoint, match="COMMIT"):
        _integrity.verify(path, "ck")


def test_verify_missing_file_is_corrupt(tmp_path):
    path = _fake_checkpoint(tmp_path)
    _integrity.commit(path, "ck")
    os.unlink(os.path.join(path, "state", "shard-1"))
    with pytest.raises(CorruptCheckpoint, match="missing file"):
        _integrity.verify(path, "ck")


def test_legacy_checkpoint_passes_unverified(tmp_path):
    # pre-protocol checkpoints (no manifest AND no COMMIT) stay restorable
    path = _fake_checkpoint(tmp_path)
    assert _integrity.verify(path, "ck") is False


def test_faultpoint_write_truncate_produces_catchable_corruption(tmp_path):
    path = _fake_checkpoint(tmp_path)
    faultpoint.arm(_integrity.FAULT_WRITE_TRUNCATE, "error", count=1)
    _integrity.commit(path, "ck")
    # COMMIT written (the torn write raced past the commit) — only
    # verification can tell this checkpoint is bad.
    assert os.path.exists(os.path.join(path, "COMMIT"))
    with pytest.raises(CorruptCheckpoint):
        _integrity.verify(path, "ck")


def test_faultpoint_commit_drop_leaves_partial(tmp_path):
    path = _fake_checkpoint(tmp_path)
    faultpoint.arm(_integrity.FAULT_COMMIT_DROP, "error", count=1)
    _integrity.commit(path, "ck")
    assert not os.path.exists(os.path.join(path, "COMMIT"))
    with pytest.raises(CorruptCheckpoint, match="COMMIT"):
        _integrity.verify(path, "ck")


# ---------------------------------------------------------------------------
# CheckpointContext: two-phase save, remote metadata, lineage.
# ---------------------------------------------------------------------------


def test_save_state_two_phase_async(tmp_path):
    ctx = _local_core(tmp_path, max_length=2, async_save=True)
    ck = ctx.checkpoint
    sid = ck.save_state({"w": np.arange(4.0, dtype=np.float32)}, 2)
    # phase 1 done, phase 2 pending: PARTIAL, no COMMIT marker yet
    assert ck.local_reported[0]["state"] == "PARTIAL"
    path = ck._storage.path_for(sid)
    assert not os.path.exists(os.path.join(path, "COMMIT"))
    ck.wait()
    assert os.path.exists(os.path.join(path, "COMMIT"))
    assert os.path.exists(os.path.join(path, "manifest.json"))
    # one record per checkpoint, flipped in place to COMPLETED
    assert [r["state"] for r in ck.local_reported] == ["COMPLETED"]
    assert ck.verify(sid) is True
    ctx.close()


class _StubCheckpointer:
    """Records orbax save calls without touching the (fake-remote) path."""

    def __init__(self):
        self.saved = []

    def save(self, path, state, force=False):
        self.saved.append(path)

    def wait_until_finished(self):
        pass

    def close(self):
        pass


class _FakeRemoteStorage:
    """gcs-shaped storage: url_for() streams to a 'bucket' (a local dir),
    upload/download/list_files act on the bucket like the cloud managers."""

    def __init__(self, base):
        from determined_tpu.storage.base import StorageManager

        self._fs = StorageManager(base)
        self.base_path = None  # no local scan path: remote-only backend

    def url_for(self, storage_id):
        return f"fake://bucket/{storage_id}"

    def upload(self, src, storage_id, paths=None):
        self._fs.upload(src, storage_id, paths)

    def download(self, storage_id, dst, selector=None):
        self._fs.download(storage_id, dst, selector)

    def list_files(self, storage_id):
        return self._fs.list_files(storage_id)

    def bucket_path(self, storage_id):
        return self._fs.path_for(storage_id)


def test_remote_checkpoint_gets_metadata_and_commit(tmp_path):
    """Satellite: remote/gcs checkpoints used to get NO metadata.json (it
    was only written for local chief paths), so resume lost
    steps_completed. The protocol files must land in the bucket too."""
    from determined_tpu.core._checkpoint import CheckpointContext

    storage = _FakeRemoteStorage(str(tmp_path / "bucket"))
    ck = CheckpointContext(None, storage, trial_id=0, async_save=True)
    ck._checkpointer = _StubCheckpointer()

    sid = ck.save_state({"w": np.arange(4.0)}, 3)
    assert ck._checkpointer.saved == [f"fake://bucket/{sid}/state"]
    bucket = storage.bucket_path(sid)
    assert os.path.exists(os.path.join(bucket, "metadata.json"))
    assert ck.local_reported[0]["state"] == "PARTIAL"

    ck.wait()
    assert os.path.exists(os.path.join(bucket, "manifest.json"))
    assert os.path.exists(os.path.join(bucket, "COMMIT"))
    assert ck.local_reported[0]["state"] == "COMPLETED"
    # the metadata fix end-to-end: resume can read steps_completed back
    assert ck.load_metadata(sid)["steps_completed"] == 3
    assert ck.verify(sid) is True

    # and the remote verifier catches a missing COMMIT
    os.unlink(os.path.join(bucket, "COMMIT"))
    with pytest.raises(CorruptCheckpoint, match="COMMIT"):
        ck.verify(sid)


def test_lineage_newest_first_and_skips_uncommitted(tmp_path):
    ctx = _local_core(tmp_path, max_length=4)
    ck = ctx.checkpoint
    state = {"w": np.arange(4.0, dtype=np.float32)}
    ck.save_state(state, 2)
    ck.save_state(state, 4)
    ck.wait()
    # fabricate a newer save whose commit never landed
    torso = ck._storage.path_for("trial0-step6")
    os.makedirs(os.path.join(torso, "state"))
    with open(os.path.join(torso, "state", "shard"), "w") as f:
        f.write("partial")
    assert ck.lineage() == ["trial0-step4", "trial0-step2"]
    ctx.close()

    # a FRESH process (empty local_reported) reconstructs the same lineage
    # from the COMMIT markers in storage
    ctx2 = _local_core(tmp_path, max_length=4)
    assert ctx2.checkpoint.lineage() == ["trial0-step4", "trial0-step2"]
    ctx2.close()


def test_restore_falls_back_through_lineage(tmp_path):
    """A COMPLETED-but-corrupt latest checkpoint (torn write) must restore
    the previous COMPLETED checkpoint — bit-identical — not start fresh."""
    ctx = _local_core(tmp_path, max_length=4)
    trial = LinearTrial(TrialContext())
    trainer = Trainer(trial, core_context=ctx)
    trainer.fit(report_period=1, checkpoint_period=2)  # ckpts at steps 2, 4
    ctx.close()

    # corrupt the newest checkpoint AFTER its commit (torn shard write)
    path4 = ctx.checkpoint._storage.path_for("trial0-step4")
    victim = None
    for root, _, files in os.walk(os.path.join(path4, "state")):
        for f in files:
            victim = os.path.join(root, f)
    with open(victim, "r+b") as f:
        f.truncate(max(0, os.path.getsize(victim) // 2))

    ctx2 = _local_core(tmp_path, max_length=4)
    trainer2 = Trainer(LinearTrial(TrialContext()), core_context=ctx2)
    trainer2._build(seed=0)
    restored = trainer2._restore("trial0-step4")
    assert restored == "trial0-step2"
    assert int(jax.device_get(trainer2.state.step)) == 2
    expected = ctx2.checkpoint.restore_state("trial0-step2", trainer2.state)
    assert _tree_equal(trainer2.state, expected)
    ctx2.close()


def test_restore_reraises_programming_errors(tmp_path):
    """Satellite: only missing/corrupt checkpoints fall through — a shape
    mismatch (wrong model for the checkpoint) is a bug and must raise, not
    silently discard training progress."""
    ctx = _local_core(tmp_path, max_length=2)
    trainer = Trainer(LinearTrial(TrialContext()), core_context=ctx)
    trainer.fit(report_period=1)  # checkpoint trial0-step2 at op end
    ctx.close()

    class WrongStructureTrial(LinearTrial):
        def init_params(self, rng):
            return {"v": jax.random.normal(rng, (4,))}  # key mismatch

    ctx2 = _local_core(tmp_path, max_length=2)
    trainer2 = Trainer(WrongStructureTrial(TrialContext()), core_context=ctx2)
    trainer2._build(seed=0)
    with pytest.raises(Exception) as err:
        trainer2._restore("trial0-step2")
    assert not isinstance(err.value, (FileNotFoundError, CorruptCheckpoint))
    ctx2.close()


# ---------------------------------------------------------------------------
# Divergence sentinel: on_nan = warn | fail | rollback.
# ---------------------------------------------------------------------------


class PoisonedTrial(LinearTrial):
    """Linear trial whose data stream contains NaN batches at fixed
    positions — loss and grads go non-finite exactly there."""

    poison_at = frozenset()

    def build_training_data(self):
        rng = np.random.default_rng(7)
        for i in range(200):
            x = rng.normal(size=(8, 4)).astype(np.float32)
            if i in self.poison_at:
                x[:] = np.nan
            yield {"x": x}


def _divergence_records(ctx):
    return [m for m in ctx.train.local_training_metrics
            if m["metrics"].get("divergence")]


def test_on_nan_warn_reports_and_continues(tmp_path):
    class T(PoisonedTrial):
        poison_at = frozenset({4})
        health = {"on_nan": "warn"}

    ctx = _local_core(tmp_path, max_length=8)
    state = Trainer(T(TrialContext()), core_context=ctx).fit(report_period=1)
    assert int(jax.device_get(state.step)) == 8  # trained through the NaN
    assert _divergence_records(ctx), "divergence event must be reported"
    ctx.close()


def test_on_nan_fail_raises(tmp_path):
    class T(PoisonedTrial):
        poison_at = frozenset({4})
        health = {"on_nan": "fail"}

    ctx = _local_core(tmp_path, max_length=8)
    with pytest.raises(DivergenceError):
        Trainer(T(TrialContext()), core_context=ctx).fit(report_period=1)
    ctx.close()


def test_on_nan_rollback_restores_and_completes(tmp_path):
    """The acceptance path: NaN at step 5, checkpoints at 2 and 4 → roll
    back to step 4, skip past the poisoned window, finish with finite
    state."""

    class T(PoisonedTrial):
        poison_at = frozenset({4})  # consumed by step 5
        health = {"on_nan": "rollback", "rollback_window": 2}

    ctx = _local_core(tmp_path, max_length=10)
    trainer = Trainer(T(TrialContext()), core_context=ctx)
    state = trainer.fit(report_period=1, checkpoint_period=2)
    assert int(jax.device_get(state.step)) == 10
    assert trainer._rollbacks == 1
    assert _divergence_records(ctx), "divergence event must be reported"
    final = np.asarray(jax.device_get(state.params["w"]))
    assert np.isfinite(final).all(), "rollback must purge the NaN state"
    ctx.close()


def test_on_nan_rollback_exhaustion_escalates(tmp_path):
    class T(PoisonedTrial):
        # everything past position 3 is poison: every rollback re-diverges
        poison_at = frozenset(range(3, 200))
        health = {"on_nan": "rollback", "rollback_window": 1,
                  "max_rollbacks": 2}

    ctx = _local_core(tmp_path, max_length=10)
    trainer = Trainer(T(TrialContext()), core_context=ctx)
    with pytest.raises(DivergenceError, match="rollback"):
        trainer.fit(report_period=1, checkpoint_period=2)
    assert trainer._rollbacks == 2
    ctx.close()


def test_on_nan_rollback_without_checkpoint_escalates(tmp_path):
    class T(PoisonedTrial):
        poison_at = frozenset({2})
        health = {"on_nan": "rollback"}

    ctx = _local_core(tmp_path, max_length=8)
    # no checkpoint_period: nothing COMPLETED exists before the NaN
    with pytest.raises(DivergenceError, match="no COMPLETED checkpoint"):
        Trainer(T(TrialContext()), core_context=ctx).fit(report_period=1)
    ctx.close()


def test_health_config_resolution():
    # trial attribute wins over expconf block; defaults otherwise
    cfg = HealthConfig.resolve(None, {"health": {"on_nan": "fail"}})
    assert cfg.on_nan == "fail"

    class T:
        health = {"on_nan": "rollback", "step_timeout_sec": 30}

    cfg = HealthConfig.resolve(T(), {"health": {"on_nan": "fail"}})
    assert cfg.on_nan == "rollback" and cfg.step_timeout_sec == 30
    assert HealthConfig.resolve(None, None) == HealthConfig()
    with pytest.raises(ValueError, match="on_nan"):
        HealthConfig.from_block({"on_nan": "explode"})


# ---------------------------------------------------------------------------
# Step watchdog: fire / no-fire.
# ---------------------------------------------------------------------------


class _FakeSession:
    def __init__(self):
        self.posts = []

    def post(self, path, body=None, **kw):
        self.posts.append((path, body))


def test_watchdog_does_not_fire_with_heartbeats(tmp_path):
    codes = []
    with open(tmp_path / "wd.log", "w+") as f:
        wd = StepWatchdog(0.5, exit_fn=codes.append, stream=f)
        wd.start()
        for _ in range(5):
            time.sleep(0.15)
            wd.beat()
        wd.stop()
    assert not wd.fired and codes == []


def test_watchdog_fires_dumps_stacks_and_reports(tmp_path):
    codes = []
    session = _FakeSession()
    f = open(tmp_path / "wd.log", "w+")
    wd = StepWatchdog(0.3, session=session, allocation_id="alloc-w",
                      exit_fn=codes.append, stream=f)
    wd.start()
    deadline = time.time() + 5
    while not wd.fired and time.time() < deadline:
        time.sleep(0.05)
    wd.stop()
    assert wd.fired and codes == [WATCHDOG_EXIT_CODE]
    f.seek(0)
    out = f.read()
    f.close()
    assert "watchdog: no training progress" in out
    assert "Thread" in out, "faulthandler stack dump must reach the log"
    assert session.posts and session.posts[0][0] == \
        "/api/v1/allocations/alloc-w/exit_reason"
    assert session.posts[0][1]["exit_code"] == WATCHDOG_EXIT_CODE


def test_watchdog_disabled_at_zero():
    wd = StepWatchdog(0.0)
    assert not wd.enabled
    wd.start()
    assert wd._thread is None
    wd.stop()


def test_step_hang_fires_watchdog_in_trainer(tmp_path, monkeypatch):
    """The trainer wiring end-to-end, minus the os._exit: an armed
    step.hang stall trips the watchdog fed by per-flush heartbeats."""
    import determined_tpu.train.trainer as trainer_mod

    fired = {}
    stream = open(tmp_path / "wd.log", "w+")
    real = trainer_mod.StepWatchdog

    class TestWatchdog(real):
        def __init__(self, timeout_sec, **kw):
            kw["exit_fn"] = lambda code: fired.setdefault("code", code)
            kw["stream"] = stream
            super().__init__(timeout_sec, **kw)

    monkeypatch.setattr(trainer_mod, "StepWatchdog", TestWatchdog)

    class T(LinearTrial):
        health = {"step_timeout_sec": 1.0}

    faultpoint.arm("step.hang", "delay-3000", count=1)
    ctx = _local_core(tmp_path, max_length=3)
    state = Trainer(T(TrialContext()), core_context=ctx).fit(report_period=1)
    # the injected exit_fn does not kill the process, so training resumes
    # after the stall — but the watchdog must have fired with code 87
    assert fired.get("code") == WATCHDOG_EXIT_CODE
    assert int(jax.device_get(state.step)) == 3
    stream.seek(0)
    assert "watchdog: no training progress" in stream.read()
    stream.close()
    ctx.close()


# ---------------------------------------------------------------------------
# GC: stale PARTIAL deletion (never the newest PARTIAL).
# ---------------------------------------------------------------------------


def test_gc_deletes_partial_uuids(tmp_path, monkeypatch):
    base = tmp_path / "ckstore"
    for name in ("doomed", "stale-partial", "kept"):
        (base / name).mkdir(parents=True)
        (base / name / "f").write_text("x")
    spec = {
        "checkpoint_storage": {"type": "shared_fs", "host_path": str(base)},
        "uuids": ["doomed"],
        "partial_uuids": ["stale-partial", "doomed"],  # dupe must not 2x
    }
    monkeypatch.setenv("DET_GC_SPEC", json.dumps(spec))
    monkeypatch.delenv("DET_MASTER", raising=False)
    from determined_tpu.exec import gc_checkpoints

    assert gc_checkpoints.main() == 0
    assert not (base / "doomed").exists()
    assert not (base / "stale-partial").exists()
    assert (base / "kept").exists()


# ---------------------------------------------------------------------------
# Chaos (slow): SIGKILL mid-async-save → lineage fallback, bit-identical.
# ---------------------------------------------------------------------------


def _run_crash_script(mode, ckpt_dir):
    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
    )
    return subprocess.run(
        [sys.executable, os.path.join(SELFHEAL_FIXTURES, "crash_resume.py"),
         mode, str(ckpt_dir)],
        env=env, capture_output=True, text=True, timeout=300)


def _assert_falls_back_bit_identical(ckpt_dir):
    """Resume against the torso of trial0-step4: restore must land on
    trial0-step2 with state equal to that checkpoint, bit for bit, and
    training must then run through."""
    ctx = core.init(max_length=4, checkpoint_dir=str(ckpt_dir),
                    async_checkpointing=False)
    trainer = Trainer(LinearTrial(TrialContext()), core_context=ctx)
    trainer._build(seed=0)
    restored = trainer._restore("trial0-step4")
    assert restored == "trial0-step2"
    expected = ctx.checkpoint.restore_state("trial0-step2", trainer.state)
    assert _tree_equal(trainer.state, expected)
    ctx.close()

    ctx2 = core.init(max_length=4, checkpoint_dir=str(ckpt_dir),
                     async_checkpointing=False)
    trainer2 = Trainer(LinearTrial(TrialContext()), core_context=ctx2)
    state = trainer2.fit(report_period=1, resume_from="trial0-step4")
    assert int(jax.device_get(state.step)) == 4
    # resumed from step 2, so only steps 3 and 4 were (re)trained
    steps = [m["steps_completed"] for m in ctx2.train.local_training_metrics]
    assert min(steps) == 3
    ctx2.close()


@pytest.mark.slow
def test_chaos_sigkill_after_truncated_commit_falls_back(tmp_path):
    """checkpoint.write.truncate + trial SIGKILL (the acceptance combo):
    the step-4 checkpoint COMMITs with a torn shard, the process dies by
    SIGKILL, and the resume detects the corruption by checksum and falls
    back to step 2."""
    ck = tmp_path / "ck"
    r = _run_crash_script("seed", ck)
    assert r.returncode == 0, r.stderr
    r = _run_crash_script("truncate-kill", ck)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr)
    # the torso COMMITted (the truncation raced past the commit)
    assert os.path.exists(ck / "trial0-step4" / "COMMIT")
    _assert_falls_back_bit_identical(ck)


@pytest.mark.slow
def test_chaos_killed_mid_commit_falls_back(tmp_path):
    """Death INSIDE the phase-2 commit (exit 137, the chaos crash mode):
    shards durable, no COMMIT marker — the resume treats the torso as
    corrupt without reading a single shard."""
    ck = tmp_path / "ck"
    r = _run_crash_script("seed", ck)
    assert r.returncode == 0, r.stderr
    r = _run_crash_script("commit-crash", ck)
    assert r.returncode == 137, (r.returncode, r.stderr)
    assert os.path.isdir(ck / "trial0-step4")
    assert not os.path.exists(ck / "trial0-step4" / "COMMIT")
    _assert_falls_back_bit_identical(ck)


# ---------------------------------------------------------------------------
# Chaos (slow): step.hang → watchdog stack dump → scheduler restart.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chaos_step_hang_watchdog_restart_e2e(tmp_path, native_binaries):
    """Acceptance: an injected step.hang produces an all-thread stack dump
    in the task log, a distinct exit reason, and a scheduler-driven
    restart that completes the trial."""
    import sqlite3

    from test_platform_e2e import Devcluster, _create_experiment, \
        _experiment_config, _wait_experiment

    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    try:
        marker_dir = os.path.join(str(tmp_path), "markers")
        os.makedirs(marker_dir)
        config = _experiment_config(
            tmp_path,
            searcher={"name": "single", "metric": "val_loss",
                      "max_length": {"batches": 6}},
            extra={"max_restarts": 2,
                   "entrypoint": "python3 watchdog_train.py"},
        )
        config["environment"] = {"WATCHDOG_MARKER_DIR": marker_dir}
        eid, token = _create_experiment(c, config)
        _wait_experiment(c, eid, token, timeout=240.0)

        trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                       token=token)["trials"]
        assert trials[0]["state"] == "COMPLETED"
        assert trials[0]["restarts"] >= 1, (
            "the watchdog exit must drive a scheduler restart")
        logs = c.api(
            "GET", f"/api/v1/tasks/trial-{trials[0]['id']}/logs?offset=0",
            token=token)["logs"]
        text = "\n".join(line["log"] for line in logs)
        assert "watchdog: no training progress" in text
        assert "Thread" in text, "all-thread stack dump must be in task log"
        assert "watchdog fixture: trial complete" in text

        # the distinct exit reason landed in the allocations table
        rows = sqlite3.connect(c.db_path).execute(
            "SELECT exit_reason FROM allocations").fetchall()
        assert any(r[0] and "watchdog" in r[0] for r in rows), rows
    finally:
        c.stop()
