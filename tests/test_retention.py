"""Checkpoint GC, log retention, job-queue reordering (VERDICT r2 #9).

Reference: checkpoint_gc.go:76 + exec/gc_checkpoints.py (GC runs as a
master-spawned zero-slot task), internal/logretention/, job queue
ahead-of/behind ops."""

import os
import time

import pytest

from tests.test_platform_e2e import (  # noqa: F401
    FIXTURES,
    Devcluster,
    _create_experiment,
    _experiment_config,
    _wait_experiment,
)


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def test_checkpoint_gc_retention(cluster, tmp_path):
    """Completed experiment keeps best + latest checkpoints only; the rest
    are deleted from storage by the GC task and marked DELETED in the
    registry."""
    storage_root = os.path.join(str(tmp_path), "checkpoints")
    config = _experiment_config(tmp_path)
    config["entrypoint"] = "python3 gc_train.py"
    config["checkpoint_storage"].update(
        save_experiment_best=0, save_trial_best=1, save_trial_latest=1)
    eid, token = _create_experiment(cluster, config, activate=True)
    _wait_experiment(cluster, eid, token)

    # fixture checkpoints at steps 2,4,6,8 with val=(s-4)^2: best=step4,
    # latest=step8 → steps 2 and 6 fall outside retention.
    deadline = time.time() + 60
    deleted = {}
    while time.time() < deadline:
        cps = cluster.api("GET", f"/api/v1/experiments/{eid}/checkpoints",
                          token=token)["checkpoints"]
        deleted = {c["uuid"]: c for c in cps if c["state"] == "DELETED"}
        if len(deleted) == 2:
            break
        time.sleep(0.5)
    assert len(deleted) == 2, f"GC did not run: {[(c['uuid'], c['state']) for c in cps]}"
    kept = {c["uuid"]: c for c in cps if c["state"] == "COMPLETED"}
    kept_steps = sorted(c["steps_completed"] for c in kept.values())
    assert kept_steps == [4, 8], kept_steps  # best + latest
    # files really deleted from storage / kept for the survivors
    for uuid in deleted:
        assert not os.path.isdir(os.path.join(storage_root, uuid)), uuid
    for uuid in kept:
        assert os.path.isdir(os.path.join(storage_root, uuid)), uuid


def test_log_retention_sweep(cluster):
    """Old task logs are deleted by the manual cleanup endpoint (the hourly
    sweep shares the same sweep_task_logs path)."""
    token = cluster.login()
    cluster.api("POST", "/api/v1/task/logs", {"logs": [
        {"task_id": "t-old", "log": "ancient line",
         "timestamp": "2020-01-01 00:00:00"},
        {"task_id": "t-new", "log": "fresh line"},
    ]}, token=token)
    admin = cluster.login("admin")  # cleanup is an admin operation
    out = cluster.api("POST", "/api/v1/master/cleanup_logs", {"days": 30},
                      token=admin)
    assert out["deleted"] == 1
    # idempotent second sweep
    out = cluster.api("POST", "/api/v1/master/cleanup_logs", {"days": 30},
                      token=admin)
    assert out["deleted"] == 0


def test_job_queue_reorder(cluster, tmp_path):
    """ahead-of moves a queued allocation in front of another."""
    token = cluster.login()
    # Fill both slots with a long-running experiment, then queue two more.
    cfgs = []
    for i in range(3):
        c = _experiment_config(
            tmp_path,
            searcher={"name": "single", "metric": "val_loss",
                      "max_length": {"batches": 400}},
        )
        c["name"] = f"queue-{i}"
        c["resources"] = {"slots_per_trial": 2, "priority": 40 + i}
        c["environment"] = {"TRIAL_STEP_SLEEP": "0.05"}
        cfgs.append(c)
    eids = [_create_experiment(cluster, c, activate=True)[0] for c in cfgs]

    def queued():
        jobs = cluster.api("GET", "/api/v1/job-queues", token=token)["jobs"]
        return [j for j in jobs if j["state"] == "QUEUED"]

    deadline = time.time() + 30
    while time.time() < deadline and len(queued()) < 2:
        time.sleep(0.3)
    q = queued()
    assert len(q) == 2, q
    # priority order: exp2 (41) ahead of exp3 (42). Move the last one ahead.
    last = next(j for j in q if j["priority"] == 42)
    first = next(j for j in q if j["priority"] == 41)
    # Queue reordering is an admin operation (jumps other users' work).
    cluster.api("POST", "/api/v1/job-queues/reorder", {
        "allocation_id": last["allocation_id"],
        "ahead_of": first["allocation_id"],
    }, token=cluster.login("admin"))
    q2 = queued()
    pos = {j["allocation_id"]: j["queue_position"] for j in q2}
    assert pos[last["allocation_id"]] < pos[first["allocation_id"]], q2
    # clean up: kill everything so teardown is fast
    for eid in eids:
        cluster.api("POST", f"/api/v1/experiments/{eid}/kill", token=token)
