"""Model lifecycle registry (docs/serving.md "Model lifecycle"):
train→serve auto-promotion and the checkpoint↔lifecycle GC guard.

Reference: the platform's model registry (registered models + versions)
grown into the full production loop — an experiment's `registry:` block
promotes its winning checkpoint on completion, and checkpoint GC must
never delete a checkpoint a registered version or a live deployment
still points at (same exclusion pattern as the compile_artifacts blob
guard)."""

import json
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def _gc_config(tmp_path, extra_env=None, registry=None):
    """gc_train fixture: checkpoints at steps 2,4,6,8, val=(s-4)^2 —
    best=4, latest=8, steps 2 and 6 outside default retention."""
    config = _experiment_config(tmp_path)
    config["entrypoint"] = "python3 gc_train.py"
    config["checkpoint_storage"].update(
        save_experiment_best=0, save_trial_best=1, save_trial_latest=1)
    if extra_env:
        config["environment"] = dict(extra_env)
    if registry:
        config["registry"] = registry
    return config


def _checkpoints_by_step(cluster, eid, token):
    cps = cluster.api("GET", f"/api/v1/experiments/{eid}/checkpoints",
                      token=token)["checkpoints"]
    return {c["steps_completed"]: c for c in cps}


def _wait_checkpoints(cluster, eid, token, steps, timeout=90.0):
    deadline = time.time() + timeout
    by_step = {}
    while time.time() < deadline:
        by_step = _checkpoints_by_step(cluster, eid, token)
        if all(s in by_step and by_step[s]["state"] == "COMPLETED"
               for s in steps):
            return by_step
        time.sleep(0.3)
    raise TimeoutError(f"checkpoints never completed: {by_step}")


def test_auto_promotion_best_then_latest(cluster, tmp_path):
    """`registry: {model, promote}`: completion registers the winning
    checkpoint — searcher-best validation for `best`, newest COMPLETED
    for `latest` — with train provenance on the version row and a
    `models` stream event, no pre-created model required."""
    token = cluster.login()
    # promote: best → the step-4 checkpoint (val=(s-4)^2 minimized).
    eid, _ = _create_experiment(
        cluster, _gc_config(tmp_path, registry={"model": "prod",
                                                "promote": "best"}),
        activate=True)
    _wait_experiment(cluster, eid, token)
    deadline = time.time() + 30
    vers = []
    while time.time() < deadline:
        vers = cluster.api("GET", "/api/v1/models/prod/versions",
                           token=token)["model_versions"]
        if vers:
            break
        time.sleep(0.3)
    assert len(vers) == 1, vers
    by_step = _checkpoints_by_step(cluster, eid, token)
    assert vers[0]["version"] == 1
    assert vers[0]["checkpoint_uuid"] == by_step[4]["uuid"]
    assert vers[0]["source_experiment_id"] == eid
    assert vers[0]["steps_completed"] == 4
    assert "auto-promoted (best)" in vers[0]["comment"]
    # The model row was auto-created by the promotion.
    model = cluster.api("GET", "/api/v1/models/prod", token=token)["model"]
    assert model["name"] == "prod"
    stream = cluster.api(
        "GET", "/api/v1/stream?entities=models&timeout_seconds=0",
        token=token)
    assert any(e["payload"].get("model") == "prod"
               and e["payload"].get("version") == 1
               for e in stream["events"]), stream

    # promote: latest on a second experiment → version 2 = its newest
    # checkpoint (step 8), same model.
    eid2, _ = _create_experiment(
        cluster, _gc_config(tmp_path, registry={"model": "prod",
                                                "promote": "latest"}),
        activate=True)
    _wait_experiment(cluster, eid2, token)
    deadline = time.time() + 30
    while time.time() < deadline:
        vers = cluster.api("GET", "/api/v1/models/prod/versions",
                           token=token)["model_versions"]
        if len(vers) == 2:
            break
        time.sleep(0.3)
    assert len(vers) == 2, vers
    by_step2 = _checkpoints_by_step(cluster, eid2, token)
    assert vers[1]["version"] == 2
    assert vers[1]["checkpoint_uuid"] == by_step2[8]["uuid"]
    assert vers[1]["source_experiment_id"] == eid2


def test_gc_excludes_registered_version(cluster, tmp_path):
    """Checkpoint GC never deletes a registered version's checkpoint:
    step 2 (outside retention) survives because it was registered mid-
    run; step 6 (also outside retention, unpinned) is the control that
    proves GC actually ran."""
    token = cluster.login()
    hold = os.path.join(str(tmp_path), "gc-hold")
    config = _gc_config(tmp_path, extra_env={"DET_GC_HOLD_FILE": hold})
    eid, _ = _create_experiment(cluster, config, activate=True)
    by_step = _wait_checkpoints(cluster, eid, token, steps=(2, 4, 6, 8))

    # Register the would-be-doomed step-2 checkpoint while the trial
    # holds, then release it: completion launches GC with the pin set.
    cluster.api("POST", "/api/v1/models",
                {"name": "pins", "metadata": {}, "labels": []}, token=token)
    ver = cluster.api("POST", "/api/v1/models/pins/versions",
                      {"checkpoint_uuid": by_step[2]["uuid"]},
                      token=token)["model_version"]
    assert ver["version"] == 1
    with open(hold, "w") as f:
        f.write("go")
    _wait_experiment(cluster, eid, token)

    # GC deletes exactly the unpinned out-of-retention checkpoint.
    deadline = time.time() + 60
    while time.time() < deadline:
        by_step = _checkpoints_by_step(cluster, eid, token)
        if by_step[6]["state"] == "DELETED":
            break
        time.sleep(0.5)
    assert by_step[6]["state"] == "DELETED", by_step
    assert by_step[2]["state"] == "COMPLETED", by_step
    assert by_step[4]["state"] == "COMPLETED"  # best, retention keeps it
    assert by_step[8]["state"] == "COMPLETED"  # latest
    storage_root = os.path.join(str(tmp_path), "checkpoints")
    assert os.path.isdir(os.path.join(storage_root, by_step[2]["uuid"]))
    assert not os.path.isdir(os.path.join(storage_root, by_step[6]["uuid"]))


def test_gc_excludes_live_deployment_checkpoint(cluster, tmp_path):
    """Checkpoint GC never deletes the checkpoint a live deployment is
    serving: step 6 survives because a deployment pins it (stable
    serving.checkpoint); unpinned step 2 is the control."""
    token = cluster.login()
    hold = os.path.join(str(tmp_path), "gc-hold-dep")
    config = _gc_config(tmp_path, extra_env={"DET_GC_HOLD_FILE": hold})
    eid, _ = _create_experiment(cluster, config, activate=True)
    by_step = _wait_checkpoints(cluster, eid, token, steps=(2, 4, 6, 8))

    dep_cfg = {
        "name": "pin-dep",
        "entrypoint": "python3 -m tests.fixtures.serving.fake_replica",
        "serving": {"model": "gpt2",
                    "checkpoint": by_step[6]["uuid"],
                    "replicas": {"min": 1, "max": 1, "target": 1}},
        "resources": {"slots_per_trial": 0},
    }
    dep_id = cluster.api("POST", "/api/v1/deployments",
                         {"config": dep_cfg}, token=token)["id"]
    with open(hold, "w") as f:
        f.write("go")
    _wait_experiment(cluster, eid, token)

    deadline = time.time() + 60
    while time.time() < deadline:
        by_step = _checkpoints_by_step(cluster, eid, token)
        if by_step[2]["state"] == "DELETED":
            break
        time.sleep(0.5)
    assert by_step[2]["state"] == "DELETED", by_step      # control: GC ran
    assert by_step[6]["state"] == "COMPLETED", by_step    # deployment pin
    storage_root = os.path.join(str(tmp_path), "checkpoints")
    assert os.path.isdir(os.path.join(storage_root, by_step[6]["uuid"]))
    cluster.api("POST", f"/api/v1/deployments/{dep_id}/kill", token=token)


def test_registry_resolution_survives_master_restart(cluster, tmp_path):
    """Lifecycle state is durable: registered versions, a deployment's
    model_version, and an armed canary split all restore on master boot
    (migration 26 columns), so a half-finished rollout resumes instead
    of resetting."""
    token = cluster.login()
    cluster.api("POST", "/api/v1/models",
                {"name": "m", "metadata": {}, "labels": []}, token=token)
    for uuid in ("ck-r1", "ck-r2"):
        cluster.api("POST", "/api/v1/checkpoints",
                    {"uuid": uuid, "state": "COMPLETED"}, token=token)
        cluster.api("POST", "/api/v1/models/m/versions",
                    {"checkpoint_uuid": uuid}, token=token)
    dep_cfg = {
        "name": "restart-dep",
        "entrypoint": "python3 -m tests.fixtures.serving.fake_replica",
        "serving": {"model": "gpt2", "model_version": "m:1",
                    "replicas": {"min": 1, "max": 2, "target": 1}},
        "resources": {"slots_per_trial": 0},
    }
    dep_id = cluster.api("POST", "/api/v1/deployments",
                         {"config": dep_cfg}, token=token)["id"]
    cluster.api("POST", f"/api/v1/deployments/{dep_id}/canary",
                {"model": "m", "version": 2, "fraction": 0.2}, token=token)

    cluster.kill_master()
    cluster.start_master()
    token = cluster.login()
    detail = cluster.api("GET", f"/api/v1/deployments/{dep_id}",
                         token=token)["deployment"]
    assert detail["model_version"] == "m:1"
    assert detail["canary"]["version"] == "m:2"
    assert detail["canary"]["fraction"] == 0.2
    vers = cluster.api("GET", "/api/v1/models/m/versions",
                       token=token)["model_versions"]
    assert [v["version"] for v in vers] == [1, 2]
    # Post-restart update still resolves through the registry.
    resp = cluster.api("POST", f"/api/v1/deployments/{dep_id}/update",
                       {"model": "m", "version": 2}, token=token)
    assert resp["checkpoint"] == "ck-r2"
