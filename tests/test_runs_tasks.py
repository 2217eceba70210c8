"""Runs view + generic task trees (reference api_runs.go:70 SearchRuns,
api_runs.go:262 MoveRuns, api_generic_tasks.go:207/:432)."""

import time

import pytest

from tests.test_platform_e2e import (
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


def test_runs_flat_view_and_move(cluster, tmp_path):
    eid, token = _create_experiment(
        cluster, _experiment_config(tmp_path), activate=True)
    _wait_experiment(cluster, eid, token)

    runs = cluster.api("GET", "/api/v1/runs", token=token)["runs"]
    mine = [r for r in runs if r["experiment_id"] == eid]
    assert mine and mine[0]["state"] == "COMPLETED"
    assert mine[0]["experiment_name"] == "e2e-fixture"
    assert "lr" in mine[0]["hparams"]

    # filters
    runs = cluster.api(
        "GET", f"/api/v1/runs?experiment_id={eid}&state=COMPLETED",
        token=token)["runs"]
    assert len(runs) == 1

    # move to a new project
    proj = cluster.api(
        "POST", "/api/v1/projects",
        {"name": "moved-into", "workspace_id": 1}, token=token)
    pid = proj.get("id") or proj.get("project", {}).get("id")
    out = cluster.api("POST", "/api/v1/runs/move",
                      {"run_ids": [mine[0]["id"]], "project_id": pid},
                      token=token)
    assert out["moved"] == 1
    runs = cluster.api(
        "GET", f"/api/v1/runs?project_id={pid}", token=token)["runs"]
    assert [r["id"] for r in runs] == [mine[0]["id"]]


def test_generic_task_tree_kill_propagates(cluster):
    token = cluster.login()
    parent = cluster.api(
        "POST", "/api/v1/generic-tasks",
        {"config": {"entrypoint": "sleep 600"}}, token=token)
    child = cluster.api(
        "POST", "/api/v1/generic-tasks",
        {"config": {"entrypoint": "sleep 600"},
         "parent_task_id": parent["id"]}, token=token)
    # both running
    deadline = time.time() + 30
    while time.time() < deadline:
        states = [
            cluster.api("GET", f"/api/v1/generic-tasks/{t['id']}",
                        token=token)["task"].get("allocation_state")
            for t in (parent, child)
        ]
        if states == ["RUNNING", "RUNNING"]:
            break
        time.sleep(0.3)
    assert states == ["RUNNING", "RUNNING"], states

    cluster.api("POST", f"/api/v1/generic-tasks/{parent['id']}/kill",
                token=token)
    deadline = time.time() + 30
    while time.time() < deadline:
        rows = [cluster.api("GET", f"/api/v1/generic-tasks/{t['id']}",
                            token=token)["task"] for t in (parent, child)]
        if all(r["state"] == "CANCELED" for r in rows):
            break
        time.sleep(0.3)
    assert all(r["state"] == "CANCELED" for r in rows), rows


def test_generic_task_bad_parent_rejected(cluster):
    token = cluster.login()
    import urllib.error

    with pytest.raises(urllib.error.HTTPError):
        cluster.api("POST", "/api/v1/generic-tasks",
                    {"config": {"entrypoint": "true"},
                     "parent_task_id": "no-such"}, token=token)


def test_completed_task_logs_immediately_readable(cluster):
    """Log durability vs task completion (VERDICT r4 weak #1): the agent
    must ship remaining log lines BEFORE the exit report, so the moment a
    task reads terminal its logs are already served. Two shapes: a fast
    task that exits on its own, and a killed task."""
    token = cluster.login()

    def logs_text(tid):
        logs = cluster.api("GET", f"/api/v1/tasks/{tid}/logs",
                           token=token)["logs"]
        return "\n".join(line["log"] for line in logs)

    # (a) fast-exit: marker printed immediately before exit
    tid = cluster.api(
        "POST", "/api/v1/commands",
        {"config": {"entrypoint":
                    "python3 -c \"print('durable-marker-%d' % (41+1))\""}},
        token=token)["id"]
    deadline = time.time() + 60
    state = None
    while time.time() < deadline:
        t = cluster.api("GET", f"/api/v1/commands/{tid}", token=token)["task"]
        state = t["state"]
        if state in ("COMPLETED", "ERROR", "CANCELED"):
            break
        time.sleep(0.05)
    assert state == "COMPLETED", state
    # NO sleep here — terminal state must imply logs are durable.
    assert "durable-marker-42" in logs_text(tid)

    # (b) killed mid-run: everything printed before the kill must be there
    tid2 = cluster.api(
        "POST", "/api/v1/commands",
        {"config": {"entrypoint":
                    "python3 -u -c \"print('pre-kill-%d' % (50+5)); "
                    "import time; time.sleep(600)\""}},
        token=token)["id"]
    deadline = time.time() + 60
    while time.time() < deadline:
        t = cluster.api("GET", f"/api/v1/commands/{tid2}", token=token)["task"]
        if t["state"] == "RUNNING":
            break
        time.sleep(0.1)
    time.sleep(1.0)  # give the task a beat to print
    cluster.api("POST", f"/api/v1/commands/{tid2}/kill", token=token)
    deadline = time.time() + 60
    state2 = None
    while time.time() < deadline:
        t = cluster.api("GET", f"/api/v1/commands/{tid2}", token=token)["task"]
        state2 = t["state"]
        if state2 in ("COMPLETED", "ERROR", "CANCELED"):
            break
        time.sleep(0.05)
    assert state2 in ("COMPLETED", "ERROR", "CANCELED"), state2
    assert "pre-kill-55" in logs_text(tid2)
