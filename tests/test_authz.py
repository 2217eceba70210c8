"""Authorization enforcement e2e (VERDICT r3 #1).

Reference semantics: master/internal/rbac/rbac.go (roles + workspace-scoped
assignments), internal/usergroup/ (groups), authz plumbing in
api_experiment.go / api_user.go. The TPU-native model: base role per user
(admin|user|viewer) + workspace-scoped grants (viewer|editor|admin) to users
or groups. These tests are the negative-path suite round 3 lacked: every
check asserts a 403/401 actually comes back.
"""

import contextlib
import urllib.error

import pytest

from tests.test_platform_e2e import (
    Devcluster,
    _experiment_config,
)


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    # Master only — authz checks don't need a running agent.
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    yield c
    c.stop()


@contextlib.contextmanager
def expect_status(code):
    try:
        yield
    except urllib.error.HTTPError as e:
        assert e.code == code, f"expected HTTP {code}, got {e.code}: {e.read()}"
    else:
        raise AssertionError(f"expected HTTP {code}, request succeeded")


def _mk_user(cluster, admin_token, name, role="user", password=""):
    cluster.api("POST", "/api/v1/users",
                {"username": name, "role": role, "password": password},
                token=admin_token)
    return cluster.login(name, password)


def _paused_experiment(cluster, token, tmp_path, name="authz-exp"):
    config = _experiment_config(tmp_path)
    config["name"] = name
    resp = cluster.api(
        "POST", "/api/v1/experiments",
        {"config": config, "model_definition": "", "activate": False},
        token=token,
    )
    return resp["id"]


def test_user_management_is_admin_only(cluster):
    admin = cluster.login("admin")
    user = cluster.login("determined")

    # Non-admin cannot mint users (round-3 hole: anyone could mint admins).
    with expect_status(403):
        cluster.api("POST", "/api/v1/users",
                    {"username": "mallory", "role": "admin"}, token=user)
    # Admin can.
    alice = _mk_user(cluster, admin, "alice")
    assert cluster.api("GET", "/api/v1/me", token=alice)["user"]["role"] == "user"

    # Non-admin cannot change someone else's role or deactivate them.
    users = cluster.api("GET", "/api/v1/users", token=user)["users"]
    alice_id = next(u["id"] for u in users if u["username"] == "alice")
    with expect_status(403):
        cluster.api("PATCH", f"/api/v1/users/{alice_id}", {"role": "admin"},
                    token=user)
    with expect_status(403):
        cluster.api("PATCH", f"/api/v1/users/{alice_id}", {"active": False},
                    token=user)
    # Self password change is allowed without admin.
    me = cluster.api("GET", "/api/v1/me", token=alice)["user"]
    cluster.api("PATCH", f"/api/v1/users/{me['id']}", {"password": "s3cret"},
                token=alice)
    assert cluster.login("alice", "s3cret")

    # Deactivation revokes sessions immediately.
    cluster.api("PATCH", f"/api/v1/users/{alice_id}", {"active": False},
                token=admin)
    with expect_status(401):
        cluster.api("GET", "/api/v1/me", token=alice)
    with expect_status(403):
        cluster.login("alice", "s3cret")


def test_non_owner_cannot_touch_experiment(cluster, tmp_path):
    admin = cluster.login("admin")
    alice = _mk_user(cluster, admin, "alice2")
    bob = _mk_user(cluster, admin, "bob2")

    eid = _paused_experiment(cluster, alice, tmp_path)

    # Bob (plain user, not owner, no grants) gets 403 on every mutation.
    for verb in ("activate", "pause", "cancel", "kill", "archive"):
        with expect_status(403):
            cluster.api("POST", f"/api/v1/experiments/{eid}/{verb}",
                        token=bob)
    with expect_status(403):
        cluster.api("DELETE", f"/api/v1/experiments/{eid}", token=bob)
    # Reads stay open.
    exp = cluster.api("GET", f"/api/v1/experiments/{eid}", token=bob)
    assert exp["experiment"]["id"] == eid

    # Owner and admin can mutate.
    cluster.api("POST", f"/api/v1/experiments/{eid}/kill", token=alice)
    eid2 = _paused_experiment(cluster, alice, tmp_path, name="authz-exp-2")
    cluster.api("POST", f"/api/v1/experiments/{eid2}/kill", token=admin)


def test_viewer_is_read_only(cluster, tmp_path):
    admin = cluster.login("admin")
    owner = cluster.login("determined")
    eve = _mk_user(cluster, admin, "eve", role="viewer")

    eid = _paused_experiment(cluster, owner, tmp_path)

    # Viewer can read everything...
    assert cluster.api("GET", "/api/v1/experiments", token=eve)["experiments"]
    assert cluster.api("GET", "/api/v1/workspaces", token=eve)["workspaces"]
    # ...but can create/mutate nothing.
    cfg = _experiment_config(tmp_path)
    with expect_status(403):
        cluster.api("POST", "/api/v1/experiments",
                    {"config": cfg, "model_definition": "", "activate": False},
                    token=eve)
    with expect_status(403):
        cluster.api("POST", f"/api/v1/experiments/{eid}/kill", token=eve)
    with expect_status(403):
        cluster.api("POST", "/api/v1/workspaces", {"name": "eve-ws"}, token=eve)
    with expect_status(403):
        cluster.api("POST", "/api/v1/commands",
                    {"config": {"entrypoint": "true"}}, token=eve)
    with expect_status(403):
        cluster.api("POST", "/api/v1/checkpoints", {"uuid": "x"}, token=eve)
    with expect_status(403):
        cluster.api("POST", "/api/v1/task/logs",
                    {"logs": [{"task_id": "t", "log": "x"}]}, token=eve)


def test_workspace_scoped_grant_raises_rights(cluster, tmp_path):
    admin = cluster.login("admin")
    alice = _mk_user(cluster, admin, "alice3")
    bob = _mk_user(cluster, admin, "bob3")
    bob_id = next(u["id"] for u in
                  cluster.api("GET", "/api/v1/users", token=admin)["users"]
                  if u["username"] == "bob3")

    eid = _paused_experiment(cluster, alice, tmp_path)
    with expect_status(403):
        cluster.api("POST", f"/api/v1/experiments/{eid}/kill", token=bob)

    # Grant bob editor on workspace 1 (Uncategorized — where project 1 lives):
    # now he can kill alice's experiment there.
    grant = cluster.api("POST", "/api/v1/rbac/assignments",
                        {"role": "editor", "user_id": bob_id,
                         "workspace_id": 1}, token=admin)
    cluster.api("POST", f"/api/v1/experiments/{eid}/kill", token=bob)

    # Revoking the grant restores the 403.
    cluster.api("DELETE", f"/api/v1/rbac/assignments/{grant['id']}",
                token=admin)
    eid2 = _paused_experiment(cluster, alice, tmp_path, name="authz-ws-2")
    with expect_status(403):
        cluster.api("POST", f"/api/v1/experiments/{eid2}/kill", token=bob)

    # Non-admin cannot self-grant.
    with expect_status(403):
        cluster.api("POST", "/api/v1/rbac/assignments",
                    {"role": "admin", "user_id": bob_id}, token=bob)


def test_group_grant_raises_viewer_to_editor(cluster, tmp_path):
    admin = cluster.login("admin")
    eve = _mk_user(cluster, admin, "eve2", role="viewer")
    eve_id = next(u["id"] for u in
                  cluster.api("GET", "/api/v1/users", token=admin)["users"]
                  if u["username"] == "eve2")

    cfg = _experiment_config(tmp_path)
    with expect_status(403):
        cluster.api("POST", "/api/v1/experiments",
                    {"config": cfg, "model_definition": "", "activate": False},
                    token=eve)

    # Group management is admin-only.
    with expect_status(403):
        cluster.api("POST", "/api/v1/groups", {"name": "nope"}, token=eve)

    gid = cluster.api("POST", "/api/v1/groups", {"name": "researchers"},
                      token=admin)["id"]
    cluster.api("POST", f"/api/v1/groups/{gid}/members", {"user_id": eve_id},
                token=admin)
    cluster.api("POST", "/api/v1/rbac/assignments",
                {"role": "editor", "group_id": gid, "workspace_id": 1},
                token=admin)

    # Viewer-by-base-role, editor-by-group-grant: create now succeeds.
    resp = cluster.api("POST", "/api/v1/experiments",
                       {"config": cfg, "model_definition": "",
                        "activate": False}, token=eve)
    cluster.api("POST", f"/api/v1/experiments/{resp['id']}/kill", token=eve)

    # Removing membership drops the grant.
    cluster.api("DELETE", f"/api/v1/groups/{gid}/members/{eve_id}",
                token=admin)
    with expect_status(403):
        cluster.api("POST", "/api/v1/experiments",
                    {"config": cfg, "model_definition": "", "activate": False},
                    token=eve)


def test_admin_gates_on_cluster_ops(cluster):
    user = cluster.login("determined")
    with expect_status(403):
        cluster.api("POST", "/api/v1/job-queues/reorder",
                    {"allocation_id": "x", "ahead_of": "y"}, token=user)
    with expect_status(403):
        cluster.api("POST", "/api/v1/master/cleanup_logs", {"days": 1},
                    token=user)
    with expect_status(403):
        cluster.api("POST", "/api/v1/agents/agent-0/disable", token=user)
    with expect_status(403):
        cluster.api("POST", "/api/v1/webhooks",
                    {"url": "http://example.invalid/hook"}, token=user)


def test_agent_drain_admin_path(cluster):
    """Admin can disable/enable agent slots (drain); 404 on unknown agent."""
    admin = cluster.login("admin")
    with expect_status(404):
        cluster.api("POST", "/api/v1/agents/no-such-agent/disable", token=admin)


def test_agent_drain_blocks_scheduling(tmp_path, native_binaries):
    """Drained agents take no new work; enable releases the queue
    (reference api_agent.go DisableAgent semantics)."""
    import time

    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    try:
        admin = c.login("admin")
        user = c.login()
        c.api("POST", "/api/v1/agents/agent-0/disable", token=admin)
        agents = c.api("GET", "/api/v1/agents", token=admin)["agents"]
        assert all(not s["enabled"] for s in agents[0]["slots"])

        tid = c.api("POST", "/api/v1/commands",
                    {"config": {"entrypoint": "echo drained",
                                "resources": {"slots": 1}}},
                    token=user)["id"]
        time.sleep(2.0)  # several scheduler ticks
        task = c.api("GET", f"/api/v1/commands/{tid}", token=user)["task"]
        assert task.get("allocation_state") in (None, "PENDING"), task

        c.api("POST", "/api/v1/agents/agent-0/enable", token=admin)
        deadline = time.time() + 60
        while time.time() < deadline:
            task = c.api("GET", f"/api/v1/commands/{tid}", token=user)["task"]
            if task["state"] == "COMPLETED":
                break
            time.sleep(0.5)
        assert task["state"] == "COMPLETED", task
    finally:
        c.stop()


def test_agent_protocol_requires_agent_role(cluster):
    """A normal user must not be able to register a fake agent: the actions
    stream hands out task environments including per-owner session tokens,
    so this would be privilege escalation (reference isolates the surface
    on the master↔agent websocket)."""
    user = cluster.login("determined")
    with expect_status(403):
        cluster.api("POST", "/api/v1/agents/register",
                    {"id": "evil-agent", "slots": [{"id": 0, "type": "cpu"}]},
                    token=user)
    with expect_status(403):
        cluster.api("GET", "/api/v1/agents/agent-0/actions?timeout_seconds=0",
                    token=user)
    # Password login to the service account is refused — it is token-only.
    with expect_status(403):
        cluster.login("determined-agent")
    # The master-minted bootstrap token (written next to the db) works.
    with open(cluster.db_path + ".agent_token") as f:
        agent_tok = f.read().strip()
    resp = cluster.api("POST", "/api/v1/agents/register",
                       {"id": "test-agent",
                        "slots": [{"id": 0, "type": "cpu"}]},
                       token=agent_tok)
    assert resp["agent_id"] == "test-agent"


def test_cross_user_checkpoint_and_logs_protected(cluster, tmp_path):
    """Bob cannot reset alice's trial resume pointer via checkpoint report,
    flip her checkpoints to DELETED, or forge lines into her task logs."""
    import time

    admin = cluster.login("admin")
    alice = _mk_user(cluster, admin, "alice5")
    bob = _mk_user(cluster, admin, "bob5")
    eid = _paused_experiment(cluster, alice, tmp_path)
    # Activate so the searcher creates the trial row (no agent is running,
    # so the allocation just queues — fine for authz checks).
    cluster.api("POST", f"/api/v1/experiments/{eid}/activate", token=alice)
    trials = []
    deadline = time.time() + 20
    while time.time() < deadline and not trials:
        trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                             token=alice)["trials"]
        time.sleep(0.2)
    assert trials, "searcher should create a trial row"
    tid = trials[0]["id"]

    with expect_status(403):
        cluster.api("POST", "/api/v1/checkpoints",
                    {"uuid": "bogus", "trial_id": tid}, token=bob)
    cluster.api("POST", "/api/v1/checkpoints",
                {"uuid": "real-ck", "trial_id": tid}, token=alice)
    with expect_status(403):
        cluster.api("PATCH", "/api/v1/checkpoints",
                    {"checkpoints": [{"uuid": "real-ck", "state": "DELETED"}]},
                    token=bob)
    # Forged logs into alice's trial task stream → 403 for bob; the agent
    # service account may ship anything.
    with expect_status(403):
        cluster.api("POST", "/api/v1/task/logs",
                    {"logs": [{"task_id": f"trial-{tid}",
                               "log": "FATAL forged"}]}, token=bob)
    with open(cluster.db_path + ".agent_token") as f:
        agent_tok = f.read().strip()
    cluster.api("POST", "/api/v1/task/logs",
                {"logs": [{"task_id": f"trial-{tid}", "log": "real line"}]},
                token=agent_tok)
    cluster.api("POST", f"/api/v1/experiments/{eid}/kill", token=alice)


def test_ntsc_kill_requires_ownership(cluster):
    admin = cluster.login("admin")
    alice = _mk_user(cluster, admin, "alice4")
    bob = _mk_user(cluster, admin, "bob4")
    resp = cluster.api("POST", "/api/v1/commands",
                       {"config": {"entrypoint": "sleep 60"}}, token=alice)
    with expect_status(403):
        cluster.api("POST", f"/api/v1/commands/{resp['id']}/kill", token=bob)
    cluster.api("POST", f"/api/v1/commands/{resp['id']}/kill", token=alice)
