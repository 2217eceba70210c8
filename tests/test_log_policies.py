"""Log-pattern policies e2e (reference logpattern/logpattern.go:232 +
schemas/expconf/v0/log-policy.json): regexes over shipped task logs drive
cancel_retries / exclude_node actions."""

import time

import pytest

from determined_tpu import expconf
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


class TestExpconfValidation:
    def base(self, policies):
        return {
            "entrypoint": "python3 t.py",
            "searcher": {"name": "single", "metric": "m",
                         "max_length": {"batches": 1}},
            "log_policies": policies,
        }

    def test_valid(self):
        assert expconf.validate(self.base([
            {"pattern": ".*OOM.*", "action": {"type": "cancel_retries"}},
            {"pattern": "bad node", "action": "exclude_node"},
        ])) == []

    def test_bad_regex(self):
        errs = expconf.validate(self.base([
            {"pattern": "(unclosed", "action": "cancel_retries"}]))
        assert any("invalid regex" in e for e in errs)

    def test_bad_action(self):
        errs = expconf.validate(self.base([
            {"pattern": "x", "action": "explode"}]))
        assert any("cancel_retries or" in e for e in errs)


def test_cancel_retries_policy(cluster, tmp_path):
    """A matching fatal line stops retries: trial ERRORs with 0 restarts
    despite max_restarts=3."""
    config = _experiment_config(tmp_path)
    config["entrypoint"] = "python3 crash_train.py"
    config["max_restarts"] = 3
    config["log_policies"] = [
        {"pattern": "UNRECOVERABLE_CONDITION",
         "action": {"type": "cancel_retries"}},
    ]
    eid, token = _create_experiment(cluster, config, activate=True)
    _wait_experiment(cluster, eid, token, want=("ERROR",))
    trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                         token=token)["trials"]
    assert trials[0]["state"] == "ERROR"
    assert trials[0]["restarts"] == 0, trials[0]


def test_without_policy_retries_happen(cluster, tmp_path):
    """Control: same crash without the policy consumes max_restarts."""
    config = _experiment_config(tmp_path)
    config["entrypoint"] = "python3 crash_train.py"
    config["max_restarts"] = 1
    eid, token = _create_experiment(cluster, config, activate=True)
    _wait_experiment(cluster, eid, token, want=("ERROR",))
    trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                         token=token)["trials"]
    assert trials[0]["restarts"] == 1, trials[0]


def test_exclude_node_policy(cluster, tmp_path):
    """exclude_node: the restart must land on a different agent."""
    import os
    import subprocess

    # second agent so the excluded trial has somewhere to go
    second = subprocess.Popen(
        [os.path.join(cluster.binaries, "determined-agent"),
         "--master-url", cluster.master_url,
         "--id", "agent-1", "--slots", "2", "--slot-type", "cpu",
         "--addr", "127.0.0.1",
         "--work-root", os.path.join(cluster.tmpdir, "agent1-work"),
         "--token-file", cluster.db_path + ".agent_token"],
        env=cluster.env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        token = cluster.login()
        deadline = time.time() + 20
        while time.time() < deadline:
            agents = cluster.api("GET", "/api/v1/agents", token=token)["agents"]
            if sum(1 for a in agents if a["alive"]) == 2:
                break
            time.sleep(0.2)

        config = _experiment_config(tmp_path)
        config["entrypoint"] = "python3 crash_train.py"
        config["max_restarts"] = 1
        config["log_policies"] = [
            {"pattern": "UNRECOVERABLE_CONDITION",
             "action": {"type": "exclude_node"}},
        ]
        eid, token = _create_experiment(cluster, config, activate=True)
        _wait_experiment(cluster, eid, token, want=("ERROR",))
        trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                             token=token)["trials"]
        assert trials[0]["restarts"] == 1
        # the two runs used two different agents
        logs = cluster.api(
            "GET", f"/api/v1/tasks/trial-{trials[0]['id']}/logs",
            token=token)["logs"]
        used = {l["agent_id"] for l in logs if l.get("agent_id")}
        assert len(used) == 2, used
    finally:
        second.kill()
        second.wait()
