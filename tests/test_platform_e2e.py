"""End-to-end platform tests: real master + agent as local processes.

Mirrors the reference's devcluster-based e2e strategy
(e2e_tests/tests/cluster/managed_cluster.py:27 — db+master+agent as local
processes, fault injection via kill/restart :50-98). Here the cluster is the
C++ master + C++ agent with artificial CPU slots; trials are real processes
running the Core API fixture in tests/fixtures/platform/.
"""

import json
import os
import shutil
import signal
import socket
import subprocess
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "platform")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_http(url: str, timeout: float = 20.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2):
                return
        except Exception:
            time.sleep(0.2)
    raise TimeoutError(f"server at {url} did not come up")


class Devcluster:
    """One master + one agent with N artificial slots."""

    def __init__(self, tmpdir: str, binaries: str, slots: int = 2):
        self.tmpdir = tmpdir
        self.binaries = binaries
        self.slots = slots
        self.port = _free_port()
        self.master_url = f"http://127.0.0.1:{self.port}"
        self.db_path = os.path.join(tmpdir, "master.db")
        self.master = None
        self.agent = None
        self.extra_agents = []  # second+ agents (spot/drain tests)
        self.env = dict(
            os.environ,
            PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
            # e2e trials run on the virtual CPU mesh; the agent→trial
            # launch on a real chip is chip_smoke.py --agent.
            JAX_PLATFORMS="cpu",
        )

    def start_master(self, extra_args=()):
        self.master = subprocess.Popen(
            [
                os.path.join(self.binaries, "determined-master"),
                "--port", str(self.port),
                "--host", "127.0.0.1",
                "--db", self.db_path,
                "--agent-timeout", "15",
                *extra_args,
            ],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        _wait_http(self.master_url + "/api/v1/master")

    def start_agent(self, agent_id="agent-0", work_root=None, extra_env=None,
                    slots=None):
        """Start an agent. The first live one is `self.agent` (restart
        semantics of the older tests); further agents — multi-node drain /
        spot tests — land in `self.extra_agents`. `slots` overrides the
        cluster default (heterogeneous pools for elastic shrink tests).
        Returns the process."""
        if work_root is None:
            work_root = os.path.join(
                self.tmpdir,
                "agent-work" if agent_id == "agent-0" else f"work-{agent_id}")
        env = dict(self.env)
        env.update(extra_env or {})
        proc = subprocess.Popen(
            [
                os.path.join(self.binaries, "determined-agent"),
                "--master-url", self.master_url,
                "--id", agent_id,
                "--slots", str(slots if slots is not None else self.slots),
                "--slot-type", "cpu",
                "--addr", "127.0.0.1",
                "--work-root", work_root,
                # Agent service-account bootstrap token minted by the master.
                "--token-file", self.db_path + ".agent_token",
            ],
            env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        if self.agent is None or self.agent.poll() is not None:
            self.agent = proc
        else:
            self.extra_agents.append(proc)
        token = self.login()
        deadline = time.time() + 20
        while time.time() < deadline:
            agents = self.api("GET", "/api/v1/agents", token=token)["agents"]
            if any(a["id"] == agent_id and a["alive"] for a in agents):
                return proc
            time.sleep(0.2)
        raise TimeoutError("agent did not register")

    def kill_master(self):
        self.master.kill()
        self.master.wait()

    @staticmethod
    def _child_pids(pid: int):
        """Direct children of `pid` (Linux /proc)."""
        out = set()
        try:
            for tid in os.listdir(f"/proc/{pid}/task"):
                try:
                    with open(f"/proc/{pid}/task/{tid}/children") as f:
                        out.update(int(c) for c in f.read().split())
                except OSError:
                    continue
        except OSError:
            pass
        return out

    def find_orphans(self):
        """Pids of task processes spawned under this cluster that are
        still alive — the agent setpgid()s every task tree, so after
        stop() this must be empty (VERDICT item 6: the proxy suite's
        spawned servers used to outlive teardown)."""
        orphans = []
        for pid in os.listdir("/proc"):
            if not pid.isdigit():
                continue
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmdline = f.read().decode(errors="replace")
            except OSError:
                continue
            if self.tmpdir in cmdline:
                orphans.append(int(pid))
        return orphans

    def stop(self):
        # Collect the agents' task process groups BEFORE SIGKILLing the
        # agents: a killed agent can't run its own kill/reap path, and the
        # tasks (each setpgid'd into its own group, native/agent/main.cc)
        # would reparent to init and leak — the test_proxy servers did
        # exactly that.
        task_pgids = set()
        for proc in (*self.extra_agents, self.agent):
            if proc is not None and proc.poll() is None:
                task_pgids.update(self._child_pids(proc.pid))
        for proc in (*self.extra_agents, self.agent, self.master):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()
        for pgid in task_pgids:
            try:
                os.killpg(pgid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        # Anything still holding on (e.g. a task that escaped its group):
        # kill by cmdline match so no suite leaks process trees.
        deadline = time.time() + 5
        while time.time() < deadline:
            orphans = self.find_orphans()
            if not orphans:
                break
            for pid in orphans:
                try:
                    os.kill(pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
            time.sleep(0.1)

    # -- tiny API client -----------------------------------------------
    def api(self, method: str, path: str, body=None, token=None):
        req = urllib.request.Request(
            self.master_url + path,
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json",
                     **({"Authorization": f"Bearer {token}"} if token else {})},
            method=method,
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            text = resp.read().decode()
            return json.loads(text) if text else None

    def login(self, user: str = "determined", password: str = "") -> str:
        return self.api("POST", "/api/v1/auth/login",
                        {"username": user, "password": password})["token"]


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def _experiment_config(tmp_path, searcher=None, extra=None):
    config = {
        "name": "e2e-fixture",
        "entrypoint": "python3 train.py",
        "searcher": searcher
        or {
            "name": "single",
            "metric": "val_loss",
            "max_length": {"batches": 8},
        },
        "hyperparameters": {"lr": 0.5},
        "checkpoint_storage": {
            "type": "shared_fs",
            "host_path": os.path.join(str(tmp_path), "checkpoints"),
        },
        "resources": {"slots_per_trial": 1},
        "max_restarts": 1,
    }
    config.update(extra or {})
    return config


def _create_experiment(cluster, config, activate=True):
    import determined_tpu.cli as cli

    token = cluster.login()
    model_def = cli._tar_context(FIXTURES)
    resp = cluster.api(
        "POST", "/api/v1/experiments",
        {"config": config, "model_definition": model_def, "activate": activate},
        token=token,
    )
    return resp["id"], token


def _wait_experiment(cluster, eid, token, timeout=120.0, want=("COMPLETED",)):
    deadline = time.time() + timeout
    state = None
    while time.time() < deadline:
        state = cluster.api("GET", f"/api/v1/experiments/{eid}", token=token)[
            "experiment"]["state"]
        if state in ("COMPLETED", "CANCELED", "ERROR"):
            assert state in want, f"experiment finished {state}, wanted {want}"
            return state
        time.sleep(0.5)
    raise TimeoutError(f"experiment {eid} stuck in {state}")


# ---------------------------------------------------------------------------


def test_devcluster_boots_from_config_files(tmp_path, native_binaries):
    """Master AND agent boot from JSON config files alone (viper-style
    file+env+flags layering, reference cmd/determined-master/init.go:13 and
    agent/internal/options/options.go) and run an experiment end to end."""
    port = _free_port()
    db_path = os.path.join(str(tmp_path), "m.db")
    master_cfg = {"host": "127.0.0.1", "port": port, "db_path": db_path,
                  "cluster_name": "from-config", "agent_timeout_s": 15}
    agent_cfg = {"master_url": f"http://127.0.0.1:{port}", "id": "cfg-agent",
                 "addr": "127.0.0.1", "slots": 2, "slot_type": "cpu",
                 "work_root": os.path.join(str(tmp_path), "work"),
                 "token_file": db_path + ".agent_token"}
    mp = os.path.join(str(tmp_path), "master.json")
    ap = os.path.join(str(tmp_path), "agent.json")
    with open(mp, "w") as f:
        json.dump(master_cfg, f)
    with open(ap, "w") as f:
        json.dump(agent_cfg, f)

    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_PLATFORMS="cpu")
    master = subprocess.Popen(
        [os.path.join(native_binaries, "determined-master"), "--config", mp],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    agent = None
    try:
        _wait_http(f"http://127.0.0.1:{port}/api/v1/master")
        agent = subprocess.Popen(
            [os.path.join(native_binaries, "determined-agent"),
             "--config", ap],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

        c = Devcluster.__new__(Devcluster)
        c.master_url = f"http://127.0.0.1:{port}"
        info = c.api("GET", "/api/v1/master")
        assert info["cluster_name"] == "from-config"
        token = c.login()
        deadline = time.time() + 20
        while time.time() < deadline:
            agents = c.api("GET", "/api/v1/agents", token=token)["agents"]
            if any(a["id"] == "cfg-agent" and a["alive"] for a in agents):
                break
            time.sleep(0.2)
        else:
            raise TimeoutError("config-file agent did not register")

        import determined_tpu.cli as cli
        model_def = cli._tar_context(FIXTURES)
        eid = c.api("POST", "/api/v1/experiments",
                    {"config": _experiment_config(tmp_path),
                     "model_definition": model_def, "activate": True},
                    token=token)["id"]
        _wait_experiment(c, eid, token)
    finally:
        for proc in (agent, master):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()


def test_master_info_and_agent_registration(cluster):
    info = cluster.api("GET", "/api/v1/master")
    assert info["cluster_name"] == "determined-tpu"
    token = cluster.login()
    # Every route except master-info/login now requires a session token.
    try:
        cluster.api("GET", "/api/v1/agents")
        raise AssertionError("unauthenticated /agents should 401")
    except urllib.error.HTTPError as e:
        assert e.code == 401
    agents = cluster.api("GET", "/api/v1/agents", token=token)["agents"]
    assert len(agents) == 1
    assert len(agents[0]["slots"]) == 2


def test_single_experiment_end_to_end(cluster, tmp_path):
    eid, token = _create_experiment(cluster, _experiment_config(tmp_path))
    _wait_experiment(cluster, eid, token)

    trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials", token=token)[
        "trials"]
    assert len(trials) == 1
    trial = trials[0]
    assert trial["state"] == "COMPLETED"
    assert trial["total_batches"] >= 8

    metrics = cluster.api(
        "GET", f"/api/v1/trials/{trial['id']}/metrics?group=training", token=token
    )["metrics"]
    assert metrics, "training metrics should be reported"
    val = cluster.api(
        "GET", f"/api/v1/trials/{trial['id']}/metrics?group=validation", token=token
    )["metrics"]
    assert val and "val_loss" in val[-1]["metrics"]

    cps = cluster.api(
        "GET", f"/api/v1/experiments/{eid}/checkpoints", token=token
    )["checkpoints"]
    assert cps, "checkpoint should be reported"
    ckpt_dir = os.path.join(str(tmp_path), "checkpoints", cps[-1]["uuid"])
    assert os.path.exists(os.path.join(ckpt_dir, "state.json"))

    logs = cluster.api(
        "GET", f"/api/v1/tasks/trial-{trial['id']}/logs?offset=0", token=token
    )["logs"]
    assert any("trial complete" in line["log"] for line in logs)


def test_metric_summary_rollups(cluster, tmp_path):
    """trials.summary_metrics (min/max/last/mean/count per metric per
    group) is maintained incrementally on report and must agree with a
    full scan of raw_metrics (reference
    static/srv/calculate-full-trial-summary-metrics.sql)."""
    eid, token = _create_experiment(cluster, _experiment_config(tmp_path))
    _wait_experiment(cluster, eid, token)
    trial = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                        token=token)["trials"][0]
    summary = trial["summary_metrics"]
    raw = cluster.api(
        "GET", f"/api/v1/trials/{trial['id']}/metrics", token=token
    )["metrics"]
    assert summary, "rollups missing"
    for group in ("training", "validation"):
        vals = {}
        for m in raw:
            if m["group_name"] != group:
                continue
            for k, v in m["metrics"].items():
                if isinstance(v, (int, float)):
                    vals.setdefault(k, []).append(float(v))
        assert vals, f"no raw {group} metrics"
        for k, xs in vals.items():
            s = summary[group][k]
            assert s["count"] == len(xs)
            assert abs(s["min"] - min(xs)) < 1e-9
            assert abs(s["max"] - max(xs)) < 1e-9
            assert abs(s["last"] - xs[-1]) < 1e-9
            assert abs(s["mean"] - sum(xs) / len(xs)) < 1e-9


def test_asha_search_end_to_end(cluster, tmp_path):
    searcher = {
        "name": "async_halving",
        "metric": "val_loss",
        "max_length": {"batches": 8},
        "num_rungs": 2,
        "divisor": 2,
        "max_trials": 4,
        "max_concurrent_trials": 2,
    }
    config = _experiment_config(
        tmp_path, searcher=searcher,
        extra={"hyperparameters": {"lr": {"type": "log", "minval": -2,
                                          "maxval": 0}}},
    )
    eid, token = _create_experiment(cluster, config)
    _wait_experiment(cluster, eid, token, timeout=180.0)
    trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials", token=token)[
        "trials"]
    assert len(trials) == 4
    assert all(t["state"] == "COMPLETED" for t in trials)
    # rung geometry (cumulative, reference asha.go:62-66): rung0 = 8/2 = 4,
    # rung1 = 4 + 8 = 12. Everyone reaches 4; promoted trials reach 12.
    batches = sorted(t["total_batches"] for t in trials)
    assert batches[0] >= 4
    assert batches[-1] >= 12


def test_pause_resume_preempts_and_resumes_from_checkpoint(cluster, tmp_path):
    config = _experiment_config(
        tmp_path,
        searcher={"name": "single", "metric": "val_loss",
                  "max_length": {"batches": 200}},
    )
    config["environment"] = {"TRIAL_STEP_SLEEP": "0.05"}
    eid, token = _create_experiment(cluster, config)

    # Let it run a bit, then pause (→ preemption signal → checkpoint+exit).
    time.sleep(4.0)
    cluster.api("POST", f"/api/v1/experiments/{eid}/pause", token=token)
    deadline = time.time() + 60
    while time.time() < deadline:
        trials = cluster.api(
            "GET", f"/api/v1/experiments/{eid}/trials", token=token)["trials"]
        if trials and trials[0].get("latest_checkpoint"):
            break
        time.sleep(0.5)
    trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials", token=token)[
        "trials"]
    assert trials[0]["latest_checkpoint"], "pause should checkpoint the trial"

    cluster.api("POST", f"/api/v1/experiments/{eid}/activate", token=token)
    _wait_experiment(cluster, eid, token, timeout=180.0)
    logs = cluster.api(
        "GET", f"/api/v1/tasks/trial-{trials[0]['id']}/logs?offset=0",
        token=token)["logs"]
    assert any("resumed from checkpoint" in line["log"] for line in logs)


def test_agent_restart_reattaches_running_task(cluster, tmp_path):
    """Kill -9 the agent mid-trial and restart it: the task process (its
    own process group, logging to files) survives, the new agent adopts it
    from running.json, and the trial COMPLETES with restarts == 0 — a
    reattach, not a restart-from-checkpoint (reference
    containers/manager.go:76 ReattachContainers)."""
    config = _experiment_config(
        tmp_path,
        searcher={"name": "single", "metric": "val_loss",
                  "max_length": {"batches": 150}},
    )
    config["environment"] = {"TRIAL_STEP_SLEEP": "0.05"}
    eid, token = _create_experiment(cluster, config)

    # Wait until the trial is actually running and logging.
    deadline = time.time() + 60
    trial = None
    while time.time() < deadline:
        trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                             token=token)["trials"]
        if trials:
            logs = cluster.api(
                "GET", f"/api/v1/tasks/trial-{trials[0]['id']}/logs?offset=0",
                token=token)["logs"]
            if logs:
                trial = trials[0]
                break
        time.sleep(0.3)
    assert trial is not None, "trial never started logging"

    cluster.agent.kill()  # SIGKILL: no cleanup, the task is orphaned
    cluster.agent.wait()
    time.sleep(1.0)
    cluster.start_agent()  # same id + work_root → reattach path

    _wait_experiment(cluster, eid, token, timeout=180.0)
    trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials",
                         token=token)["trials"]
    assert trials[0]["state"] == "COMPLETED"
    assert trials[0]["restarts"] == 0, (
        "reattach must not consume a restart: the surviving process "
        "finished the trial")
    logs = cluster.api(
        "GET", f"/api/v1/tasks/trial-{trials[0]['id']}/logs?offset=0",
        token=token)["logs"]
    assert any("trial complete" in line["log"] for line in logs)


def test_master_restart_restores_experiment(cluster, tmp_path):
    config = _experiment_config(
        tmp_path,
        searcher={"name": "single", "metric": "val_loss",
                  "max_length": {"batches": 120}},
    )
    config["environment"] = {"TRIAL_STEP_SLEEP": "0.05"}
    eid, token = _create_experiment(cluster, config)
    time.sleep(3.0)

    cluster.kill_master()
    time.sleep(1.0)
    cluster.start_master()  # same db; snapshot restore (restore.go analogue)
    token = cluster.login()

    _wait_experiment(cluster, eid, token, timeout=180.0)
    trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials", token=token)[
        "trials"]
    assert trials[0]["state"] == "COMPLETED"


def test_cancel_experiment(cluster, tmp_path):
    config = _experiment_config(
        tmp_path,
        searcher={"name": "single", "metric": "val_loss",
                  "max_length": {"batches": 10000}},
    )
    config["environment"] = {"TRIAL_STEP_SLEEP": "0.05"}
    eid, token = _create_experiment(cluster, config)
    time.sleep(3.0)
    cluster.api("POST", f"/api/v1/experiments/{eid}/cancel", token=token)
    state = _wait_experiment(cluster, eid, token, timeout=60.0,
                             want=("CANCELED", "COMPLETED"))
    assert state in ("CANCELED", "COMPLETED")


def test_sdk_workflow(cluster, tmp_path):
    """Drive the flow through the experimental SDK (reference
    determined.experimental.client)."""
    from determined_tpu.experimental import Determined

    d = Determined(cluster.master_url)
    assert d.get_master_info()["cluster_name"] == "determined-tpu"
    assert len(d.get_agents()) == 1

    exp = d.create_experiment(_experiment_config(tmp_path), FIXTURES)
    assert exp.wait(timeout=120.0) == "COMPLETED"
    trials = exp.get_trials()
    assert len(trials) == 1 and trials[0].state == "COMPLETED"
    metrics = list(trials[0].iter_metrics("validation"))
    assert metrics and "val_loss" in metrics[-1]["metrics"]

    ckpt = exp.top_checkpoint()
    assert ckpt.uuid
    local = ckpt.download(os.path.join(str(tmp_path), "dl"))
    assert os.path.exists(os.path.join(local, "state.json"))

    model = d.create_model("sdk-model")
    version = model.register_version(ckpt.uuid)
    assert version.version == 1
    assert d.get_model("sdk-model").get_versions()[0].checkpoint_uuid == ckpt.uuid


def test_command_task(cluster):
    """NTSC command task end to end (reference command/command.go)."""
    token = cluster.login()
    resp = cluster.api(
        "POST", "/api/v1/commands",
        {"config": {"entrypoint": "echo hello-from-command"}}, token=token,
    )
    task_id = resp["id"]
    deadline = time.time() + 60
    task = None
    while time.time() < deadline:
        task = cluster.api("GET", f"/api/v1/commands/{task_id}", token=token)["task"]
        if task["state"] in ("COMPLETED", "ERROR", "CANCELED"):
            break
        time.sleep(0.5)
    assert task and task["state"] == "COMPLETED", task
    logs = cluster.api(
        "GET", f"/api/v1/tasks/{task_id}/logs?offset=0", token=token)["logs"]
    assert any("hello-from-command" in line["log"] for line in logs)

    listed = cluster.api("GET", "/api/v1/commands", token=token)["commands"]
    assert any(t["id"] == task_id for t in listed)


def test_tensorboard_metrics_synced_to_storage(cluster, tmp_path):
    """Trial tfevents must land in checkpoint storage under
    tensorboard/<exp>/<trial>/ (reference tensorboard/base.py sync)."""
    eid, token = _create_experiment(cluster, _experiment_config(tmp_path))
    _wait_experiment(cluster, eid, token)
    trials = cluster.api("GET", f"/api/v1/experiments/{eid}/trials", token=token)[
        "trials"]
    tb_dir = os.path.join(str(tmp_path), "checkpoints", "tensorboard",
                          str(eid), str(trials[0]["id"]))
    assert os.path.isdir(tb_dir), f"no synced tfevents dir at {tb_dir}"
    assert any(name.startswith("events.") for name in os.listdir(tb_dir))


def test_custom_searcher(cluster, tmp_path):
    """User-defined SearchMethod driving trials through the master's
    custom-searcher event queue (reference custom_search.go +
    searcher/_remote_search_runner.py)."""
    from determined_tpu.experimental import Determined
    from determined_tpu.searcher import (
        Close, Create, RemoteSearchRunner, SearchMethod, Shutdown,
        ValidateAfter,
    )

    class TwoRoundSearch(SearchMethod):
        """2 trials; the better one trains a second round."""

        def __init__(self):
            self.results = {}
            self.closed = 0
            self.extended = None

        def initial_operations(self):
            ops = []
            for lr in (0.1, 0.9):
                create = Create({"lr": lr})
                ops += [create, ValidateAfter(create.request_id, 4)]
            return ops

        def on_validation_completed(self, request_id, metric, train_length):
            self.results[request_id] = metric
            if train_length >= 8:
                return [Close(request_id)]
            if len(self.results) < 2:
                return []
            best = min(self.results, key=self.results.get)
            if self.extended is None:
                self.extended = best
                ops = [ValidateAfter(best, 8)]
                ops += [Close(r) for r in self.results if r != best]
                return ops
            return [Close(request_id)]

        def on_trial_closed(self, request_id):
            self.closed += 1
            return [Shutdown()] if self.closed == 2 else []

        def progress(self):
            return min(1.0, self.closed / 2)

    config = _experiment_config(
        tmp_path, searcher={"name": "custom", "metric": "val_loss"})
    runner = RemoteSearchRunner(TwoRoundSearch(),
                                Determined(cluster.master_url))
    eid = runner.run(config, FIXTURES, poll_timeout=5.0)

    d = Determined(cluster.master_url)
    exp = d.get_experiment(eid)
    assert exp.state == "COMPLETED"
    trials = exp.get_trials()
    assert len(trials) == 2
    batches = sorted(t.total_batches for t in trials)
    assert batches == [4, 8]


def test_cli_workflow(cluster, tmp_path, monkeypatch, capsys):
    """Drive the same flow through the det CLI."""
    import determined_tpu.cli as cli

    monkeypatch.setattr(cli, "TOKEN_CACHE",
                        os.path.join(str(tmp_path), "tokens.json"))
    cfg_path = os.path.join(str(tmp_path), "config.json")
    with open(cfg_path, "w") as f:
        json.dump(_experiment_config(tmp_path), f)

    rc = cli.main(["-m", cluster.master_url, "experiment", "create",
                   cfg_path, FIXTURES, "--follow"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Created experiment" in out
    assert "COMPLETED" in out

    rc = cli.main(["-m", cluster.master_url, "experiment", "list"])
    assert rc == 0
    assert "e2e-fixture" in capsys.readouterr().out

    rc = cli.main(["-m", cluster.master_url, "agent", "list"])
    assert rc == 0
    assert "agent-0" in capsys.readouterr().out


def test_model_def_content_store_and_file_tree(cluster, tmp_path):
    """Content-addressed model-def store (reference master/internal/cache
    role): identical context tarballs dedupe to one blob; trials still
    fetch their context; file_tree lists the tarball's files; delete
    releases the reference."""
    cfg = _experiment_config(tmp_path)
    e1, token = _create_experiment(cluster, cfg, activate=False)
    e2, _ = _create_experiment(cluster, cfg, activate=False)

    # Same tarball → the two experiments share one blob.
    md1 = cluster.api("GET", f"/api/v1/experiments/{e1}/model_def",
                      token=token)["b64_tgz"]
    md2 = cluster.api("GET", f"/api/v1/experiments/{e2}/model_def",
                      token=token)["b64_tgz"]
    assert md1 == md2 and md1

    tree = cluster.api("GET", f"/api/v1/experiments/{e1}/file_tree",
                       token=token)["files"]
    paths = {f["path"] for f in tree}
    assert "train.py" in paths, paths
    assert all(f["size"] >= 0 for f in tree)
    # PAX/GNU metadata records must not leak as pseudo-files.
    assert not any("PaxHeader" in p for p in paths), paths

    # A run still gets its context after dedupe (activate e1, let the
    # trial extract + complete).
    cluster.api("POST", f"/api/v1/experiments/{e1}/activate", token=token)
    _wait_experiment(cluster, e1, token)

    # Cancel + delete e2: the blob must survive (e1 still references it).
    cluster.api("POST", f"/api/v1/experiments/{e2}/cancel", token=token)
    deadline = time.time() + 30
    while time.time() < deadline:
        st = cluster.api("GET", f"/api/v1/experiments/{e2}",
                         token=token)["experiment"]["state"]
        if st in ("CANCELED", "COMPLETED", "ERROR"):
            break
        time.sleep(0.2)
    cluster.api("DELETE", f"/api/v1/experiments/{e2}", token=token)
    md1_after = cluster.api("GET", f"/api/v1/experiments/{e1}/model_def",
                            token=token)["b64_tgz"]
    assert md1_after == md1


def test_preflight_gate_and_persistence(tmp_path, native_binaries):
    """The master-side preflight gate (docs/preflight.md): DTL2xx config
    rules run natively at experiment create; diagnostics persist on the
    record and surface through the API; `preflight: {gate: error}` rejects
    with 400; suppression waives the gate. Master-only cluster — nothing
    is scheduled."""
    import urllib.error

    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    try:
        token = c.login()

        def config(gate=None, suppress=None, gbs=30):
            cfg = {
                "name": "preflight-e2e",
                "entrypoint": "python3 train.py",
                "searcher": {"name": "single", "metric": "loss",
                             "max_length": {"batches": 8}},
                "resources": {"slots_per_trial": 8},
                "hyperparameters": {"global_batch_size": gbs},
            }
            pf = {}
            if gate:
                pf["gate"] = gate
            if suppress:
                pf["suppress"] = suppress
            if pf:
                cfg["preflight"] = pf
            return cfg

        # Default gate (warn): created, diagnostics persisted + returned.
        out = c.api("POST", "/api/v1/experiments",
                    {"config": config(), "model_definition": "",
                     "activate": False}, token=token)
        assert [d["code"] for d in out["preflight"]] == ["DTL201"]
        eid = out["id"]
        got = c.api("GET", f"/api/v1/experiments/{eid}", token=token)
        assert [d["code"] for d in got["experiment"]["preflight"]] == [
            "DTL201"]

        # gate: error -> 400 with diagnostics in the body.
        try:
            c.api("POST", "/api/v1/experiments",
                  {"config": config(gate="error"), "model_definition": "",
                   "activate": False}, token=token)
            raise AssertionError("gated create unexpectedly succeeded")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            body = json.loads(e.read().decode())
            assert [d["code"] for d in body["preflight"]] == ["DTL201"]

        # Suppressing the code waives the gate.
        out = c.api("POST", "/api/v1/experiments",
                    {"config": config(gate="error", suppress=["DTL201"]),
                     "model_definition": "", "activate": False}, token=token)
        assert out["preflight"][0]["suppressed"] is True

        # A clean config carries no diagnostics.
        out = c.api("POST", "/api/v1/experiments",
                    {"config": config(gbs=32), "model_definition": "",
                     "activate": False}, token=token)
        assert out["preflight"] == []
    finally:
        c.stop()
