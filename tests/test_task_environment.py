"""Task environment management e2e: env vars, python_path, venv activation.

Reference: the task-spec builder renders env images/mounts/env vars into the
container spec (master/pkg/tasks/task.go:194-234). The TPU equivalent is
process-level: the master injects config env vars into the task env, and the
launch layer (determined_tpu/exec/launch.py apply_task_environment) performs
venv activation + PYTHONPATH extension before exec'ing the entrypoint."""

import os
import sys
import time

import pytest

from determined_tpu.exec.launch import apply_task_environment
from tests.test_platform_e2e import (
    FIXTURES,
    Devcluster,
    _wait_experiment,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TASKENV_FIXTURES = os.path.join(REPO, "tests", "fixtures", "taskenv")


class TestApplyTaskEnvironment:
    def test_env_vars_list_form(self):
        env = apply_task_environment(
            {}, {"environment": {"environment_variables": ["A=1", "B=x=y"]}}
        )
        assert env["A"] == "1"
        assert env["B"] == "x=y"  # split on first '=' only

    def test_venv_activation(self):
        env = apply_task_environment(
            {"PATH": "/usr/bin", "PYTHONHOME": "/opt/py"},
            {"environment": {"venv": "/opt/task-venv"}},
        )
        assert env["VIRTUAL_ENV"] == "/opt/task-venv"
        assert env["PATH"].startswith("/opt/task-venv/bin" + os.pathsep)
        assert "PYTHONHOME" not in env

    def test_python_path_appended(self):
        env = apply_task_environment(
            {"PYTHONPATH": "/ctx"},
            {"environment": {"python_path": ["/pkgs/a", "/pkgs/b"]}},
        )
        assert env["PYTHONPATH"] == os.pathsep.join(["/ctx", "/pkgs/a", "/pkgs/b"])

    def test_no_environment_block(self):
        assert apply_task_environment({"X": "1"}, {}) == {"X": "1"}


class TestExpconfEnvironmentValidation:
    def test_valid(self):
        from determined_tpu import expconf

        c = {
            "entrypoint": "python3 t.py",
            "searcher": {"name": "single", "metric": "m",
                         "max_length": {"batches": 1}},
            "environment": {
                "FOO": "bar",
                "environment_variables": ["K=V"],
                "venv": "/opt/venv",
                "python_path": ["/pkgs"],
            },
        }
        assert expconf.validate(c) == []

    def test_bad_entries(self):
        from determined_tpu import expconf

        c = {
            "entrypoint": "python3 t.py",
            "searcher": {"name": "single", "metric": "m",
                         "max_length": {"batches": 1}},
            "environment": {
                "environment_variables": ["NOEQUALS"],
                "venv": 7,
                "python_path": "notalist",
                "NUM": 3,
            },
        }
        errs = expconf.validate(c)
        assert any("NOEQUALS" in e for e in errs)
        assert any("venv" in e for e in errs)
        assert any("python_path" in e for e in errs)
        assert any("environment.NUM" in e for e in errs)


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def test_task_environment_e2e(cluster, tmp_path):
    """A trial sees its configured env vars, imports from an extra package
    root, and runs under the task venv's interpreter."""
    # Extra package root (outside the context dir).
    extra = tmp_path / "extra-pkgs"
    extra.mkdir()
    (extra / "extra_pkg.py").write_text("VALUE = 42\n")
    # Fake venv whose bin/python3 is the real interpreter.
    venv = tmp_path / "fake-venv"
    (venv / "bin").mkdir(parents=True)
    os.symlink(sys.executable, venv / "bin" / "python3")

    import determined_tpu.cli as cli

    config = {
        "name": "taskenv-e2e",
        "entrypoint": "python3 train_env.py",
        "searcher": {
            "name": "single",
            "metric": "val_loss",
            "max_length": {"batches": 2},
        },
        "checkpoint_storage": {
            "type": "shared_fs",
            "host_path": os.path.join(str(tmp_path), "ckpts"),
        },
        "environment": {
            "MY_TASK_FLAG": "from-config",
            "environment_variables": ["MY_TASK_FLAG2=listed"],
            "venv": str(venv),
            "python_path": [str(extra)],
        },
        "resources": {"slots_per_trial": 1},
        "max_restarts": 0,
    }
    token = cluster.login()
    resp = cluster.api(
        "POST", "/api/v1/experiments",
        {
            "config": config,
            "model_definition": cli._tar_context(TASKENV_FIXTURES),
            "activate": True,
        },
        token=token,
    )
    _wait_experiment(cluster, resp["id"], token, timeout=120)
    # The fixture asserts the environment before reporting; reaching
    # COMPLETED proves env vars + python_path + venv all applied.
    logs = cluster.api(
        "GET", f"/api/v1/experiments/{resp['id']}/trials", token=token
    )["trials"]
    assert logs[0]["state"] == "COMPLETED"


def test_startup_hook_runs_before_entrypoint(cluster, tmp_path):
    """startup-hook.sh in the context dir runs before the entrypoint
    (reference exec/prep_container.py); a failing hook fails the task."""
    import shutil

    ctx = tmp_path / "hookctx"
    ctx.mkdir()
    shutil.copy(os.path.join(FIXTURES, "train.py"), ctx / "train.py")
    (ctx / "startup-hook.sh").write_text(
        "echo hook-side-effect > hook_output.txt\n"
        "echo startup-hook-ran-$((40+4))\n")
    (ctx / "reader.py").write_text(
        "print('hook says:', open('hook_output.txt').read().strip())\n")

    token = cluster.login()
    import determined_tpu.cli as cli

    tid = cluster.api(
        "POST", "/api/v1/commands",
        {"config": {"entrypoint": "python3 reader.py"},
         "context": cli._tar_context(str(ctx))}, token=token)["id"]
    deadline = time.time() + 60
    state = None
    while time.time() < deadline:
        t = cluster.api("GET", f"/api/v1/commands/{tid}", token=token)["task"]
        state = t["state"]
        if state in ("COMPLETED", "ERROR", "CANCELED"):
            break
        time.sleep(0.2)
    assert state == "COMPLETED", state
    logs = cluster.api("GET", f"/api/v1/tasks/{tid}/logs",
                       token=token)["logs"]
    text = "\n".join(line["log"] for line in logs)
    assert "startup-hook-ran-44" in text       # hook output shipped
    assert "hook says: hook-side-effect" in text  # entrypoint saw its work

    # Failing hook → task fails, entrypoint never runs.
    ctx2 = tmp_path / "hookctx2"
    ctx2.mkdir()
    (ctx2 / "startup-hook.sh").write_text("echo doomed; exit 3\n")
    (ctx2 / "nope.py").write_text("print('must-not-run')\n")
    tid2 = cluster.api(
        "POST", "/api/v1/commands",
        {"config": {"entrypoint": "python3 nope.py"},
         "context": cli._tar_context(str(ctx2))}, token=token)["id"]
    deadline = time.time() + 60
    while time.time() < deadline:
        t = cluster.api("GET", f"/api/v1/commands/{tid2}",
                        token=token)["task"]
        if t["state"] in ("COMPLETED", "ERROR", "CANCELED"):
            break
        time.sleep(0.2)
    assert t["state"] == "ERROR", t["state"]
    logs2 = cluster.api("GET", f"/api/v1/tasks/{tid2}/logs",
                        token=token)["logs"]
    text2 = "\n".join(line["log"] for line in logs2)
    assert "must-not-run" not in text2


def test_cli_cmd_run_with_context(cluster, tmp_path):
    """`det cmd run --context DIR …` ships the dir (reference parity)."""
    import subprocess

    ctx = tmp_path / "clictx"
    ctx.mkdir()
    (ctx / "data.txt").write_text("context-payload-99\n")
    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        HOME=cluster.tmpdir,
    )
    r = subprocess.run(
        [sys.executable, "-m", "determined_tpu.cli",
         "-m", cluster.master_url, "cmd", "run", "--context", str(ctx),
         "cat", "data.txt"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    tid = r.stdout.split("Started ")[1].split(" ")[0]
    token = cluster.login()
    deadline = time.time() + 60
    while time.time() < deadline:
        t = cluster.api("GET", f"/api/v1/commands/{tid}", token=token)["task"]
        if t["state"] in ("COMPLETED", "ERROR", "CANCELED"):
            break
        time.sleep(0.2)
    assert t["state"] == "COMPLETED", t["state"]
    logs = cluster.api("GET", f"/api/v1/tasks/{tid}/logs",
                       token=token)["logs"]
    assert any("context-payload-99" in line["log"] for line in logs)


def test_task_context_released_on_terminal(cluster, tmp_path):
    """A terminal task releases its content-store claim: blobs must not
    accumulate per `det cmd run --context` invocation."""
    import sqlite3

    ctx = tmp_path / "relctx"
    ctx.mkdir()
    (ctx / "unique.txt").write_text(f"payload-{tmp_path}\n")
    import determined_tpu.cli as cli

    token = cluster.login()
    tid = cluster.api(
        "POST", "/api/v1/commands",
        {"config": {"entrypoint": "cat unique.txt"},
         "context": cli._tar_context(str(ctx))}, token=token)["id"]
    deadline = time.time() + 60
    while time.time() < deadline:
        t = cluster.api("GET", f"/api/v1/commands/{tid}", token=token)["task"]
        if t["state"] in ("COMPLETED", "ERROR", "CANCELED"):
            break
        time.sleep(0.2)
    assert t["state"] == "COMPLETED", t["state"]

    deadline = time.time() + 15
    while time.time() < deadline:
        con = sqlite3.connect(f"file:{cluster.db_path}?mode=ro", uri=True)
        try:
            row = con.execute(
                "SELECT context_hash FROM tasks WHERE id=?", (tid,)
            ).fetchone()
            n_blobs = con.execute(
                "SELECT COUNT(*) FROM model_defs WHERE refcount <= 0"
            ).fetchone()[0]
        finally:
            con.close()
        if row and row[0] is None and n_blobs == 0:
            return
        time.sleep(0.5)
    raise AssertionError(f"context not released: hash={row}, "
                         f"zombie blobs={n_blobs}")
