"""Task entrypoint: prep, then spawn the experiment's entrypoint.

Reference: harness/determined/exec/launch.py:29 (spawn + signal forwarding,
SIGTERM→preemption :49-55) combined with the launch layers under
harness/determined/launch/. The TPU launch model is simpler than
torchrun/horovodrun: ONE process per host owns all local chips, so there is
no per-device process fan-out — the "distributed launcher" reduces to
exporting the jax.distributed coordination env and exec'ing the user
entrypoint.

Exported for multi-host JAX (consumed by determined_tpu.core.init /
user code):
  DET_COORDINATOR_ADDR  chief_host:port  (jax.distributed.initialize)
  DET_NODE_RANK / DET_NUM_NODES          (process_id / num_processes)
"""

from __future__ import annotations

import logging
import os
import shlex
import signal
import subprocess
import sys

from determined_tpu.exec import prep as prep_mod

logger = logging.getLogger("determined_tpu.exec")


def build_command(config: Optional[dict] = None) -> list:
    """Resolve the experiment entrypoint into an argv list."""
    import json

    if config is None:
        config = json.loads(os.environ.get("DET_EXPERIMENT_CONFIG", "{}"))
    entrypoint = config.get("entrypoint")
    if entrypoint is None:
        entrypoint = os.environ.get("DET_ENTRYPOINT")
        # Array entrypoints travel as JSON to keep argument boundaries
        # exact (a space-joined string would re-split wrongly).
        if entrypoint and entrypoint.lstrip().startswith("["):
            try:
                entrypoint = json.loads(entrypoint)
            except ValueError:
                pass
    if entrypoint is None:
        raise RuntimeError("no entrypoint in experiment config")
    if isinstance(entrypoint, list):
        return [str(x) for x in entrypoint]
    return shlex.split(str(entrypoint))


def apply_task_environment(env: dict, config: dict) -> dict:
    """Render the expconf `environment:` block into the process env
    (reference: task-spec env/image rendering, master/pkg/tasks/task.go:194-234
    — on TPU-VMs there are no containers, so "environment management" means
    interpreter selection + import paths + env vars):

      environment_variables: ["K=V", ...]   (also applied master-side; done
                                             here too so local mode matches)
      venv: /path/to/venv                    activation-equivalent: VIRTUAL_ENV
                                             + venv/bin first on PATH, so a
                                             `python3 ...` entrypoint resolves
                                             to the task's interpreter
      python_path: [dir, ...]                appended to PYTHONPATH (extra
                                             package roots shipped with the
                                             context or mounted on the host)
    """
    envcfg = config.get("environment") or {}
    # Flat "K": "V" entries are env vars too (master-side rendering does the
    # same; applying here keeps local mode identical).
    for k, v in envcfg.items():
        if k in ("environment_variables", "venv", "python_path"):
            continue
        if isinstance(v, str):
            env[k] = v
    for kv in envcfg.get("environment_variables", []) or []:
        k, sep, v = str(kv).partition("=")
        if sep:
            env[k] = v
    venv = envcfg.get("venv")
    if venv:
        venv = os.path.expanduser(str(venv))
        env["VIRTUAL_ENV"] = venv
        env["PATH"] = os.path.join(venv, "bin") + os.pathsep + env.get("PATH", "")
        env.pop("PYTHONHOME", None)
    for p in envcfg.get("python_path", []) or []:
        env["PYTHONPATH"] = (
            env.get("PYTHONPATH", "") + os.pathsep + os.path.expanduser(str(p))
        ).strip(os.pathsep)
    return env


def bind_tpu_chips(env: dict, slot_ids: list) -> None:
    """Bind the allocation's slots to chips: libtpu claims EVERY chip of
    the host for the first process that initializes it, so without this a
    1-slot trial on a 4-chip host takes all four and a second concurrent
    trial cannot start. The whole host needs no binding (libtpu's
    default); one chip is a 1x1x1 process on that chip. Other sub-host
    shapes would need the chips' ICI coordinates to form the bounds — not
    supported, and said so rather than handing the task the wrong chips.
    An expconf `environment_variables` entry for any of these wins."""
    host_slots = int(env.get("DET_HOST_SLOTS", "0"))
    if len(slot_ids) == host_slots:
        return
    if len(slot_ids) != 1:
        raise RuntimeError(
            f"allocation of slots {slot_ids} on a {host_slots}-chip host: "
            "only one chip or the whole host can be bound to a task "
            "(resources.slots_per_trial: 1 or the host's chip count)")
    env.setdefault("TPU_VISIBLE_CHIPS", str(slot_ids[0]))
    env.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    env.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def main() -> int:
    import json

    logging.basicConfig(level=logging.INFO, format="%(name)s: %(message)s")

    info = prep_mod.prep()
    env = dict(os.environ)
    if info is not None and len(info["container_addrs"]) > 1:
        env["DET_COORDINATOR_ADDR"] = info["coordinator_addr"]
    # Make the extracted context importable.
    workdir = env.get("DET_WORKDIR", os.getcwd())
    env["PYTHONPATH"] = workdir + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("PYTHONUNBUFFERED", "1")
    config = json.loads(os.environ.get("DET_EXPERIMENT_CONFIG", "{}"))
    apply_task_environment(env, config)

    # Virtual-slot devclusters (JAX_PLATFORMS=cpu): make the task's visible
    # JAX device count MATCH its allocated slot count, so the mesh resolves
    # at the size the scheduler granted (tpu slots: bind_tpu_chips below).
    # This is what lets an elastic re-placement at a new size
    # (docs/elasticity.md) actually re-resolve the mesh instead of always
    # seeing one CPU device.
    try:
        slot_ids = json.loads(env.get("DET_SLOT_IDS", "[]"))
    except ValueError:
        slot_ids = []
    if (slot_ids and env.get("JAX_PLATFORMS", "") == "cpu"
            and "xla_force_host_platform_device_count"
            not in env.get("XLA_FLAGS", "")):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={len(slot_ids)}")
    if slot_ids and env.get("DET_SLOT_TYPE") == "tpu" \
            and env.get("JAX_PLATFORMS", "") != "cpu":
        bind_tpu_chips(env, slot_ids)

    # startup-hook.sh from the context dir runs before the entrypoint
    # (reference exec/prep_container.py + entrypoint.sh: dependency
    # installs, data staging). A failing hook fails the task — running a
    # trial against a half-prepared environment would be worse.
    hook = os.path.join(workdir, "startup-hook.sh")
    if os.path.exists(hook):
        logger.info("running startup-hook.sh")
        rc = subprocess.run(["sh", hook], env=env, cwd=workdir).returncode
        if rc != 0:
            logger.error("startup-hook.sh failed (exit %d)", rc)
            return rc

    cmd = build_command(config)
    logger.info("launching entrypoint: %s", cmd)
    proc = subprocess.Popen(cmd, env=env, cwd=workdir)

    # Forward termination signals so preemption/kill reaches the training
    # process (reference exec/launch.py:49-55).
    def forward(signum, frame):
        try:
            proc.send_signal(signum)
        except ProcessLookupError:
            pass

    signal.signal(signal.SIGTERM, forward)
    signal.signal(signal.SIGINT, forward)

    return proc.wait()


if __name__ == "__main__":
    sys.exit(main())
