"""The shipped examples must actually run — via the CLI, like the README says.

Reference anchor: e2e_tests/tests/experiment/ runs the reference's example
configs on a devcluster; here the README quickstart commands are executed
verbatim (CLI `experiment create <config> <context> --follow`) against the
C++ master+agent.
"""

import os
import subprocess
import sys
import time

import pytest

from tests.test_platform_e2e import Devcluster, _wait_experiment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def _cli(cluster, *args, timeout=300):
    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        HOME=cluster.tmpdir,  # isolate the CLI token cache
    )
    return subprocess.run(
        [sys.executable, "-m", "determined_tpu.cli",
         "-m", cluster.master_url, *args],
        capture_output=True, text=True, env=env, timeout=timeout,
    )


def _patch_storage(tmp_path, config_path, mutate=None):
    """Point the example's checkpoint_storage at the test tmpdir; `mutate`
    may shrink the config further (test-size lengths/models)."""
    import yaml

    with open(config_path) as f:
        cfg = yaml.safe_load(f)
    cfg["checkpoint_storage"]["host_path"] = os.path.join(str(tmp_path), "ckpts")
    if mutate is not None:
        mutate(cfg)
    out = os.path.join(str(tmp_path), os.path.basename(config_path))
    with open(out, "w") as f:
        yaml.safe_dump(cfg, f)
    return out


def test_mnist_example_quickstart(cluster, tmp_path):
    """The README quickstart command, verbatim (storage redirected)."""
    cfg = _patch_storage(tmp_path, os.path.join(EXAMPLES, "mnist", "config.yaml"))
    r = _cli(cluster, "experiment", "create", cfg,
             os.path.join(EXAMPLES, "mnist"), "--follow", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]

    token = cluster.login()
    trials = cluster.api("GET", "/api/v1/experiments/1/trials", token=token)[
        "trials"]
    assert trials and trials[0]["state"] == "COMPLETED"
    metrics = cluster.api(
        "GET", f"/api/v1/trials/{trials[0]['id']}/metrics",
        token=token)["metrics"]
    assert any(m["group_name"] == "validation" for m in metrics)
    cps = cluster.api("GET", "/api/v1/experiments/1/checkpoints",
                      token=token)["checkpoints"]
    assert cps, "example must produce a checkpoint"


def test_gpt2_example(cluster, tmp_path):
    cfg = _patch_storage(tmp_path, os.path.join(EXAMPLES, "gpt2", "config.yaml"))
    r = _cli(cluster, "experiment", "create", cfg,
             os.path.join(EXAMPLES, "gpt2"), "--follow", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]


def test_mnist_adaptive_example(cluster, tmp_path):
    """The shipped adaptive_asha config runs a real multi-trial search
    (shrunk trial count/length)."""
    def shrink(cfg):
        cfg["searcher"].update(max_trials=4, max_length={"batches": 8})
        cfg["hyperparameters"]["global_batch_size"] = 32

    out = _patch_storage(
        tmp_path, os.path.join(EXAMPLES, "mnist", "adaptive.yaml"), shrink)
    r = _cli(cluster, "experiment", "create", out,
             os.path.join(EXAMPLES, "mnist"), "--follow", timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]
    token = cluster.login()
    trials = cluster.api("GET", "/api/v1/experiments/1/trials",
                         token=token)["trials"]
    assert len(trials) == 4  # the search really ran multiple trials


def test_hf_trainer_example(cluster, tmp_path):
    """The shipped HF-Trainer DetCallback example, shrunk."""
    def shrink(cfg):
        cfg["searcher"]["max_length"] = {"batches": 4}
        cfg["hyperparameters"].update(max_steps=4, eval_steps=4, seq_len=32)

    out = _patch_storage(
        tmp_path, os.path.join(EXAMPLES, "hf_trainer", "config.yaml"), shrink)
    r = _cli(cluster, "experiment", "create", out,
             os.path.join(EXAMPLES, "hf_trainer"), "--follow", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]


def test_cifar10_keras_distributed_example(cluster, tmp_path):
    """The BASELINE CIFAR-10 KerasTrial workload, shrunk: DataParallel over
    the trial's 8-device CPU mesh through the full platform."""
    import yaml

    with open(os.path.join(EXAMPLES, "cifar10_keras", "distributed.yaml")) as f:
        cfg = yaml.safe_load(f)
    cfg["checkpoint_storage"]["host_path"] = os.path.join(str(tmp_path), "ckpts")
    cfg["searcher"]["max_length"] = {"batches": 2}
    cfg["hyperparameters"].update(width=8, blocks_per_stage=1,
                                  global_batch_size=64)
    cfg["resources"]["slots_per_trial"] = 2
    out = os.path.join(str(tmp_path), "cifar.yaml")
    with open(out, "w") as f:
        yaml.safe_dump(cfg, f)
    r = _cli(cluster, "experiment", "create", out,
             os.path.join(EXAMPLES, "cifar10_keras"), "--follow", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]


def test_gpt2_torch_distributed_example(cluster, tmp_path):
    """The torch compat GPT-2 workload, shrunk: 2-process DDP (gloo) via the
    torch_distributed launch layer inside a managed task."""
    import yaml

    with open(os.path.join(EXAMPLES, "gpt2_torch", "distributed.yaml")) as f:
        cfg = yaml.safe_load(f)
    cfg["checkpoint_storage"]["host_path"] = os.path.join(str(tmp_path), "ckpts")
    cfg["searcher"]["max_length"] = {"batches": 2}
    cfg["hyperparameters"].update(
        model_size="tiny", seq_len=32, per_device_batch_size=4, fsdp=False)
    cfg["resources"]["slots_per_trial"] = 2
    cfg["entrypoint"] = (
        "python3 -m determined_tpu.launch.torch_distributed "
        "--nproc-per-node 2 -- python3 model_def.py"
    )
    out = os.path.join(str(tmp_path), "gpt2_torch.yaml")
    with open(out, "w") as f:
        yaml.safe_dump(cfg, f)
    r = _cli(cluster, "experiment", "create", out,
             os.path.join(EXAMPLES, "gpt2_torch"), "--follow", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]
    # both ranks ran (wrap_rank prefixes) and DDP-wrapped training reported
    assert "[rank=0]" in r.stdout and "[rank=1]" in r.stdout, r.stdout[-2000:]


def test_gpt_neox_zero1_example(cluster, tmp_path):
    """BASELINE config 4: GPT-NeoX through the DeepSpeedTrial API with the
    TPU-native ZeRO-1 engine, shrunk to 2 processes (gloo) in a managed
    task. The shipped zero1.yaml is this with 410m/64 slots."""
    import yaml

    with open(os.path.join(EXAMPLES, "gpt_neox", "zero1.yaml")) as f:
        cfg = yaml.safe_load(f)
    cfg["checkpoint_storage"]["host_path"] = os.path.join(str(tmp_path), "ckpts")
    cfg["searcher"]["max_length"] = {"batches": 2}
    cfg["hyperparameters"].update(
        model_size="tiny", seq_len=32, micro_batch_size=2,
        gradient_accumulation=2)
    cfg["resources"]["slots_per_trial"] = 2
    cfg["entrypoint"] = (
        "python3 -m determined_tpu.launch.torch_distributed "
        "--nproc-per-node 2 -- python3 model_def.py"
    )
    out = os.path.join(str(tmp_path), "gpt_neox.yaml")
    with open(out, "w") as f:
        yaml.safe_dump(cfg, f)
    r = _cli(cluster, "experiment", "create", out,
             os.path.join(EXAMPLES, "gpt_neox"), "--follow", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]
    assert "[rank=0]" in r.stdout and "[rank=1]" in r.stdout, r.stdout[-2000:]
    # the engine partitioned the optimizer across the two workers
    assert "zero1: rank 0/2" in r.stdout and "zero1: rank 1/2" in r.stdout, \
        r.stdout[-2000:]


def test_diffusion_finetune_asha_example(cluster, tmp_path):
    """BASELINE config 5: diffusion finetune + adaptive_asha across
    sub-slices, shrunk: tiny UNet, 2-slot trials on the 2-slot agent,
    3-trial search. Also exercises the finetune path: a pretrained pickle
    is produced first and pretrained_path points at it."""
    import yaml

    # Pretrain for real (tiny, 4 steps) via the shipped script — this is
    # pretrain.py's only end-to-end coverage, don't hand-pickle instead.
    pre = os.path.join(str(tmp_path), "pretrained.pkl")
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_PLATFORMS="cpu")
    pr = subprocess.run(
        [sys.executable, "-m", "examples.diffusion.pretrain",
         "--steps", "4", "--batch", "8", "--model-size", "tiny",
         "--out", pre],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert pr.returncode == 0, pr.stdout[-2000:] + pr.stderr[-2000:]
    assert os.path.exists(pre)

    with open(os.path.join(EXAMPLES, "diffusion", "finetune_asha.yaml")) as f:
        cfg = yaml.safe_load(f)
    cfg["checkpoint_storage"]["host_path"] = os.path.join(str(tmp_path), "ckpts")
    cfg["searcher"].update(max_trials=3, max_length={"batches": 4})
    cfg["hyperparameters"].update(
        model_size="tiny", global_batch_size=8, pretrained_path=pre)
    cfg["resources"]["slots_per_trial"] = 2
    out = os.path.join(str(tmp_path), "diffusion.yaml")
    with open(out, "w") as f:
        yaml.safe_dump(cfg, f)
    r = _cli(cluster, "experiment", "create", out,
             os.path.join(EXAMPLES, "diffusion"), "--follow", timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]
    token = cluster.login()
    trials = cluster.api("GET", "/api/v1/experiments/1/trials",
                         token=token)["trials"]
    assert len(trials) == 3  # the search really ran multiple trials


def test_gpt2_pipeline_example(cluster, tmp_path):
    """pipeline.yaml runs the GPipe path: mesh.pipeline=2 makes the Trainer
    select loss_pipelined inside the spawned trial (8-device CPU mesh via the
    conftest XLA_FLAGS the agent inherits), shrunk to test size."""
    import yaml

    with open(os.path.join(EXAMPLES, "gpt2", "pipeline.yaml")) as f:
        cfg = yaml.safe_load(f)
    cfg["checkpoint_storage"]["host_path"] = os.path.join(str(tmp_path), "ckpts")
    cfg["searcher"]["max_length"] = {"batches": 2}
    cfg["hyperparameters"].update(
        model_size="tiny", seq_len=16, global_batch_size=8,
        mesh={"pipeline": 2, "data": -1})
    cfg["resources"]["slots_per_trial"] = 2
    out = os.path.join(str(tmp_path), "pipeline.yaml")
    with open(out, "w") as f:
        yaml.safe_dump(cfg, f)
    r = _cli(cluster, "experiment", "create", out,
             os.path.join(EXAMPLES, "gpt2"), "--follow", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]


def test_gpt2_long_context_example(cluster, tmp_path):
    """long_context.yaml runs sequence parallelism inside the spawned
    trial: mesh.context=2 shards the sequence, ulysses all-to-all head
    sharding computes attention (ring needs the pallas kernel's TPU
    shapes; ulysses exercises the same context axis on the CPU mesh).
    seq_len 256 deliberately EXCEEDS tiny's n_positions=128 so the
    config's defining behavior — widening the position table for long
    context — is what the test exercises."""
    def shrink(cfg):
        cfg["searcher"]["max_length"] = {"batches": 2}
        cfg["hyperparameters"].update(
            model_size="tiny", seq_len=256, global_batch_size=4,
            attention_impl="ulysses", scan_unroll=1, remat=False,
            mesh={"context": 2, "data": -1})
        cfg["resources"]["slots_per_trial"] = 2

    out = _patch_storage(
        tmp_path, os.path.join(EXAMPLES, "gpt2", "long_context.yaml"),
        shrink)
    r = _cli(cluster, "experiment", "create", out,
             os.path.join(EXAMPLES, "gpt2"), "--follow", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]


def test_gpt2_moe_example(cluster, tmp_path):
    """moe.yaml routes every block's FFN over the expert mesh axis inside
    the spawned trial (expert=2 on the agent's 8-device CPU mesh)."""
    import yaml

    with open(os.path.join(EXAMPLES, "gpt2", "moe.yaml")) as f:
        cfg = yaml.safe_load(f)
    cfg["checkpoint_storage"]["host_path"] = os.path.join(str(tmp_path), "ckpts")
    cfg["searcher"]["max_length"] = {"batches": 2}
    cfg["hyperparameters"].update(
        model_size="tiny", seq_len=16, global_batch_size=8, num_experts=4,
        attention_impl="dot", scan_unroll=1,
        mesh={"expert": 2, "data": -1})
    cfg["resources"]["slots_per_trial"] = 2
    out = os.path.join(str(tmp_path), "moe.yaml")
    with open(out, "w") as f:
        yaml.safe_dump(cfg, f)
    r = _cli(cluster, "experiment", "create", out,
             os.path.join(EXAMPLES, "gpt2"), "--follow", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "COMPLETED" in r.stdout, r.stdout[-2000:]
