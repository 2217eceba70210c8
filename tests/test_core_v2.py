"""Core API v2 / unmanaged experiments e2e (reference
experimental/core_v2/_core_v2.py + _unmanaged.py: "det as a library").

The training process here is the TEST process — no agent, no scheduling;
the master just tracks the run."""

import numpy as np
import pytest

from determined_tpu.experimental import core_v2
from tests.test_platform_e2e import Devcluster


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()  # NOTE: no agent — unmanaged runs need none
    yield c
    c.stop()


def test_unmanaged_run_e2e(cluster, tmp_path):
    ctx = core_v2.init(
        config={"name": "laptop-run",
                "searcher": {"name": "single", "metric": "loss",
                             "max_length": {"batches": 6}}},
        master=cluster.master_url,
        hparams={"lr": 0.1},
        checkpoint_storage={"type": "shared_fs",
                            "host_path": str(tmp_path / "ckpts")},
        max_length=6,
    )
    # the module-level handles work like the reference's core_v2 globals
    losses = []
    for op in core_v2.searcher.operations():
        for step in range(1, op.length + 1):
            loss = 1.0 / step
            losses.append(loss)
            core_v2.train.report_training_metrics(step, {"loss": loss})
        core_v2.train.report_validation_metrics(op.length, {"loss": losses[-1]})
        op.report_completed(losses[-1])
    sid = core_v2.checkpoint.upload(
        _make_ckpt_dir(tmp_path), metadata={"steps_completed": 6})
    core_v2.close()

    token = cluster.login()
    exps = cluster.api("GET", "/api/v1/experiments", token=token)["experiments"]
    e = next(x for x in exps if x["id"] == ctx.experiment_id)
    assert e["state"] == "COMPLETED"
    assert e["name"] == "laptop-run"
    trials = cluster.api(
        "GET", f"/api/v1/experiments/{ctx.experiment_id}/trials",
        token=token)["trials"]
    assert len(trials) == 1 and trials[0]["state"] == "COMPLETED"
    metrics = cluster.api(
        "GET", f"/api/v1/trials/{ctx.trial_id}/metrics", token=token
    )["metrics"]
    assert [m for m in metrics if m["group_name"] == "training"]
    cps = cluster.api(
        "GET", f"/api/v1/experiments/{ctx.experiment_id}/checkpoints",
        token=token)["checkpoints"]
    assert [c for c in cps if c["uuid"] == sid]


def test_managed_experiments_reject_manual_trials(cluster, tmp_path):
    import determined_tpu.cli as cli
    from tests.test_platform_e2e import FIXTURES, _experiment_config

    token = cluster.login()
    resp = cluster.api(
        "POST", "/api/v1/experiments",
        {"config": _experiment_config(tmp_path),
         "model_definition": cli._tar_context(FIXTURES), "activate": False},
        token=token)
    import urllib.error

    with pytest.raises(urllib.error.HTTPError):
        cluster.api("POST", f"/api/v1/experiments/{resp['id']}/trials",
                    {}, token=token)


def _make_ckpt_dir(tmp_path):
    d = tmp_path / "artifact"
    d.mkdir(exist_ok=True)
    np.save(d / "weights.npy", np.arange(4.0))
    return str(d)
