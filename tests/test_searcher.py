"""Searcher-engine tests via the native simulator.

Mirrors the reference's whole-search simulations
(master/pkg/searcher/simulate.go, asha_test.go, adaptive_asha_test.go):
drive each search method end-to-end with a synthetic metric and check trial
counts, rung geometry, promotion behavior, determinism, and mid-search
snapshot/restore.
"""

import json
import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIM = os.path.join(REPO, "native", "bin", "searcher_sim")


@pytest.fixture(scope="session")
def sim(native_binaries):
    return SIM


def run_sim(sim, searcher, hparams=None, seed=7, **kwargs):
    payload = {
        "searcher": searcher,
        "hyperparameters": hparams or {"lr": {"type": "double", "minval": 0,
                                              "maxval": 1}},
        "seed": seed,
        **kwargs,
    }
    out = subprocess.run(
        [sim], input=json.dumps(payload), capture_output=True, text=True,
        check=True,
    )
    return json.loads(out.stdout)


def test_single(sim):
    r = run_sim(sim, {"name": "single", "metric": "loss",
                      "max_length": {"batches": 100}})
    assert r["trials_created"] == 1
    assert r["total_units"] == 100
    assert r["shutdown"]


def test_random(sim):
    r = run_sim(sim, {"name": "random", "metric": "loss", "max_length": 50,
                      "max_trials": 5})
    assert r["trials_created"] == 5
    assert r["total_units"] == 250
    assert all(t["units"] == 50 for t in r["trials"].values())
    assert r["shutdown"]


def test_grid(sim):
    hp = {
        "lr": {"type": "log", "minval": -3, "maxval": -1, "count": 3},
        "bs": {"type": "categorical", "vals": [16, 32]},
        "depth": {"type": "const", "val": 4},
        "nested": {"opt": {"type": "int", "minval": 1, "maxval": 2, "count": 2}},
    }
    r = run_sim(sim, {"name": "grid", "metric": "loss", "max_length": 10}, hp)
    assert r["trials_created"] == 3 * 2 * 1 * 2
    assert r["shutdown"]


def test_asha_rung_geometry_and_promotions(sim):
    # max_length 16, divisor 4, 3 rungs → cumulative rungs 1, 5, 21
    # (reference asha.go:62-66 cumulative units).
    r = run_sim(
        sim,
        {"name": "async_halving", "metric": "loss", "max_length": 16,
         "num_rungs": 3, "divisor": 4, "max_trials": 16,
         "max_concurrent_trials": 16},
    )
    assert r["trials_created"] == 16
    assert r["shutdown"]
    units = sorted(t["units"] for t in r["trials"].values())
    assert set(units) <= {1, 5, 21}
    # 16 trials / divisor 4 → 4 reach rung 1; 4/4 → 1 reaches rung 2.
    assert units.count(21) >= 1
    assert sum(1 for u in units if u >= 5) >= 4


def test_asha_stop_once(sim):
    r = run_sim(
        sim,
        {"name": "async_halving", "metric": "loss", "max_length": 16,
         "num_rungs": 2, "divisor": 4, "max_trials": 8, "stop_once": True},
    )
    assert r["trials_created"] == 8
    assert r["shutdown"]


def test_adaptive_asha_brackets(sim):
    r = run_sim(
        sim,
        {"name": "adaptive_asha", "metric": "loss",
         "max_length": {"batches": 64}, "max_rungs": 3, "divisor": 4,
         "max_trials": 12, "mode": "standard"},
    )
    assert r["trials_created"] == 12
    assert r["shutdown"]
    # standard mode with R=3 → 2 brackets, request ids prefixed b0-/b1-.
    prefixes = {rid.split("-")[0] for rid in r["trials"]}
    assert prefixes == {"b0", "b1"}


def test_determinism(sim):
    cfg = {"name": "random", "metric": "loss", "max_length": 10,
           "max_trials": 4}
    r1 = run_sim(sim, cfg, seed=123)
    r2 = run_sim(sim, cfg, seed=123)
    assert r1 == r2
    r3 = run_sim(sim, cfg, seed=124)
    assert r3["best_metric"] != r1["best_metric"]


def test_snapshot_restore_midway(sim):
    """Snapshot + restore mid-search must not change the outcome
    (reference restore.go exact-resume semantics)."""
    cfg = {"name": "async_halving", "metric": "loss", "max_length": 16,
           "num_rungs": 3, "divisor": 4, "max_trials": 16,
           "max_concurrent_trials": 16}
    base = run_sim(sim, cfg, seed=99)
    restored = run_sim(sim, cfg, seed=99, restore_midway=True)
    assert base == restored


def test_smaller_is_better_false(sim):
    cfg = {"name": "async_halving", "metric": "acc", "smaller_is_better": False,
           "max_length": 16, "num_rungs": 2, "divisor": 2, "max_trials": 4}
    r = run_sim(sim, cfg)
    assert r["shutdown"]
    # With larger-is-better, promoted (longer-trained) trials are the ones
    # with the HIGHEST raw metric among rung-0 peers.
    trials = list(r["trials"].values())
    top = max(trials, key=lambda t: t["units"])
    assert top["units"] > min(t["units"] for t in trials)
