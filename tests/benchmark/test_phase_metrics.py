"""The four metrics read from the program's own phase records: a traced
tiny run of a cell of each kind reports them, each from the phases it
names, and a program without phases gives none of them."""

import pytest

from benchmarks import cells, phases
from benchmarks.run import ROOT, run_cell

MANIFEST = cells.load_manifest(ROOT)
NEW = {"serve_prefill_stall_share": "%", "serve_step_host_ms": "ms",
       "serve_itl_ms_p99": "ms", "train_loop_host_ms": "ms"}


def read(name, run):
    return cells.load_reader(ROOT, MANIFEST, name)(run)


def test_manifest_lists_the_phase_metrics_where_they_can_be_read():
    entries = {m["name"]: m for m in MANIFEST["per_layer"] if m["name"] in NEW}
    assert set(entries) == set(NEW)
    kinds = {w["name"]: w["traffic"] for w in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for name, m in entries.items():
        assert m["unit"] == NEW[name] and m["source"] == "program_span"
        assert m["better"] == "lower" and m["workloads"]
        # every cell it lists reports the end-to-end metric it moves
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        train = name.startswith("train_")
        assert all((kinds[w] == "fit-steady") == train
                   for w in m["workloads"])


def test_traced_serving_cell_reports_stall_share_step_host_and_itl_tail():
    line, run = run_cell("serve-large-decode", seed=2 ** 31 + 5, seconds=1.0,
                         trace=True, tiny=True)
    got = line["metrics"]
    for name in ("serve_prefill_stall_share", "serve_step_host_ms",
                 "serve_itl_ms_p99"):
        assert got[name]["unit"] == NEW[name] and got[name]["value"] > 0, name
    assert got["serve_prefill_stall_share"]["value"] < 100
    log = phases.serve_window(run)
    steps = [r for r in log if r["name"] == "serve.loop.step"]
    # the phases count what the benchmark's own spans count
    assert sum(r["counts"]["lanes"] for r in steps) == run["occupancy_sum"]
    assert len(steps) == run["active_steps"]
    gaps = [g for r in steps for g in r["counts"]["gaps_ms"]]
    assert max(gaps) >= got["serve_itl_ms_p99"]["value"] >= min(gaps)
    host = got["serve_step_host_ms"]["value"]
    assert host < read("serve_decode_step_ms", run) * 1.5 + 1.0
    # nothing in the window: nothing, never a 0
    empty = dict(run, t_open=run["t_close"] + 1e6, t_close=run["t_close"] + 2e6)
    assert all(read(name, empty) is None for name in NEW
               if name.startswith("serve_"))


def test_traced_prompts_cell_stalls_more_than_it_decodes():
    line, run = run_cell("serve-large-prompts", seed=9, seconds=1.0,
                         trace=True, tiny=True)
    got = line["metrics"]
    assert "serve_itl_ms_p99" not in got          # not listed for this cell
    assert got["serve_step_host_ms"]["value"] > 0
    # a pass that found no live lane stalls from its first request's
    # first token on; one that found some, for all of its length
    log = phases.serve_window(run)
    admits = [r for r in log if r["name"] == "serve.loop.admit"]
    firsts = [r for r in log if r["name"] == "serve.admit.first_token"]
    stalled = 0.0
    for r in admits:
        since = r["start"]
        if r["counts"]["live_lanes"] == 0:
            first = next(f for f in firsts if r["start"] <= f["start"]
                         and f["end"] <= r["end"])
            since = r["counts"]["live_from"]
            assert first["end"] <= since <= first["end"] + 0.05
        stalled += r["end"] - since
    assert any(r["counts"]["live_lanes"] == 0 for r in admits)
    assert got["serve_prefill_stall_share"]["value"] == pytest.approx(
        100 * stalled / run["window_s"])
    assert 0 < got["serve_prefill_stall_share"]["value"] < 100


def test_traced_training_cell_reports_the_loops_host_time():
    line, run = run_cell("train-medium-1chip", seed=4, seconds=1.0,
                         trace=True, tiny=True)
    got = line["metrics"]["train_loop_host_ms"]
    assert got["unit"] == "ms" and got["value"] > 0
    log = phases.train_window(run)
    steps = [r for r in log if r["name"] == "harness.step"]
    assert len(steps) == len(run["reports"]) - 1
    step_ms = 1e3 * sum(r["end"] - r["start"] for r in steps) / len(steps)
    assert got["value"] < step_ms
    assert read("train_loop_host_ms", dict(run, reports=[])) is None


def test_a_program_without_phases_gives_no_record_and_no_metric(monkeypatch):
    """What the parent of the PR that brought the phases looks like to
    the readers: they return nothing and do not raise."""
    from determined_tpu.common import trace

    monkeypatch.delattr(trace, "phase_log")
    run = {"t_open": 0.0, "t_close": 1e12, "window_s": 30.0,
           "reports": [{"t": 0.0}, {"t": 1e12}]}
    assert phases.records(0.0, 1e12) == []
    assert all(read(name, run) is None for name in NEW)
