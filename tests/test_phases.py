"""Step phases (common/trace.py `phase()`; docs/observability.md "Step
phases"): the primitive, and what the batcher loop and the fit loop leave
in the ring. All on the CPU; nothing here is a timing of the device."""

import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from determined_tpu.common import metric_names, trace
from determined_tpu.serve.scheduler import Request
from determined_tpu.serve.tracing import RequestTracer
from tests.test_serving import make_batcher, make_engine, tiny_params  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mine(since=None):
    """This thread's records: other tests' loops share the ring."""
    me = threading.get_ident()
    return [r for r in trace.phase_log(since) if r["thread"] == me]


def inside(child, parent):
    return parent["start"] <= child["start"] and child["end"] <= parent["end"]


# ------------------------------------------------------------ the primitive


def test_phase_nests_on_the_monotonic_clock_with_no_profiler_session():
    before = time.monotonic()
    with trace.phase("t.outer", iteration=7, lanes=3) as outer:
        with trace.phase("t.inner") as inner:
            inner.set(ids=["a"])
        with trace.phase("t.dropped") as dropped:
            dropped.cancel()
    after = time.monotonic()
    records = mine(since=before)
    assert [r["name"] for r in records] == ["t.inner", "t.outer"]
    child, parent = records
    assert before <= parent["start"] <= child["start"] <= child["end"] \
        <= parent["end"] <= after
    assert parent["parent"] is None and child["parent"] == "t.outer"
    assert parent["iteration"] == child["iteration"] == 7   # inherited
    assert parent["counts"] == {"lanes": 3}
    assert child["counts"] == {"ids": ["a"]}
    assert outer.live and outer.seconds == parent["end"] - parent["start"]
    # the thread's innermost-open marker is unwound, also by an exception
    with pytest.raises(KeyError):
        with trace.phase("t.raises"):
            raise KeyError("x")
    with trace.phase("t.after") as later:
        pass
    assert later.parent is None
    assert mine(since=after)[0]["name"] == "t.raises"


def test_ring_is_bounded_and_drops_the_oldest():
    for i in range(trace.PHASE_RING + 5):
        with trace.phase("t.fill", iteration=i):
            pass
    log = trace.phase_log()
    assert len(log) == trace.PHASE_RING
    assert log[-1]["iteration"] == trace.PHASE_RING + 4
    assert all(a["end"] <= b["end"] for a, b in zip(log[-50:], log[-49:]))
    assert trace.phase_log(since=time.monotonic() + 1) == []


def test_det_trace_off_makes_phase_a_no_op():
    code = (
        "from determined_tpu.common import trace\n"
        "with trace.phase('t.a', iteration=1, n=2) as p:\n"
        "    p.set(x=1); p.cancel()\n"
        "    with trace.phase('t.b'): pass\n"
        "assert not p.live and p.seconds == 0.0\n"
        "assert trace.phase_log() == []\n"
        "assert trace.Tracer().enabled is False\n")
    env = dict(os.environ, DET_TRACE_OFF="1", PYTHONPATH=ROOT)
    off = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert off.returncode == 0, off.stderr


def test_every_phase_the_loops_open_is_registered():
    from determined_tpu.analysis import metric_lint

    assert metric_lint._PY_SPAN_RE.findall('trace.phase(\n  "a.b", x=1)') \
        == ["a.b"]
    for rel in ("determined_tpu/serve/scheduler.py",
                "determined_tpu/serve/engine.py"):
        assert rel in metric_lint.SPAN_SOURCES
    phases = {n for n in metric_names.SPAN_NAMES
              if n.startswith(("serve.loop.", "serve.admit.", "serve.step.",
                               "harness.step", "harness.flush."))}
    assert len(phases) == 15
    assert metric_lint.lint_registry() == []


def test_phases_and_spans_sit_in_the_profilers_host_plane(tmp_path):
    """Under a profiler session the phase names (and a Tracer span's) are
    events of this thread's line in the host plane, read back the way the
    benchmark reads a trace."""
    from benchmarks import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with trace.phase("t.traced.outer", iteration=1):
            with trace.phase("t.traced.inner"):
                time.sleep(0.002)
        with trace.Tracer(enabled=True).span("t.traced.span"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    profile = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    found = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("t.traced."):
                        found[ev.name] = (ev.start_ns,
                                          ev.start_ns + ev.duration_ns)
    assert set(found) == {"t.traced.outer", "t.traced.inner",
                          "t.traced.span"}
    outer, inner = found["t.traced.outer"], found["t.traced.inner"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    assert inner[1] - inner[0] >= 2_000_000


# --------------------------------------------------------- the batcher loop


def test_batcher_leaves_admit_and_step_phases(tiny_params):  # noqa: F811
    eng = make_engine(tiny_params, slots=2)
    b = make_batcher(eng)
    b.tracer = RequestTracer()
    t0 = time.monotonic()
    b.start()
    try:
        reqs = [b.submit(Request(np.arange(1, 4, dtype=np.int32),
                                 max_new_tokens=n)) for n in (2, 12, 3, 1)]
        results = [r.result(timeout=60) for r in reqs]
        loop = b.stats()["loop"]
        thread = b._thread.ident
    finally:
        b.stop()      # joins the thread: every phase has closed
    log = [r for r in trace.phase_log(since=t0) if r["thread"] == thread]
    admits = [r for r in log if r["name"] == "serve.loop.admit"]
    steps = [r for r in log if r["name"] == "serve.loop.step"]

    # every request joined in some admit pass; a pass that admitted none
    # left no record
    assert sorted(i for r in admits for i in r["counts"]["ids"]) \
        == sorted(r.id for r in reqs)
    assert all(r["counts"]["admitted"] == len(r["counts"]["ids"]) >= 1
               for r in admits)
    assert admits[0]["counts"]["live_lanes"] == 0
    assert any(r["counts"]["live_lanes"] >= 1 for r in admits[1:])

    # children fit inside their parents, and each parent has them all
    tops = {"serve.loop.admit": admits, "serve.loop.step": steps}
    for rec in log:
        if rec["parent"] is None:
            continue
        parent = [p for p in tops[rec["parent"]]
                  if p["iteration"] == rec["iteration"] and inside(rec, p)]
        assert len(parent) == 1, rec
    for step in steps:
        kids = [r["name"] for r in log if r["parent"] == "serve.loop.step"
                and r["iteration"] == step["iteration"]]
        assert kids == ["serve.step.dispatch", "serve.step.fetch",
                        "serve.step.retire"]
    prefills = [r for r in log if r["name"] == "serve.admit.prefill"]
    assert [r["counts"]["request"] for r in prefills] == \
        [r["counts"]["request"] for r in log
         if r["name"] == "serve.admit.blocks"]
    assert all(r["counts"]["bucket"] == 8 and r["counts"]["novel"] == 3
               for r in prefills)
    assert len([r for r in log if r["name"] == "serve.admit.first_token"]) \
        == len(reqs)

    # lanes are the batcher's own occupancy; step numbers are its steps
    assert sum(r["counts"]["lanes"] for r in steps) == b.occupancy_sum
    assert [r["iteration"] for r in steps] == list(range(1, b.steps + 1))
    retired = [i for r in log if r["name"] == "serve.step.retire"
               for i in r["counts"]["ids"]]
    assert sorted(retired) == sorted(r.id for r in reqs[:3])  # 4th: at admit

    # one request's gaps sum to the time from its first to its last token
    slot_of = {r["counts"]["request"]: r["counts"]["slot"] for r in prefills}
    for req, res in zip(reqs[:3], results):
        gaps = [g for r in steps
                if req.first_token_at < r["end"] and r["start"]
                < req.finished_at
                for s, g in zip(r["counts"]["slots"], r["counts"]["gaps_ms"])
                if s == slot_of[req.id]]
        assert len(gaps) == len(req.out_tokens) - 1
        assert sum(gaps) == pytest.approx(
            (req.finished_at - req.first_token_at) * 1e3, abs=1e-6)
        assert res["itl_max_ms"] == pytest.approx(max(gaps), abs=1e-3)
    # the long request stood still while later ones were prefilled
    stalls = sum(r["end"] - max(r["start"], reqs[1].first_token_at)
                 for r in admits if r["end"] > reqs[1].first_token_at)
    assert results[1]["stalled_ms"] == pytest.approx(stalls * 1e3, abs=1e-2)
    assert results[1]["stalled_ms"] > 0
    assert "itl_max_ms" not in results[3]      # one token: no gap

    # the operator's readers: /v1/stats and the request's trace
    assert loop["steps"] >= 1 and loop["host_ms_per_step"] > 0
    assert 0 < loop["admit_share"] + loop["step_share"] \
        + loop["idle_share"] <= 1.0001
    b.tracer.flush()
    decode = [s for s in b.tracer.local_spans if s["name"] == "serve.decode"
              and s["trace_id"] == reqs[1].id]
    assert decode[0]["attrs"]["stalled_ms"] == results[1]["stalled_ms"]
    assert decode[0]["attrs"]["itl_max_ms"] == results[1]["itl_max_ms"]
    assert not hasattr(b, "events")


# ------------------------------------------------------------- the fit loop


def test_fit_leaves_one_step_phase_a_step_with_its_children(tmp_path):
    from determined_tpu import core
    from determined_tpu.train import Trainer
    from determined_tpu.train.trial import TrialContext
    from tests.test_trainer import TinyGPT2Trial

    ctx = core.init(max_length=5, checkpoint_dir=str(tmp_path),
                    async_checkpointing=False)
    reports = []
    report = ctx.train.report_training_metrics
    ctx.train.report_training_metrics = lambda step, m: (
        reports.append((step, dict(m))), report(step, m))
    t0 = time.monotonic()
    Trainer(TinyGPT2Trial(TrialContext()), core_context=ctx).fit(
        report_period=1)
    ctx.close()
    log = mine(since=t0)
    steps = [r for r in log if r["name"] == "harness.step"]
    assert [r["iteration"] for r in steps] == [1, 2, 3, 4, 5]
    assert [r["counts"]["step"] for r in steps] == [1, 2, 3, 4, 5]
    for step in steps:
        kids = [r for r in log if r["iteration"] == step["iteration"]
                and r["parent"] == "harness.step"]
        assert [r["name"] for r in kids] == [
            "harness.step.input", "harness.step.dispatch",
            "harness.flush.fetch", "harness.flush.report"]
        assert all(inside(k, step) for k in kids)
        assert all(a["end"] <= b["start"] for a, b in zip(kids, kids[1:]))
    # host_ms: the steps that ended since the last report, less their fetch
    assert "host_ms" not in reports[0][1]
    for (_, metrics), prev in zip(reports[1:], steps):
        fetch = next(r for r in log if r["name"] == "harness.flush.fetch"
                     and r["iteration"] == prev["iteration"])
        want = (prev["end"] - prev["start"]
                - (fetch["end"] - fetch["start"])) * 1e3
        assert float(metrics["host_ms"]) == pytest.approx(want, abs=1e-6)
