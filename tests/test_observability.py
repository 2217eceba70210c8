"""Observability: trial lifecycle tracing + fleet Prometheus metrics
(docs/observability.md).

Reference: internal/prom/det_state_metrics.go (master gauges) and the
profiler-metrics pipeline (SURVEY §5 asks for TPU utilization in it).
Covers the Tracer span library, the master span ingest/read API, the
expanded master /metrics, the agent's own /metrics, the serve exposition,
the metric/span-name registry lint, and the profiler hardening
satellites."""

import json
import os
import time
import urllib.error
import urllib.request

import pytest

from determined_tpu.common import faultpoint
from determined_tpu.common import metric_names
from determined_tpu.common.trace import Tracer, now_us, render_waterfall
from determined_tpu.core._profiler import PEAK_BF16_FLOPS, ProfilerContext
from determined_tpu.core._train import TrainContext
from tests.test_platform_e2e import (
    Devcluster,
    _create_experiment,
    _experiment_config,
    _free_port,
    _wait_experiment,
)


class TestProfilerUtilization:
    def test_device_flops_util_math(self):
        train = TrainContext(None)
        p = ProfilerContext(train)
        p._peak = 197e12  # v5e chip peak (CPU test host detects none)
        p.set_flops_per_step(197e12 * 0.5, n_devices=1)  # half-peak model
        p.observe_steps(20, 10.0)  # 2 steps/sec
        m = p._utilization_window()
        assert m["steps_per_second"] == pytest.approx(2.0)
        assert m["device_flops_util"] == pytest.approx(1.0)  # 2 × half = peak
        # window resets after read
        assert p._utilization_window() == {}

    def test_multi_device_normalization(self):
        p = ProfilerContext(TrainContext(None))
        p._peak = 100.0
        p.set_flops_per_step(400.0, n_devices=8)  # global-step flops
        p.observe_steps(10, 10.0)  # 1 step/sec
        m = p._utilization_window()
        assert m["device_flops_util"] == pytest.approx(0.5)

    def test_no_flops_no_series(self):
        p = ProfilerContext(TrainContext(None))
        p._peak = 100.0
        p.observe_steps(5, 1.0)
        m = p._utilization_window()
        assert "device_flops_util" not in m
        assert m["steps_per_second"] == pytest.approx(5.0)

    def test_peak_table_covers_v5e(self):
        assert PEAK_BF16_FLOPS["TPU v5 lite"] == 197e12

    def test_input_pipeline_gauges(self):
        """DevicePrefetcher window sums flow through observe_input into the
        profiling series as per-batch means."""
        p = ProfilerContext(TrainContext(None))
        p.observe_input(40.0, 8.0, 6.0, 4)   # two flushes accumulate
        p.observe_input(20.0, 4.0, 2.0, 4)
        m = p._utilization_window()
        assert m["input_wait_ms"] == pytest.approx(7.5)   # 60/8
        assert m["h2d_ms"] == pytest.approx(1.5)          # 12/8
        assert m["prefetch_queue_depth"] == pytest.approx(1.0)  # 8/8
        # window resets; zero-batch observations are ignored
        p.observe_input(0.0, 0.0, 0.0, 0)
        assert p._utilization_window() == {}

    def test_trainer_feeds_profiler(self, tmp_path):
        """Trainer.fit(profile=True) reports a profiling metric series."""
        from determined_tpu import core
        from determined_tpu.train import Trainer
        from determined_tpu.train.trial import TrialContext
        from tests.test_trainer import TinyGPT2Trial

        class FlopsTrial(TinyGPT2Trial):
            def flops_per_step(self):
                return 1e9

        ctx = core.init(max_length=4, checkpoint_dir=str(tmp_path),
                        async_checkpointing=False)
        trainer = Trainer(FlopsTrial(TrialContext()), core_context=ctx)
        # make the collector tick fast enough for a short run
        trainer.fit(report_period=1, profile=True)
        ctx.profiler._collector is None or ctx.profiler.off()
        # observe_steps was fed; utilisation window accumulates between
        # collector ticks — read it directly
        assert ctx.profiler._flops_per_step == 1e9
        ctx.close()


def test_master_metrics_endpoint(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    try:
        c.start_agent()
        token = c.login()
        # generate some API traffic
        c.api("GET", "/api/v1/agents", token=token)
        # unauthenticated scrape is rejected like every API route
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(c.master_url + "/metrics", timeout=10)
        assert ei.value.code == 401
        req = urllib.request.Request(
            c.master_url + "/metrics",
            headers={"Authorization": f"Bearer {token}"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            body = r.read().decode()
        assert "det_agents_alive 1" in body
        assert "det_slots_total 2" in body
        assert "det_slots_free 2" in body
        assert "det_scheduler_queue_depth 0" in body
        assert 'det_api_requests_total{code="200"}' in body
        assert "det_api_request_seconds_count" in body
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# Tracer span library (determined_tpu/common/trace.py).
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_parentage_and_attrs(self):
        t = Tracer(enabled=True)
        with t.span("harness.validate", step=3) as outer:
            with t.span("harness.checkpoint.save") as inner:
                assert inner.parent == outer.span_id
            assert outer.parent == t.root_span_id
        t.flush()
        spans = {s["name"]: s for s in t.local_spans}
        # Children buffer before parents (closed inner-first); parentage
        # is by id, not order.
        assert spans["harness.checkpoint.save"]["parent"] == \
            spans["harness.validate"]["span_id"]
        assert spans["harness.validate"]["attrs"] == {"step": 3}
        for s in spans.values():
            assert s["end_us"] >= s["start_us"] > 0
            assert s["trace_id"] == t.trace_id

    def test_emit_defaults_parent_to_root(self):
        t = Tracer(enabled=True)
        t0 = now_us()
        sp = t.emit("harness.compile", t0, t0 + 5, {"executable": "x"})
        assert sp.parent == t.trace_id  # root span id == trace id
        t.flush()
        assert t.local_spans[0]["start_us"] == t0
        assert t.local_spans[0]["end_us"] == t0 + 5

    def test_exception_records_span_with_error_attr(self):
        t = Tracer(enabled=True)
        with pytest.raises(ValueError):
            with t.span("harness.restore"):
                raise ValueError("boom")
        t.flush()
        assert t.local_spans[0]["attrs"]["error"] == "ValueError"
        # The parent stack unwound: a new span parents to root again.
        with t.span("harness.validate") as sp:
            assert sp.parent == t.root_span_id

    def test_flush_batches_and_empties_buffer(self):
        t = Tracer(enabled=True)
        assert t.flush() == 0  # empty flush is free
        t.emit("a.b", 1, 2)
        t.emit("c.d", 2, 3)
        assert t.pending() == 2
        assert t.flush() == 2
        assert t.pending() == 0
        assert len(t.local_spans) == 2

    def test_trace_off_env_disables_emission(self, monkeypatch):
        monkeypatch.setenv("DET_TRACE_OFF", "1")
        t = Tracer()
        assert not t.enabled
        with t.span("harness.validate") as sp:
            assert sp is None
        assert t.emit("a.b", 1, 2) is None
        assert t.flush() == 0 and t.local_spans == []

    def test_trace_id_from_env(self, monkeypatch):
        monkeypatch.setenv("DET_TRACE_ID", "cafe0123deadbeef")
        t = Tracer()
        assert t.trace_id == "cafe0123deadbeef"
        assert t.root_span_id == "cafe0123deadbeef"

    def test_span_drop_fault_point(self):
        """docs/chaos.md trace.span.drop: the sink eats the batch, the
        caller never sees an error (trials survive span-sink loss)."""
        t = Tracer(enabled=True)
        t.emit("a.b", 1, 2)
        faultpoint.arm("trace.span.drop", "drop", count=1)
        try:
            assert t.flush() == 0
        finally:
            faultpoint.disarm_all()
        assert t.dropped == 1 and t.local_spans == []
        # Next batch flows again.
        t.emit("c.d", 1, 2)
        assert t.flush() == 1

    def test_sink_failure_drops_batch_not_the_trial(self):
        class DeadSession:
            def post(self, *a, **kw):
                raise ConnectionError("sink down")

        t = Tracer(session=DeadSession(), trial_id=7, enabled=True)
        t.emit("a.b", 1, 2)
        assert t.flush() == 0  # logged + dropped, no raise
        assert t.dropped == 1 and t.pending() == 0

    def test_flush_posts_idempotent_batch(self):
        calls = []

        class FakeSession:
            def post(self, path, body=None, idempotent=False, **kw):
                calls.append((path, body, idempotent))

        t = Tracer(session=FakeSession(), trial_id=42, enabled=True)
        t.emit("a.b", 1, 2)
        t.emit("c.d", 3, 4)
        assert t.flush() == 2
        (path, body, idempotent), = calls
        assert path == "/api/v1/trials/42/spans"
        assert idempotent is True
        assert [s["name"] for s in body["spans"]] == ["a.b", "c.d"]

    def test_render_waterfall(self):
        t = Tracer(enabled=True)
        t0 = now_us()
        t.emit("trial.queue_wait", t0, t0 + 100_000)
        t.emit("agent.container_start", t0 + 100_000, t0 + 150_000)
        t.flush()
        out = render_waterfall(t.local_spans)
        assert "trial.queue_wait" in out and "agent.container_start" in out
        assert "100.0" in out  # queue wait duration in ms
        assert render_waterfall([]) == "(no spans)"


# ---------------------------------------------------------------------------
# Metric/span name registry + lint (the make-lint drift gate).
# ---------------------------------------------------------------------------


class TestMetricRegistry:
    def test_registry_self_check_clean(self):
        assert metric_names.check_registry() == []

    def test_repo_emitters_match_registry(self):
        """The actual repo sources and the registry agree in BOTH
        directions — this is the same check `make lint` runs."""
        from determined_tpu.analysis import metric_lint

        assert metric_lint.lint_registry() == []

    def test_naming_rules_catch_violations(self, monkeypatch):
        monkeypatch.setitem(metric_names.MASTER_METRICS,
                            "det_badCounter", ("counter", "x"))
        monkeypatch.setitem(metric_names.MASTER_METRICS,
                            "det_events_lost", ("counter", "x"))
        monkeypatch.setitem(metric_names.MASTER_METRICS,
                            "det_queue_wait", ("gauge", "no unit"))
        problems = "\n".join(metric_names.check_registry())
        assert "det_badCounter" in problems          # not snake_case
        assert "det_events_lost" in problems         # counter w/o _total
        assert "det_queue_wait" in problems          # measured, no unit

    def test_scan_finds_metric_literals_only_in_strings(self):
        from determined_tpu.analysis.metric_lint import _emitted_metrics

        text = '''
        // comment about det_state_metrics.go stays out
        out << "# TYPE det_agents_alive gauge\\n";
        out << "det_api_request_seconds_bucket{route=\\"x\\"} 1\\n";
        f(".det_status");  // filenames stay out
        '''
        assert _emitted_metrics(text) == {"det_agents_alive",
                                          "det_api_request_seconds"}

    def test_scan_finds_span_call_sites(self):
        from determined_tpu.analysis.metric_lint import _emitted_spans

        py = 'with core.tracer.span(\n        "harness.restore", x=1):\n' \
             '    tracer.emit("harness.compile", t0, t1)\n' \
             '    self._span("harness.checkpoint.save", t0)\n'
        assert _emitted_spans("a.py", py) == {
            "harness.restore", "harness.compile", "harness.checkpoint.save"}
        cc = 'trace::make_span(\n    trial->trace_id, "trial.queue_wait",\n'
        assert _emitted_spans("a.cc", cc) == {"trial.queue_wait"}

    def test_unregistered_emission_is_flagged(self, tmp_path):
        """A fresh gauge added to an emitter without a registry row fails
        the lint (the drift this satellite exists to prevent)."""
        from determined_tpu.analysis import metric_lint

        root = tmp_path
        for rel in metric_lint.METRIC_SOURCES + metric_lint.SPAN_SOURCES:
            p = root / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(open(os.path.join(
                metric_lint.REPO_ROOT, rel)).read())
        agent = root / "native/agent/main.cc"
        agent.write_text(agent.read_text() +
                         '\n// new\nconst char* x = "det_agent_new_thing";\n')
        problems = metric_lint.lint_registry(str(root))
        assert any("det_agent_new_thing" in p for p in problems)


# ---------------------------------------------------------------------------
# Serving exposition (determined_tpu/serve/http.py /metrics).
# ---------------------------------------------------------------------------


def _parse_prom(text: str):
    """Tiny Prometheus text-format parser: 'name{labels}' -> float, plus a
    {name -> type} map from # TYPE lines. Raises on malformed lines."""
    values, types = {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split()
            types[name] = mtype
            continue
        if line.startswith("#"):
            continue
        series, value = line.rsplit(" ", 1)
        values[series] = float(value)
    return values, types


def test_serve_prometheus_exposition():
    from determined_tpu.serve.http import prometheus_exposition

    stats = {"queue_depth": 3, "active": 5, "draining": True,
             "completed": 17, "generated_tokens": 123,
             "kv_blocks": {"free_blocks": 9, "num_blocks": 16}}
    values, types = _parse_prom(prometheus_exposition(stats))
    assert values["det_serve_queue_depth"] == 3
    assert values["det_serve_active_requests"] == 5
    assert values["det_serve_draining"] == 1
    assert values["det_serve_kv_blocks_free"] == 9
    assert values["det_serve_kv_blocks_total"] == 16
    assert values["det_serve_requests_total"] == 17
    assert values["det_serve_tokens_total"] == 123
    assert types["det_serve_tokens_total"] == "counter"


# ---------------------------------------------------------------------------
# Profiler hardening satellites (core/_profiler.py).
# ---------------------------------------------------------------------------


class TestProfilerHardening:
    def test_off_joins_collector_bounded(self):
        """The collector's stop event no longer shadows
        threading.Thread._stop (join() used to blow up), and off() joins
        the thread instead of orphaning it."""
        p = ProfilerContext(TrainContext(None))
        p.on(sampling_interval=0.05)
        collector = p._collector
        assert collector.is_alive()
        t0 = time.monotonic()
        p.off()
        assert time.monotonic() - t0 < 5.0
        assert not collector.is_alive()
        assert p._collector is None
        p.off()  # idempotent

    def test_trace_reentry_refused_without_wedging(self, monkeypatch):
        import jax

        calls = {"start": 0, "stop": 0}
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d, **kw: calls.__setitem__("start", calls["start"] + 1))
        monkeypatch.setattr(
            jax.profiler, "stop_trace",
            lambda: calls.__setitem__("stop", calls["stop"] + 1))
        p = ProfilerContext(TrainContext(None), tensorboard_dir="/tmp/tb-t")
        with p.trace():
            with p.trace():  # nested: runs untraced, does NOT re-start
                pass
            assert calls == {"start": 1, "stop": 0}
        assert calls == {"start": 1, "stop": 1}
        # Usable again afterwards.
        with p.trace():
            pass
        assert calls == {"start": 2, "stop": 2}

    def test_trace_start_failure_logs_not_raises(self, monkeypatch):
        import jax

        def boom(d, **kw):
            raise RuntimeError("profiler unavailable")

        stopped = []
        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: stopped.append(1))
        p = ProfilerContext(TrainContext(None), tensorboard_dir="/tmp/tb-t")
        ran = []
        with p.trace():
            ran.append(1)  # body still runs
        assert ran == [1]
        assert stopped == []  # never started -> never stopped
        assert p._trace_active is False

    def test_trace_stop_failure_clears_active_flag(self, monkeypatch):
        import jax

        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d, **kw: None)

        def boom():
            raise RuntimeError("wedged")

        monkeypatch.setattr(jax.profiler, "stop_trace", boom)
        p = ProfilerContext(TrainContext(None), tensorboard_dir="/tmp/tb-t")
        with p.trace():
            pass  # stop failure is swallowed
        assert p._trace_active is False


# ---------------------------------------------------------------------------
# Trainer span emission (local mode; real jitted steps).
# ---------------------------------------------------------------------------


def test_trainer_emits_lifecycle_spans(tmp_path):
    """A local fit lands compile + checkpoint save/commit + validate spans
    in the tracer buffer, with root parentage and zero per-step residue
    (the compile wrapper uninstalls itself)."""
    from determined_tpu import core
    from determined_tpu.train import Trainer
    from determined_tpu.train.trial import TrialContext
    from tests.test_trainer import TinyGPT2Trial

    ctx = core.init(max_length=6, checkpoint_dir=str(tmp_path),
                    async_checkpointing=False)
    trainer = Trainer(TinyGPT2Trial(TrialContext()), core_context=ctx)
    trainer.fit(report_period=2, checkpoint_period=3, validation_period=3)
    ctx.close()
    names = [s["name"] for s in ctx.tracer.local_spans]
    assert "harness.compile" in names
    assert "harness.checkpoint.save" in names
    assert "harness.checkpoint.commit" in names
    by_name = {s["name"]: s for s in ctx.tracer.local_spans}
    compiles = [s for s in ctx.tracer.local_spans
                if s["name"] == "harness.compile"]
    compile_span = next(s for s in compiles
                        if s["attrs"]["executable"] == "train_step")
    assert compile_span["parent"] == ctx.tracer.root_span_id
    # Exactly one compile span per executable: the wrapper uninstalled.
    assert names.count("harness.compile") == len(
        {s["attrs"]["executable"] for s in ctx.tracer.local_spans
         if s["name"] == "harness.compile"})
    # Non-overlapping phase accounting: the checkpoint save follows the
    # compile (first step) and the commit follows its save.
    save = by_name["harness.checkpoint.save"]
    commit = by_name["harness.checkpoint.commit"]
    assert save["start_us"] >= compile_span["end_us"]
    assert commit["start_us"] >= save["end_us"]
    assert save["attrs"]["storage_id"] == commit["attrs"]["storage_id"]


def test_trainer_fit_unchanged_with_tracing_off(tmp_path, monkeypatch):
    """DET_TRACE_OFF=1: no spans, and fit still runs to completion — the
    bench A/B switch must not change training behavior."""
    monkeypatch.setenv("DET_TRACE_OFF", "1")
    from determined_tpu import core
    from determined_tpu.train import Trainer
    from determined_tpu.train.trial import TrialContext
    from tests.test_trainer import TinyGPT2Trial

    ctx = core.init(max_length=4, checkpoint_dir=str(tmp_path),
                    async_checkpointing=False)
    trainer = Trainer(TinyGPT2Trial(TrialContext()), core_context=ctx)
    state = trainer.fit(report_period=2)
    assert state is not None
    ctx.close()
    assert ctx.tracer.local_spans == []


# ---------------------------------------------------------------------------
# Master span ingest/read API + expanded /metrics (devcluster, master-only).
# ---------------------------------------------------------------------------


@pytest.fixture()
def master_only(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    yield c
    c.stop()


def _unmanaged_trial(cluster, token):
    eid = cluster.api("POST", "/api/v1/experiments",
                      {"unmanaged": True, "config": {"name": "obs"}},
                      token=token)["id"]
    tid = cluster.api("POST", f"/api/v1/experiments/{eid}/trials",
                      {"hparams": {}}, token=token)["id"]
    return eid, tid


def _mk_span(name, start, end, span_id=None, parent=""):
    import uuid

    return {"trace_id": "t1", "span_id": span_id or uuid.uuid4().hex[:16],
            "parent": parent, "name": name, "start_us": start,
            "end_us": end, "attrs": {"k": "v"}}


def test_span_ingest_roundtrip_dedupe_and_validation(master_only):
    c = master_only
    token = c.login()
    _, tid = _unmanaged_trial(c, token)

    s1 = _mk_span("agent.container_start", 1000, 2000)
    s2 = _mk_span("harness.compile", 2000, 5000, parent=s1["span_id"])
    r = c.api("POST", f"/api/v1/trials/{tid}/spans",
              {"spans": [s1, s2]}, token=token)
    assert r["ingested"] == 2

    # Row-level dedupe: replaying the same batch inserts nothing new.
    c.api("POST", f"/api/v1/trials/{tid}/spans", {"spans": [s1, s2]},
          token=token)
    trace = c.api("GET", f"/api/v1/trials/{tid}/trace", token=token)
    assert len(trace["spans"]) == 2
    # Ordered by start time; parentage preserved.
    assert [s["name"] for s in trace["spans"]] == [
        "agent.container_start", "harness.compile"]
    assert trace["spans"][1]["parent"] == s1["span_id"]
    assert trace["spans"][0]["attrs"] == {"k": "v"}

    # Malformed entries are skipped, the batch survives.
    r = c.api("POST", f"/api/v1/trials/{tid}/spans",
              {"spans": [{"name": "", "span_id": "x"},
                         _mk_span("agent.log_drain", 6000, 7000)]},
              token=token)
    assert r["ingested"] == 1

    # Contract errors.
    with pytest.raises(urllib.error.HTTPError) as ei:
        c.api("POST", f"/api/v1/trials/{tid}/spans", {"nope": 1},
              token=token)
    assert ei.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        c.api("GET", "/api/v1/trials/999999/trace", token=token)
    assert ei.value.code == 404


def _scrape(cluster, token):
    req = urllib.request.Request(
        cluster.master_url + "/metrics",
        headers={"Authorization": f"Bearer {token}"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.headers.get("Content-Type"), r.read().decode()


def test_master_metrics_exposition_and_counters_increment(master_only):
    """The satellite: exposition content-type, ApiStats counters actually
    move across an API call, and every new gauge parses with a tiny
    text-format parser."""
    c = master_only
    token = c.login()

    ctype, text = _scrape(c, token)
    assert ctype.startswith("text/plain")
    assert "version=0.0.4" in ctype
    values, types = _parse_prom(text)

    # New fleet gauges present and typed.
    for name in ("det_slots_allocated", "det_slots_draining",
                 "det_stream_backlog_events"):
        assert values.get(name) is not None, name
        assert types[name] == "gauge"
    for name in ("det_preemptions_total", "det_resizes_total",
                 "det_trial_requeues_total", "det_idempotency_replays_total",
                 "det_trial_spans_ingested_total"):
        assert name in values and types[name] == "counter"
    assert types["det_scheduler_queue_wait_seconds"] == "histogram"
    assert types["det_api_request_seconds"] == "histogram"

    before = values['det_api_requests_total{code="200"}']
    c.api("GET", "/api/v1/agents", token=token)
    values2, _ = _parse_prom(_scrape(c, token)[1])
    assert values2['det_api_requests_total{code="200"}'] > before
    # Route-family latency histogram saw the agents call; +Inf bucket ==
    # series count (cumulative-bucket invariant).
    inf = values2['det_api_request_seconds_bucket{route="agents",le="+Inf"}']
    cnt = values2['det_api_request_seconds_count{route="agents"}']
    assert inf == cnt >= 1


def test_span_ingest_bumps_counter_and_replay_cache_metric(master_only):
    c = master_only
    token = c.login()
    _, tid = _unmanaged_trial(c, token)

    values0, _ = _parse_prom(_scrape(c, token)[1])

    # Idempotency-keyed batch, sent twice with the SAME key: the second is
    # answered from the replay cache — no double-insert, replay counter up.
    body = json.dumps({"spans": [_mk_span("harness.validate", 1, 2)]}).encode()
    key = "obs-test-key-1"
    for _ in range(2):
        req = urllib.request.Request(
            c.master_url + f"/api/v1/trials/{tid}/spans", data=body,
            headers={"Content-Type": "application/json",
                     "Authorization": f"Bearer {token}",
                     "X-Idempotency-Key": key},
            method="POST")
        with urllib.request.urlopen(req, timeout=10) as r:
            resp = json.loads(r.read().decode())
            assert resp["ingested"] == 1
            replayed = r.headers.get("x-idempotent-replay")
    assert replayed == "true"

    values1, _ = _parse_prom(_scrape(c, token)[1])
    assert values1["det_trial_spans_ingested_total"] == \
        values0["det_trial_spans_ingested_total"] + 1  # replay not re-applied
    assert values1["det_idempotency_replays_total"] >= \
        values0["det_idempotency_replays_total"] + 1
    trace = c.api("GET", f"/api/v1/trials/{tid}/trace", token=token)
    assert len(trace["spans"]) == 1


def test_agent_metrics_endpoint(tmp_path, native_binaries):
    """Every agent serves its own /metrics (docs/observability.md): task
    states, log backlog, drain state — parseable Prometheus text."""
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    port = _free_port()
    try:
        c.start_agent(extra_env={"DET_AGENT_METRICS_PORT": str(port)})
        # The agent binds /metrics just after registering; registration
        # visibility can beat the bind by a moment — retry briefly.
        deadline = time.time() + 15
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                    assert r.headers.get("Content-Type").startswith(
                        "text/plain")
                    values, types = _parse_prom(r.read().decode())
                break
            except (urllib.error.URLError, ConnectionError):
                if time.time() > deadline:
                    raise
                time.sleep(0.2)
        assert values["det_agent_slots"] == 2
        assert values['det_agent_tasks{state="running"}'] == 0
        assert values["det_agent_log_backlog_lines"] == 0
        assert values["det_agent_draining"] == 0
        assert values["det_agent_uptime_seconds"] >= 0
        assert types["det_agent_tasks"] == "gauge"
        # /healthz for scrapers' liveness checks.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            assert json.loads(r.read().decode())["status"] == "ok"
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# Acceptance e2e (slow): the full waterfall off a real devcluster trial,
# then the emergency-checkpoint span under a notice-file drain.
# ---------------------------------------------------------------------------


def _span_map(trace):
    out = {}
    for s in trace["spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


@pytest.mark.slow
def test_trace_e2e_full_waterfall(tmp_path, native_binaries):
    """A devcluster trial yields a complete waterfall: queue-wait,
    container-start, compile, ≥1 checkpoint commit — correct parentage,
    non-overlapping phase accounting — and `det trial trace` renders it."""
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    try:
        c.start_agent()
        config = _experiment_config(
            tmp_path,
            searcher={"name": "single", "metric": "val_loss",
                      "max_length": {"batches": 12}},
            extra={"entrypoint": "python3 trace_train.py"},
        )
        eid, token = _create_experiment(c, config)
        _wait_experiment(c, eid, token, timeout=180.0)
        trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                       token=token)["trials"]
        tid = trials[0]["id"]
        trace = c.api("GET", f"/api/v1/trials/{tid}/trace", token=token)
        spans = _span_map(trace)

        for required in ("trial.lifecycle", "trial.queue_wait",
                         "agent.image_setup", "agent.container_start",
                         "harness.compile", "harness.checkpoint.save",
                         "harness.checkpoint.commit", "agent.log_drain"):
            assert required in spans, (required, sorted(spans))

        # Parentage: the root is span_id == trace_id and closed; every
        # other span's parent resolves to a known span.
        root = spans["trial.lifecycle"][0]
        assert root["span_id"] == trace["trace_id"]
        assert root["end_us"] > root["start_us"] > 0
        ids = {s["span_id"] for s in trace["spans"]}
        for s in trace["spans"]:
            if s["name"] == "trial.lifecycle":
                continue
            assert s["parent"] in ids, (s["name"], s["parent"])

        # Non-overlapping phase accounting along the lifecycle chain:
        # queue wait -> image setup -> container start -> compile ->
        # first checkpoint save -> its commit.
        qw = spans["trial.queue_wait"][0]
        img = spans["agent.image_setup"][0]
        cs = spans["agent.container_start"][0]
        compile_sp = spans["harness.compile"][0]
        save = spans["harness.checkpoint.save"][0]
        commit = spans["harness.checkpoint.commit"][0]
        assert qw["end_us"] <= img["start_us"]
        assert img["end_us"] <= cs["start_us"]
        assert cs["start_us"] <= compile_sp["start_us"]
        assert compile_sp["end_us"] <= save["start_us"]
        assert save["end_us"] <= commit["start_us"]
        for s in (qw, img, cs, compile_sp, save, commit):
            assert s["end_us"] >= s["start_us"] > 0, s["name"]

        # The CLI waterfall renders it (the operator-facing surface).
        from determined_tpu.common.api import Session
        from determined_tpu.common.trace import render_waterfall

        session = Session(c.master_url, token)
        resp = session.get(f"/api/v1/trials/{tid}/trace")
        out = render_waterfall(resp["spans"])
        assert "trial.queue_wait" in out and "harness.compile" in out
    finally:
        c.stop()


@pytest.mark.slow
def test_trace_e2e_emergency_span_under_drain(tmp_path, native_binaries):
    """Under a notice-file drain the emergency-checkpoint span lands on
    the trace (flushed before the exit), and the restarted run adds a
    harness.restore span on the survivor."""
    c = Devcluster(str(tmp_path), native_binaries, slots=1)
    c.start_master()
    notice_files = {}
    try:
        for agent_id in ("obs-a", "obs-b"):
            nf = os.path.join(str(tmp_path), f"notice-{agent_id}.json")
            notice_files[agent_id] = nf
            c.start_agent(agent_id,
                          extra_env={"DET_AGENT_NOTICE_FILE": nf})
        config = _experiment_config(
            tmp_path,
            searcher={"name": "single", "metric": "val_loss",
                      "max_length": {"batches": 300}},
            extra={"max_restarts": 2,
                   "entrypoint": "python3 spot_train.py"},
        )
        config["environment"] = {"SPOT_STEP_SLEEP": "0.1"}
        eid, token = _create_experiment(c, config)

        # Mid-run: find the victim agent.
        deadline = time.time() + 120
        trial, victim = None, None
        while time.time() < deadline:
            trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                           token=token)["trials"]
            if trials:
                rows = c.api(
                    "GET",
                    f"/api/v1/trials/{trials[0]['id']}/metrics?group=training",
                    token=token)["metrics"]
                if len(rows) >= 5:
                    trial = trials[0]
                    jobs = [j for j in c.api("GET", "/api/v1/job-queues",
                                             token=token)["jobs"]
                            if j.get("experiment_id") == eid]
                    alloc = c.api(
                        "GET",
                        f"/api/v1/allocations/{jobs[0]['allocation_id']}",
                        token=token)["allocation"]
                    victim = alloc["resources"][0]["agent_id"]
                    break
            time.sleep(0.5)
        assert trial is not None and victim in ("obs-a", "obs-b")

        with open(notice_files[victim], "w") as f:
            json.dump({"deadline_seconds": 30,
                       "reason": "spot_preemption"}, f)

        _wait_experiment(c, eid, token, timeout=240.0)
        trials = c.api("GET", f"/api/v1/experiments/{eid}/trials",
                       token=token)["trials"]
        assert trials[0]["restarts"] >= 1

        trace = c.api("GET", f"/api/v1/trials/{trial['id']}/trace",
                      token=token)
        spans = _span_map(trace)
        assert "harness.checkpoint.emergency" in spans, sorted(spans)
        em = spans["harness.checkpoint.emergency"][0]
        assert em["attrs"].get("attempted") in (True, 1, "true", True)
        # The emergency window nests the phase-2 commit under it.
        commits = spans.get("harness.checkpoint.commit", [])
        assert any(s["parent"] == em["span_id"] for s in commits), (
            "no commit span nested under the emergency window")
        # The restarted run restored on the survivor.
        assert "harness.restore" in spans, sorted(spans)
        restore = spans["harness.restore"][-1]
        assert restore["attrs"].get("restored")
        # Two container runs -> two queue_wait / container_start spans.
        assert len(spans["trial.queue_wait"]) >= 2
        assert len(spans["agent.container_start"]) >= 2
    finally:
        c.stop()
