"""Streaming updates e2e (reference internal/stream/publisher.go +
common/streams/_client.py): entity-change events long-polled while an
experiment runs."""

import threading

import pytest

from determined_tpu.common.api import Session
from determined_tpu.common.streams import StreamClient
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


def test_stream_events_during_experiment(cluster, tmp_path):
    token = cluster.login()
    session = Session(cluster.master_url, token)
    client = StreamClient(session)

    events = []
    stop = threading.Event()

    def consume():
        while not stop.is_set():
            events.extend(client.poll(timeout_seconds=2))

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    eid, _ = _create_experiment(
        cluster, _experiment_config(tmp_path), activate=True)
    _wait_experiment(cluster, eid, token)
    stop.set()
    t.join(timeout=10)

    entities = {e["entity"] for e in events}
    assert {"experiments", "trials", "metrics", "checkpoints"} <= entities, (
        entities)
    # ordered, gapless sequence numbers
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    assert len(set(seqs)) == len(seqs)
    # lifecycle visible: ACTIVE before COMPLETED for our experiment
    states = [e["payload"]["state"] for e in events
              if e["entity"] == "experiments" and e["payload"]["id"] == eid]
    assert "ACTIVE" in states and states[-1] == "COMPLETED", states
    # trial completion observed
    tstates = [e["payload"]["state"] for e in events
               if e["entity"] == "trials"]
    assert "COMPLETED" in tstates
    assert not client.dropped


def test_stream_resync_marker_on_overflow(tmp_path, native_binaries):
    """Bounded backlog (docs/cluster-ops.md "Overload, quotas & fair
    use"): a slow subscriber whose cursor fell off the capped ring gets a
    synthetic `resync` marker at the head of its next batch (plus the
    response-level dropped flag) and must re-list; a subscriber that keeps
    up loses nothing."""
    import json as _json
    import os as _os

    cfg_path = _os.path.join(str(tmp_path), "master-ring.json")
    with open(cfg_path, "w") as f:
        _json.dump({"stream_backlog_cap": 16}, f)
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master(extra_args=("--config", cfg_path))
    try:
        token = c.login()
        session = Session(c.master_url, token)
        eid = c.api("POST", "/api/v1/experiments",
                    {"unmanaged": True, "config": {"name": "stream-ring"}},
                    token=token)["id"]
        tid = c.api("POST", f"/api/v1/experiments/{eid}/trials",
                    {"hparams": {}}, token=token)["id"]

        slow = StreamClient(session)
        fast = StreamClient(session)
        # Prime both cursors with one real event: a fresh subscriber
        # (since=0) is exempt from drop detection by design — only a
        # cursor that points at evicted history must resync.
        c.api("POST", f"/api/v1/trials/{tid}/metrics",
              {"group": "training", "steps_completed": 0,
               "trial_run_id": 0, "metrics": {"loss": 9.0}}, token=token)
        assert slow.poll(timeout_seconds=2.0)
        assert fast.poll(timeout_seconds=2.0)

        fast_events = []
        for batch in range(6):
            for i in range(10):
                c.api("POST", f"/api/v1/trials/{tid}/metrics",
                      {"group": "training",
                       "steps_completed": 1 + batch * 10 + i,
                       "trial_run_id": 0, "metrics": {"loss": 1.0}},
                      token=token)
            # The fast subscriber drains between bursts — each burst (10)
            # fits the 16-slot ring, so it never falls behind.
            fast_events += fast.poll(timeout_seconds=1.0)

        # 60 events went past a 16-slot ring: the slow cursor is gone.
        events = slow.poll(timeout_seconds=1.0)
        assert slow.dropped
        assert events and events[0]["entity"] == "resync", events[:2]
        marker = events[0]["payload"]
        assert marker["latest_seq"] >= marker["since"]
        assert "re-list" in marker["reason"]
        # The marker precedes real events; the cursor still advances.
        assert all(e["entity"] != "resync" for e in events[1:])

        # The fast subscriber saw every report exactly once, in order.
        assert not fast.dropped
        metrics = [e for e in fast_events if e["entity"] == "metrics"]
        assert len(metrics) == 60, len(metrics)
        seqs = [e["seq"] for e in fast_events]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    finally:
        c.stop()


def test_stream_entity_filter_and_since(cluster, tmp_path):
    token = cluster.login()
    session = Session(cluster.master_url, token)
    eid, _ = _create_experiment(
        cluster, _experiment_config(tmp_path), activate=True)
    _wait_experiment(cluster, eid, token)

    only_exp = StreamClient(session).poll(
        entities=["experiments"], timeout_seconds=1)
    assert only_exp and all(e["entity"] == "experiments" for e in only_exp)

    # since-cursor: polling from the last seq returns nothing new
    c2 = StreamClient(session)
    first = c2.poll(timeout_seconds=1)
    assert first
    again = c2.poll(timeout_seconds=1)
    assert again == []
