"""Traffic: the stratified seeded shuffle, the closed loop's discipline,
and what a window counts."""

import collections
import threading
import time

import numpy as np
import pytest

from benchmarks import cells, loops, traffic

ROOT = loops.os.path.dirname(loops.os.path.dirname(
    loops.os.path.abspath(loops.__file__)))


def _spec(name, tiny=False):
    manifest = cells.load_manifest(ROOT)
    return cells.load_json(cells.find(ROOT, manifest, f"traffic/{name}.json"),
                           tiny)


@pytest.mark.parametrize("name", ["decode-closed", "prompts-closed"])
def test_two_seeds_same_shapes_other_order_other_ids(name):
    spec = _spec(name)
    n = len(spec["shapes"])
    a = [traffic.shape_at(spec, 1, k) for k in range(n)]
    b = [traffic.shape_at(spec, 2 ** 31 + 7, k) for k in range(n)]
    grid = sorted(tuple(s) for s in spec["shapes"])
    assert sorted(a) == sorted(b) == grid       # the same multiset
    assert a != b                               # in another order
    second_pass = [traffic.shape_at(spec, 1, n + k) for k in range(n)]
    assert sorted(second_pass) == grid and second_pass != a
    ids_a, _ = traffic.prompt_at(spec, 1, 0, 50257)
    ids_a2, _ = traffic.prompt_at(spec, 1, 0, 50257)
    ids_b, _ = traffic.prompt_at(spec, 2, 0, 50257)
    assert np.array_equal(ids_a, ids_a2)        # the same seed, the same ids
    assert len(ids_a) == a[0][0] and not np.array_equal(
        ids_a[:8], ids_b[:8])


@pytest.mark.parametrize("name", ["decode-closed", "prompts-closed"])
def test_grid_is_the_stated_log_uniform_quantiles(name):
    spec = _spec(name)
    lo, hi = spec["prompt_range"]
    prompts = sorted(p for p, _ in spec["shapes"])
    assert prompts == traffic.log_uniform_grid(lo, hi, len(prompts))
    assert lo <= prompts[0] and prompts[-1] <= hi
    news = [n for _, n in spec["shapes"]]
    assert min(news) >= spec["new_range"][0] \
        and max(news) <= spec["new_range"][1]


def test_train_rows_differ_by_row_step_and_seed():
    a = traffic.train_rows(5, 0, 8, 32, 512)
    assert a.shape == (8, 33) and a.dtype == np.int32
    assert len({row.tobytes() for row in a}) == 8
    assert np.array_equal(a, traffic.train_rows(5, 0, 8, 32, 512))
    assert not np.array_equal(a, traffic.train_rows(5, 1, 8, 32, 512))
    assert not np.array_equal(a, traffic.train_rows(6, 0, 8, 32, 512))


class _FakeBatcher:
    """Answers every request a little later, from another thread, and
    counts how many it holds at once."""

    def __init__(self):
        self.lock = threading.Lock()
        self.in_flight = self.max_in_flight = self.submitted = 0

    def submit(self, req):
        with self.lock:
            self.in_flight += 1
            self.submitted += 1
            self.max_in_flight = max(self.max_in_flight, self.in_flight)
        threading.Timer(0.002, self._answer, (req,)).start()
        return req

    def _answer(self, req):
        with self.lock:
            self.in_flight -= 1
        req.out_tokens.extend([1] * req.max_new_tokens)
        req._finish()


def test_closed_loop_never_exceeds_its_callers():
    spec = dict(_spec("decode-closed", tiny=True), callers=3)
    batcher = _FakeBatcher()
    loop = loops.ClosedLoop(batcher, spec, seed=4, vocab=512)
    loop.start()
    deadline = time.monotonic() + 20
    while batcher.submitted < 60 and time.monotonic() < deadline:
        time.sleep(0.01)
    loop.finish()
    assert not any(t.is_alive() for t in loop.threads)
    assert batcher.submitted >= 60
    assert batcher.max_in_flight <= 3
    per_caller = collections.Counter(r["caller"] for r in loop.records)
    assert set(per_caller) == {0, 1, 2}
    # caller c's turn t is stream element c + 3 t, whoever ran first
    for r in loop.records:
        assert r["k"] % 3 == r["caller"]
        assert (len(r["prompt"]), r["new"]) == traffic.shape_at(spec, 4, r["k"])
    assert all(r["late_ms"] >= 0 for r in loop.records if "late_ms" in r)


def test_window_counts_only_work_finished_inside():
    # (kind, start, end, tokens processed, context, tokens generated)
    calls = [("decode", 0.0, 1.0, 4, 40, 4), ("prefill", 1.0, 1.5, 9, 0, 1),
             ("decode", 1.5, 2.5, 5, 55, 5), ("decode", 2.5, 3.5, 5, 60, 5),
             ("decode", 3.5, 4.5, 4, 50, 4), ("decode", 4.5, 5.5, 4, 54, 4)]
    records = [{"id": i, "finished": t} for i, t in
               enumerate([1.0001, 2.5001, 2.5002, 4.5001, 5.5001])]
    t_open, t_close, inside, done = loops.window_of(
        calls, records, opener=1.0001, closer=4.5001)
    assert (t_open, t_close) == (1.0, 4.5)
    assert [c[2] for c in inside] == [1.5, 2.5, 3.5, 4.5]
    assert sum(c[5] for c in inside) == 1 + 5 + 5 + 4
    # the opener (finished by the call that ended AT the edge) is outside;
    # the closer and whatever its call finished are inside
    assert [r["id"] for r in done] == [1, 2, 3]


class _Clock:
    def __init__(self):
        self.now = 100.0

    def monotonic(self):
        return self.now


def test_training_window_opens_and_closes_at_flushes(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(loops.time, "monotonic", clock.monotonic)
    trainer = type("T", (), {"state": None})()
    spec = {"warm_steps": 5, "trace_steps": 8}
    sink = loops.FitSink(trainer, t0=90.0, seconds=2.0, spec=spec, b1=0.9,
                         trace=False, root=".", seed=1)
    with pytest.raises(loops.WindowClosed):
        for step in range(4, 40):
            clock.now += 0.3
            sink(step, {"loss": 1.0, "input_wait_ms": 0.5,
                        "all_finite": 0.0 if step == 7 else 1.0})
    assert sink.open["step"] == 5                 # step 4 is still warm-up
    assert sink.setup_s == pytest.approx(100.6 - 90.0)
    assert sink.close["step"] - sink.open["step"] == 7   # 7 * 0.3 >= 2.0
    assert sink.close["t"] - sink.open["t"] == pytest.approx(2.1)
    assert [r["step"] for r in sink.reports] == list(range(6, 13))
    assert sum(1 for r in sink.reports if not r["finite"]) == 1
