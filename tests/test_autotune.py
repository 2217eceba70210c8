"""Autotune (dsat-equivalent) tests: pure search-logic unit tests + a full
custom-searcher e2e on the devcluster (reference
pytorch/dsat/_dsat_search_method.py workflow)."""

import pytest

from determined_tpu.autotune import BatchSizeSearchMethod
from tests.test_platform_e2e import Devcluster


class TestSearchLogic:
    def drive(self, method, fits):
        """Simulate the master: run ops until Shutdown; `fits(size)` decides
        OOM. Returns the per-size throughput the method collected."""
        ops = list(method.initial_operations())
        guard = 0
        while ops and guard < 100:
            guard += 1
            op = ops.pop(0)
            kind = type(op).__name__
            if kind == "Create":
                self.sizes[op.request_id] = op.hparams["global_batch_size"]
            elif kind == "ValidateAfter":
                size = self.sizes[op.request_id]
                if fits(size):
                    # throughput grows with size (amortized overhead)
                    ops += method.on_validation_completed(
                        op.request_id, size * 10.0 / (1 + size / 100), op.length)
                else:
                    ops += method.on_trial_exited_early(
                        op.request_id, "errored")
            elif kind == "Close":
                ops += method.on_trial_closed(op.request_id)
            elif kind == "Shutdown":
                return
        raise AssertionError("search did not shut down")

    def setup_method(self, m):
        self.sizes = {}

    def test_cliff_then_binary_search(self):
        method = BatchSizeSearchMethod(start_size=8, max_size=1024)
        self.drive(method, fits=lambda s: s <= 100)
        best, sps = method.best()
        # doubling: 8,16,32,64 fit; 128 fails; binary: 96 fits...
        assert 64 <= best <= 100
        assert method.failed_sizes and min(method.failed_sizes) <= 128
        assert method.progress() == 1.0

    def test_everything_fits_caps_at_max(self):
        method = BatchSizeSearchMethod(start_size=8, max_size=64)
        self.drive(method, fits=lambda s: True)
        best, _ = method.best()
        assert best == 64
        assert method.failed_sizes == []

    def test_nothing_fits(self):
        method = BatchSizeSearchMethod(start_size=8)
        self.drive(method, fits=lambda s: False)
        assert method.results == {}
        assert method.progress() == 1.0

    def test_transient_failure_retried_not_bounded(self):
        """A one-off crash (flaky node) must not become the OOM cliff."""
        flaked = []

        def fits(size):
            if size == 16 and not flaked:
                flaked.append(size)
                return False  # transient: fails once, then fits
            return size <= 40

        method = BatchSizeSearchMethod(start_size=8, max_size=256)
        self.drive(method, fits=fits)
        best, _ = method.best()
        assert best >= 32, (best, method.results)  # recovered past 16
        assert 16 not in method.failed_sizes

    def test_user_cancel_stops_search(self):
        method = BatchSizeSearchMethod(start_size=8)
        ops = method.initial_operations()
        rid = ops[0].request_id
        out = method.on_trial_exited_early(rid, "user_canceled")
        assert type(out[0]).__name__ == "Shutdown"
        assert method.progress() == 1.0

    def test_extra_hparams_passthrough(self):
        method = BatchSizeSearchMethod(
            start_size=8, base_hparams={"remat": True})
        ops = method.initial_operations()
        assert ops[0].hparams == {"remat": True, "global_batch_size": 8}


@pytest.fixture()
def cluster(tmp_path, native_binaries):
    c = Devcluster(str(tmp_path), native_binaries)
    c.start_master()
    c.start_agent()
    yield c
    c.stop()


def test_autotune_e2e(cluster, tmp_path):
    """The full dsat-style workflow: RemoteSearchRunner drives the
    autotuner against real trials that fake an OOM cliff at 64."""
    import os

    from determined_tpu.experimental.client import Determined
    from determined_tpu.searcher import RemoteSearchRunner
    from tests.test_platform_e2e import FIXTURES

    os.environ["DET_MASTER"] = cluster.master_url
    try:
        client = Determined(cluster.master_url)
        method = BatchSizeSearchMethod(start_size=8, max_size=512,
                                       profile_steps=2)
        runner = RemoteSearchRunner(method, client=client)
        config = {
            "name": "autotune-batch-size",
            "entrypoint": "python3 autotune_train.py",
            "searcher": {"name": "custom", "metric": "samples_per_second",
                         "smaller_is_better": False},
            "environment": {"FAKE_MEMORY_LIMIT": "64",
                            "TRIAL_STEP_SLEEP": "0.0"},
            "checkpoint_storage": {
                "type": "shared_fs",
                "host_path": str(tmp_path / "ckpts")},
            "resources": {"slots_per_trial": 1},
            "max_restarts": 0,
        }
        eid = runner.run(config, model_dir=FIXTURES)
        assert eid > 0
        best, sps = method.best()
        assert best == 64, (best, method.results, method.failed_sizes)
        assert sps > 0
        # the cliff hunt tried 128 and failed it
        assert 128 in method.failed_sizes
    finally:
        os.environ.pop("DET_MASTER", None)
