"""Preflight analyzer tests — every shipped DTL rule, positive + negative.

Engine coverage:
  abstract (DTL001-DTL005): inline trial classes driven through
      analysis.abstract.analyze_trial (no AST involvement).
  AST lint (DTL101-DTL104): source strings through analysis.lint_source.
  config   (DTL201-DTL202): dicts through analysis.check_config (the
      native master mirror is covered by native/tests/test_native.cc).
  end-to-end: the tests/fixtures/preflight/{bad,clean} pair through the
      real `det preflight` CLI — the acceptance contract: bad reports
      exactly {DTL001, DTL002, DTL101}, clean reports nothing.
"""

import json
import os

import jax
import numpy as np

from determined_tpu.analysis import RULES, check_config
from determined_tpu.analysis.abstract import analyze_trial
from determined_tpu.analysis.astlint import lint_source
from determined_tpu.train.trial import JaxTrial, TrialContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "preflight")


def codes(diags):
    return sorted({d.code for d in diags if not d.suppressed})


# ---------------------------------------------------------------------------
# abstract engine (DTL001-DTL005)
# ---------------------------------------------------------------------------


class SmallTrial(JaxTrial):
    """Clean baseline: small params, divisible batch, donation on."""

    def __init__(self, context, batch=32):
        super().__init__(context)
        self._batch = batch

    def init_params(self, rng):
        return {"w": jax.random.normal(rng, (16, 8)) * 0.1}

    def loss(self, params, batch, rng):
        logits = batch["x"] @ params["w"]
        return jax.numpy.mean((logits - batch["y"]) ** 2)

    def build_training_data(self):
        while True:
            yield {
                "x": np.zeros((self._batch, 16), np.float32),
                "y": np.zeros((self._batch, 8), np.float32),
            }


class NoDonateTrial(SmallTrial):
    donate_state = False


class BigReplicatedTrial(SmallTrial):
    """One 32 MiB leaf, no logical axes -> replicated on every chip."""

    def init_params(self, rng):
        return {"emb": jax.random.normal(rng, (32768, 256))}

    def loss(self, params, batch, rng):
        return jax.numpy.mean(params["emb"]) * jax.numpy.mean(batch["x"])


class BigShardedTrial(BigReplicatedTrial):
    """Same leaf, annotated; under mesh fsdp=4 it shards -> no DTL002."""

    def param_logical_axes(self):
        return {"emb": ("embed", None)}  # embed -> fsdp

    def mesh_config(self):
        from determined_tpu.parallel.mesh import MeshConfig

        return MeshConfig(data=2, fsdp=4)


class BrokenLossTrial(SmallTrial):
    def loss(self, params, batch, rng):
        return batch["x"] @ params["w"] @ batch["x"]  # shape error


def _ctx(**hp):
    return TrialContext(hparams=hp, n_devices=8)


class TestAbstractEngine:
    def test_clean_trial_no_diagnostics(self):
        diags, hbm, _ = analyze_trial(SmallTrial(_ctx()), 8)
        assert codes(diags) == []
        assert hbm["total_bytes"] > 0
        assert hbm["donated"] is True

    def test_dtl001_not_donated(self):
        diags, hbm, _ = analyze_trial(NoDonateTrial(_ctx()), 8)
        assert codes(diags) == ["DTL001"]
        assert hbm["donation_extra_bytes"] == (
            hbm["params_bytes"] + hbm["opt_state_bytes"])

    def test_dtl002_replicated_large_leaf(self):
        diags, _, _ = analyze_trial(BigReplicatedTrial(_ctx()), 8)
        assert codes(diags) == ["DTL002"]
        assert "emb" in diags[0].message

    def test_dtl002_negative_when_sharded(self):
        diags, hbm, _ = analyze_trial(BigShardedTrial(_ctx()), 8)
        assert codes(diags) == []
        # fsdp=4 shards the 32 MiB leaf -> 8 MiB per device.
        assert hbm["params_bytes"] == 32 * 2**20 // 4

    def test_dtl002_negative_single_device(self):
        diags, _, _ = analyze_trial(BigReplicatedTrial(
            TrialContext(hparams={}, n_devices=1)), 1)
        assert codes(diags) == []

    def test_dtl003_batch_not_divisible(self):
        diags, _, _ = analyze_trial(SmallTrial(_ctx(), batch=30), 8)
        assert codes(diags) == ["DTL003"]
        assert diags[0].level == "error"

    def test_dtl003_negative_divisible(self):
        diags, _, _ = analyze_trial(SmallTrial(_ctx(), batch=32), 8)
        assert codes(diags) == []

    def test_dtl004_hbm_over_budget(self):
        diags, _, _ = analyze_trial(
            BigReplicatedTrial(_ctx()), 8, hbm_budget_bytes=16 * 2**20)
        assert "DTL004" in codes(diags)

    def test_dtl004_negative_under_budget(self):
        diags, _, _ = analyze_trial(
            SmallTrial(_ctx()), 8, hbm_budget_bytes=2**30)
        assert "DTL004" not in codes(diags)

    def test_dtl005_trace_failure(self):
        diags, _, _ = analyze_trial(BrokenLossTrial(_ctx()), 8)
        assert codes(diags) == ["DTL005"]

    def test_dtl005_excused_by_ast_finding(self):
        diags, _, notes = analyze_trial(
            BrokenLossTrial(_ctx()), 8, trace_failure_excused=True)
        assert codes(diags) == []
        assert any("does not trace" in n for n in notes)

    def test_hbm_footprint_scales_with_mesh(self):
        _, hbm8, _ = analyze_trial(BigShardedTrial(_ctx()), 8)
        _, hbm1, _ = analyze_trial(
            BigReplicatedTrial(TrialContext(hparams={}, n_devices=1)), 1)
        assert hbm8["params_bytes"] * 4 == hbm1["params_bytes"]


# ---------------------------------------------------------------------------
# AST lint engine (DTL101-DTL104)
# ---------------------------------------------------------------------------


def _lint(body, cls_extra=""):
    src = (
        "import time, random\n"
        "import numpy as np\n"
        "import jax\n"
        "from determined_tpu.train import JaxTrial\n"
        "class T(JaxTrial):\n"
        "    def init_params(self, rng):\n"
        "        return {}\n"
        f"{cls_extra}"
        "    def loss(self, params, batch, rng):\n"
        f"{body}"
        "        return batch\n"
    )
    return lint_source(src, "t.py")


class TestAstEngine:
    def test_dtl101_item(self):
        assert codes(_lint("        x = batch.sum().item()\n")) == ["DTL101"]

    def test_dtl101_device_get(self):
        assert codes(_lint("        x = jax.device_get(batch)\n")) == [
            "DTL101"]

    def test_dtl101_block_until_ready(self):
        assert codes(_lint("        batch.block_until_ready()\n")) == [
            "DTL101"]

    def test_dtl101_np_asarray_on_value(self):
        assert codes(_lint("        x = np.asarray(batch)\n")) == ["DTL101"]

    def test_dtl101_negative_np_constant(self):
        # np.asarray of a literal is a trace-time constant: fine.
        assert codes(_lint("        x = np.asarray([1.0, 2.0])\n")) == []

    def test_dtl101_negative_outside_traced(self):
        src = (
            "import jax\n"
            "def report(metrics):\n"
            "    return {k: v.item() for k, v in metrics.items()}\n"
        )
        assert codes(lint_source(src, "t.py")) == []

    def test_dtl102_python_rng(self):
        assert codes(_lint("        x = random.random()\n")) == ["DTL102"]
        assert codes(_lint("        x = np.random.normal()\n")) == ["DTL102"]

    def test_dtl102_negative_jax_rng(self):
        assert codes(_lint("        x = jax.random.normal(rng, (2,))\n")) == []

    def test_dtl103_wall_clock(self):
        assert codes(_lint("        t = time.time()\n")) == ["DTL103"]

    def test_dtl103_negative_outside_traced(self):
        src = "import time\ndef tick():\n    return time.time()\n"
        assert codes(lint_source(src, "t.py")) == []

    def test_dtl104_shape_branch(self):
        out = _lint("        if batch.shape[0] > 2:\n            pass\n")
        assert codes(out) == ["DTL104"]

    def test_dtl104_while_len(self):
        out = _lint("        while len(batch) > 2:\n            pass\n")
        assert codes(out) == ["DTL104"]

    def test_dtl104_negative_plain_reshape(self):
        # Using .shape outside a branch is normal traced code.
        assert codes(_lint(
            "        x = batch.reshape(batch.shape[0], -1)\n")) == []

    def test_noqa_line_suppression(self):
        out = _lint("        x = batch.sum().item()  # det: noqa[DTL101]\n")
        assert codes(out) == []
        assert [d.code for d in out if d.suppressed] == ["DTL101"]

    def test_noqa_bare_suppresses_all(self):
        out = _lint("        x = batch.sum().item()  # det: noqa\n")
        assert codes(out) == []

    def test_noqa_wrong_code_does_not_suppress(self):
        out = _lint("        x = batch.sum().item()  # det: noqa[DTL104]\n")
        assert codes(out) == ["DTL101"]

    def test_jit_factory_idiom_is_traced(self):
        src = (
            "import jax, time\n"
            "def make_step(loss):\n"
            "    def step(state, batch):\n"
            "        t = time.time()\n"
            "        return state\n"
            "    return jax.jit(step, donate_argnums=(0,))\n"
        )
        assert codes(lint_source(src, "t.py")) == ["DTL103"]

    def test_module_loss_fn_closure(self):
        src = (
            "import time\n"
            "def _helper(x):\n"
            "    return time.time()\n"
            "def loss_fn(params, batch):\n"
            "    return _helper(batch)\n"
        )
        out = lint_source(src, "t.py")
        assert codes(out) == ["DTL103"]

    def test_torch_trials_not_traced(self):
        src = (
            "class MyTrial(PyTorchTrial):\n"
            "    def evaluate(self, params, batch):\n"
            "        return {'loss': batch.sum().item()}\n"
        )
        assert codes(lint_source(src, "t.py")) == []


def _loader_src(body):
    return (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from determined_tpu.train import JaxTrial\n"
        "class T(JaxTrial):\n"
        "    def init_params(self, rng):\n"
        "        return {}\n"
        "    def loss(self, params, batch, rng):\n"
        "        return batch\n"
        "    def build_training_data(self):\n"
        f"{body}"
    )


class TestDataLoaderRule:
    """DTL105 — device transfer inside build_*_data double-transfers with
    the async input pipeline (determined_tpu/data)."""

    def test_dtl105_device_put_in_loader(self):
        out = lint_source(_loader_src(
            "        while True:\n"
            "            yield jax.device_put({'x': np.zeros(4)})\n"), "t.py")
        assert codes(out) == ["DTL105"]
        assert "device_put" in out[0].message

    def test_dtl105_jnp_yield(self):
        assert codes(lint_source(_loader_src(
            "        for _ in range(4):\n"
            "            yield jnp.zeros((8, 4))\n"), "t.py")) == ["DTL105"]

    def test_dtl105_validation_loader_return(self):
        src = (
            "import jax.numpy as jnp\n"
            "from determined_tpu.train import JaxTrial\n"
            "class T(JaxTrial):\n"
            "    def build_validation_data(self):\n"
            "        return jnp.zeros((2, 4))\n"
        )
        assert codes(lint_source(src, "t.py")) == ["DTL105"]

    def test_dtl105_negative_numpy_loader(self):
        assert codes(lint_source(_loader_src(
            "        while True:\n"
            "            yield {'x': np.zeros((8, 4), np.float32)}\n"),
            "t.py")) == []

    def test_dtl105_negative_device_put_outside_loader(self):
        src = (
            "import jax\n"
            "def stage(batch):\n"
            "    return jax.device_put(batch)\n"
        )
        assert codes(lint_source(src, "t.py")) == []

    def test_dtl105_negative_torch_loader(self):
        src = (
            "import jax\n"
            "class MyTrial(PyTorchTrial):\n"
            "    def build_training_data(self):\n"
            "        yield jax.device_put({'x': 1})\n"
        )
        assert codes(lint_source(src, "t.py")) == []

    def test_dtl105_noqa_suppression(self):
        out = lint_source(_loader_src(
            "        while True:\n"
            "            yield jax.device_put({'x': np.zeros(4)})"
            "  # det: noqa[DTL105]\n"), "t.py")
        assert codes(out) == []
        assert [d.code for d in out if d.suppressed] == ["DTL105"]

    def test_dtl105_level_is_warning(self):
        out = lint_source(_loader_src(
            "        yield jnp.zeros((8, 4))\n"), "t.py")
        assert out[0].level == "warning"


class TestThreadStopRule:
    """DTL106 — `_stop` shadowing on threading.Thread subclasses crashes
    join() at thread exit (Thread._stop() is an internal method)."""

    def test_dtl106_instance_event(self):
        out = lint_source(
            "import threading\n"
            "class Worker(threading.Thread):\n"
            "    def __init__(self):\n"
            "        super().__init__()\n"
            "        self._stop = threading.Event()\n", "t.py")
        assert codes(out) == ["DTL106"]
        assert "_stop_evt" in out[0].message
        assert out[0].level == "error"

    def test_dtl106_class_attribute(self):
        assert codes(lint_source(
            "from threading import Thread\n"
            "class Worker(Thread):\n"
            "    _stop = None\n", "t.py")) == ["DTL106"]

    def test_dtl106_method(self):
        assert codes(lint_source(
            "import threading\n"
            "class Worker(threading.Thread):\n"
            "    def _stop(self):\n"
            "        pass\n", "t.py")) == ["DTL106"]

    def test_dtl106_subclass_of_subclass(self):
        assert codes(lint_source(
            "import threading\n"
            "class Base(threading.Thread):\n"
            "    pass\n"
            "class Worker(Base):\n"
            "    def run(self):\n"
            "        self._stop = threading.Event()\n", "t.py")) == ["DTL106"]

    def test_dtl106_negative_stop_evt(self):
        assert codes(lint_source(
            "import threading\n"
            "class Worker(threading.Thread):\n"
            "    def __init__(self):\n"
            "        super().__init__()\n"
            "        self._stop_evt = threading.Event()\n", "t.py")) == []

    def test_dtl106_negative_not_a_thread(self):
        assert codes(lint_source(
            "import threading\n"
            "class Manager:\n"
            "    def __init__(self):\n"
            "        self._stop = threading.Event()\n", "t.py")) == []

    def test_dtl106_noqa_suppression(self):
        out = lint_source(
            "import threading\n"
            "class Worker(threading.Thread):\n"
            "    def __init__(self):\n"
            "        self._stop = threading.Event()  # det: noqa[DTL106]\n",
            "t.py")
        assert codes(out) == []
        assert [d.code for d in out if d.suppressed] == ["DTL106"]

    def test_dtl106_tree_is_clean(self):
        """No Thread subclass in the tree shadows `_stop` (the long-running
        watchers use `_stop_evt`)."""
        from determined_tpu.analysis.astlint import lint_paths

        diags = lint_paths([os.path.join(REPO, "determined_tpu")])
        assert [d for d in diags if d.code == "DTL106"] == []


class TestAttnRule:
    """DTL107 — hand-rolled attention softmax inside traced trial code
    bypasses `optimizations.attention_impl` kernel selection."""

    def test_dtl107_softmax_in_loss(self):
        out = _lint("        p = jax.nn.softmax(batch)\n")
        assert codes(out) == ["DTL107"]
        assert "attention_impl" in out[0].message
        assert out[0].level == "warning"

    def test_dtl107_helper_closure(self):
        # A same-class helper called from loss() is linted as trial code.
        src = (
            "import jax\n"
            "from determined_tpu.train import JaxTrial\n"
            "class T(JaxTrial):\n"
            "    def _attn(self, q, k, v):\n"
            "        return jax.nn.softmax(q @ k.T) @ v\n"
            "    def loss(self, params, batch, rng):\n"
            "        return self._attn(batch, batch, batch)\n"
        )
        assert codes(lint_source(src, "t.py")) == ["DTL107"]

    def test_dtl107_negative_model_library_fn(self):
        # Module-level apply*/loss_fn* roots are the model *library* idiom
        # (ops/flash_attention.py's reference path) — not trial code.
        src = (
            "import jax\n"
            "def apply_attention(q, k, v):\n"
            "    return jax.nn.softmax(q @ k.T) @ v\n"
        )
        assert codes(lint_source(src, "t.py")) == []

    def test_dtl107_negative_log_softmax(self):
        # log_softmax is the cross-entropy idiom, not attention.
        assert codes(_lint(
            "        p = jax.nn.log_softmax(batch)\n")) == []

    def test_dtl107_negative_torch_trial(self):
        src = (
            "import torch\n"
            "class MyTrial(PyTorchTrial):\n"
            "    def loss(self, params, batch, rng):\n"
            "        return torch.nn.softmax(batch)\n"
        )
        assert codes(lint_source(src, "t.py")) == []

    def test_dtl107_noqa_suppression(self):
        out = _lint(
            "        p = jax.nn.softmax(batch)  # det: noqa[DTL107]\n")
        assert codes(out) == []
        assert [d.code for d in out if d.suppressed] == ["DTL107"]

    def test_dtl107_tree_is_clean(self):
        """The platform's own trials (examples/) route attention through
        the model library; none hand-roll softmax in traced methods."""
        from determined_tpu.analysis.astlint import lint_paths

        diags = lint_paths([os.path.join(REPO, "determined_tpu"),
                            os.path.join(REPO, "examples")])
        assert [d for d in diags if d.code == "DTL107"] == []


# ---------------------------------------------------------------------------
# config rules (DTL201-DTL202) — python side; native mirror in
# native/tests/test_native.cc
# ---------------------------------------------------------------------------


def _config(**over):
    c = {
        "entrypoint": "python3 train.py",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 64}},
        "resources": {"slots_per_trial": 8},
        "hyperparameters": {},
    }
    c.update(over)
    return c


class TestConfigRules:
    def test_dtl201(self):
        c = _config(hyperparameters={"global_batch_size": 30})
        assert codes(check_config(c)) == ["DTL201"]
        c["hyperparameters"]["global_batch_size"] = 32
        assert check_config(c) == []

    def test_dtl202(self):
        c = _config(searcher={"name": "async_halving", "metric": "loss",
                              "max_length": {"batches": 100},
                              "num_rungs": 5, "divisor": 4})
        assert codes(check_config(c)) == ["DTL202"]
        c["searcher"]["max_length"] = {"batches": 256}
        assert check_config(c) == []

    def test_dtl203_explicit_zero_with_restarts(self):
        c = _config(min_checkpoint_period={"batches": 0}, max_restarts=3)
        assert codes(check_config(c)) == ["DTL203"]
        # default max_restarts (5) counts as "restarts configured"
        c = _config(min_checkpoint_period={"batches": 0})
        assert codes(check_config(c)) == ["DTL203"]

    def test_dtl206_block_size_must_divide_max_seq(self):
        c = {"serving": {"checkpoint": "latest", "kv_block_size": 24,
                         "max_seq_len": 256}}
        diags = check_config(c)
        assert codes(diags) == ["DTL206"]
        assert diags[0].level == "error"
        c["serving"]["kv_block_size"] = 16
        assert check_config(c) == []

    def test_dtl206_pool_must_hold_one_sequence(self):
        c = {"serving": {"checkpoint": "latest", "kv_block_size": 16,
                         "max_seq_len": 256, "kv_num_blocks": 8}}  # 128 tok
        assert codes(check_config(c)) == ["DTL206"]
        c["serving"]["kv_num_blocks"] = 16  # exactly one sequence
        assert check_config(c) == []
        # Derived pool (no explicit kv_num_blocks) can never underrun.
        del c["serving"]["kv_num_blocks"]
        assert check_config(c) == []

    def test_dtl206_negative(self):
        # Defaults (16 | 256) are clean.
        assert check_config({"serving": {"checkpoint": "latest"}}) == []
        # Non-serving configs never fire it.
        assert "DTL206" not in codes(check_config(_config()))

    def test_dtl206_suppressible(self):
        from determined_tpu.analysis import filter_suppressed

        c = {"serving": {"checkpoint": "latest", "kv_block_size": 24,
                         "max_seq_len": 256}}
        diags = filter_suppressed(check_config(c), ["DTL206"])
        assert [d.code for d in diags] == ["DTL206"]
        assert diags[0].suppressed

    def test_dtl203_negative(self):
        # absent key: the default is also 0 batches, but only an EXPLICIT
        # zero is flagged (otherwise every config would warn)
        assert check_config(_config(max_restarts=3)) == []
        # periodic checkpoints configured: nothing to flag
        c = _config(min_checkpoint_period={"batches": 50}, max_restarts=3)
        assert check_config(c) == []
        # restarts off: nothing to restart, rule moot
        c = _config(min_checkpoint_period={"batches": 0}, max_restarts=0)
        assert check_config(c) == []


# ---------------------------------------------------------------------------
# end-to-end: fixtures through preflight() and the det CLI
# ---------------------------------------------------------------------------


def _load_yaml(path):
    import yaml

    with open(path) as f:
        return yaml.safe_load(f)


class TestEndToEnd:
    def test_bad_fixture_exact_codes(self):
        from determined_tpu.analysis import preflight

        report = preflight(
            _load_yaml(os.path.join(FIXTURES, "bad", "config.yaml")),
            context_dir=os.path.join(FIXTURES, "bad"))
        # The acceptance contract: exactly these three, nothing else.
        assert report.codes() == ["DTL001", "DTL002", "DTL101"]
        assert report.hbm["donation_extra_bytes"] > 0

    def test_clean_fixture_reports_none(self):
        from determined_tpu.analysis import preflight

        report = preflight(
            _load_yaml(os.path.join(FIXTURES, "clean", "config.yaml")),
            context_dir=os.path.join(FIXTURES, "clean"))
        assert report.codes() == []
        assert report.errors == []

    def test_config_suppression_via_preflight_block(self):
        from determined_tpu.analysis import preflight

        cfg = _load_yaml(os.path.join(FIXTURES, "bad", "config.yaml"))
        cfg["preflight"] = {"suppress": ["DTL001", "DTL002", "DTL101"]}
        report = preflight(cfg, context_dir=os.path.join(FIXTURES, "bad"))
        assert report.codes() == []
        assert sum(1 for d in report.diagnostics if d.suppressed) == 3

    def test_cli_bad_fixture(self, capsys):
        from determined_tpu.cli import main

        rc = main(["preflight",
                   os.path.join(FIXTURES, "bad", "config.yaml"),
                   os.path.join(FIXTURES, "bad"), "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1  # error-level findings -> nonzero exit
        assert out["summary"]["codes"] == ["DTL001", "DTL002", "DTL101"]

    def test_cli_clean_fixture(self, capsys):
        from determined_tpu.cli import main

        rc = main(["preflight",
                   os.path.join(FIXTURES, "clean", "config.yaml"),
                   os.path.join(FIXTURES, "clean"), "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["summary"]["codes"] == []

    def test_every_shipped_rule_is_documented(self):
        doc = open(os.path.join(REPO, "docs", "preflight.md")).read()
        for code in RULES:
            assert code in doc, f"{code} missing from docs/preflight.md"

    def test_tree_is_lint_clean(self):
        """The dogfood gate: the platform's own models and examples pass
        the platform's own lint (suppressions must be annotated)."""
        from determined_tpu.analysis.astlint import lint_paths

        diags = lint_paths([os.path.join(REPO, "determined_tpu"),
                            os.path.join(REPO, "examples")])
        active = [d for d in diags if not d.suppressed]
        assert active == [], [f"{d.location()}: {d.code}" for d in active]
