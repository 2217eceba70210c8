"""The harness end to end at the test-only tiny size on the CPU: every
cell's last line, the refusals, the manifest's own rules, that a new
configuration / traffic mix / metric / architecture needs only new files,
and that a broken timed path comes out as not correct."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import cells
from benchmarks.run import ROOT, run_cell

MANIFEST = cells.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _cli(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600)


# ------------------------------------------------------------ end to end


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end_at_the_tiny_size(workload):
    line, run = run_cell(workload, seed=2 ** 31 + 11, seconds=1.0,
                         trace=False, tiny=True)
    assert set(line) == LINE_KEYS and list(line)[-1] == "compared"
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"      # and says so
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in
            cells.metrics_for(MANIFEST, workload, "end_to_end")}
    assert set(line["metrics"]) == want and "setup_s" in want
    for name, metric in line["metrics"].items():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0, name
    for name, (value, limit) in line["compared"].items():
        assert limit is not None and value <= limit, name
    json.dumps(line)                                  # one JSON object


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_per_layer_metrics_and_no_cpu_roofline(workload):
    line, run = run_cell(workload, seed=5, seconds=1.0, trace=True,
                         tiny=True)
    listed = {m["name"] for m in
              cells.metrics_for(MANIFEST, workload, "per_layer")}
    assert line["metrics"] and set(line["metrics"]) <= listed
    # no device plane in a CPU trace: a reader with nothing to read
    # returns nothing, and a share of a peak is never made up
    assert not [n for n in line["metrics"]
                if n.endswith("_roofline") or "mfu" in n]
    assert "breakdown" not in line and "busy_s" not in line["device"]
    assert not os.path.exists(os.path.join(ROOT, ".bench_trace"))


def test_command_line_prints_the_result_last_and_refuses_without_a_chip(
        tmp_path):
    ok = _cli(["--workload", "serve-large-prompts", "--seed", "3",
               "--seconds", "1", "--trace", "0", "--tiny"])
    assert ok.returncode == 0, ok.stderr[-2000:]
    line = json.loads(ok.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS and line["correct"] is True
    # each number compared stands beside its limit at the end of stderr
    assert "compared token_gap:" in ok.stderr.splitlines()[-3]

    refused = _cli(["--workload", "serve-large-prompts", "--seed", "3",
                    "--seconds", "1", "--trace", "0"])
    assert refused.returncode == 3 and refused.stdout == ""
    assert "TPU" in refused.stderr

    # alone with BENCHMARK.json and its own directories there is no
    # program to measure: no result, and not exit code 0
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in MANIFEST["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    alone = _cli(["--workload", "serve-large-prompts", "--seed", "3",
                  "--seconds", "1", "--trace", "0", "--tiny"], cwd=tmp_path)
    assert alone.returncode != 0 and alone.stdout == ""


# -------------------------------------------------------------- manifest


def test_manifest_keeps_the_contracts_rules():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert m["paths"] == ["benchmarks", "tests/benchmark"]
    names = [x["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in m[group]]
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in m[group]}) == len(m[group])
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({x["name"] for x in metrics}) == len(metrics)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) \
        == len(m["workloads"])
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert any(w["config"] == c["name"] for w in m["workloads"])
        assert 1 <= len(c["why"]) <= 200 and c["source"].startswith("https://")
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in x["layer"] and 1 <= len(x["layer"]) <= 200
    for x in metrics:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    assert len(json.dumps(m)) < 64 * 1024


def test_every_cell_reports_what_it_must_and_moves_resolve():
    e2e_names = {x["name"] for x in MANIFEST["end_to_end"]}
    for workload in CELLS:
        e2e = {x["name"] for x in
               cells.metrics_for(MANIFEST, workload, "end_to_end")}
        layer = cells.metrics_for(MANIFEST, workload, "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layer, workload
        for metric in layer:
            # the metric it should move is one this cell reports
            assert metric["moves"] in e2e_names
            assert metric["moves"] in e2e, (workload, metric["name"])
    for metric in MANIFEST["per_layer"]:
        for workload in metric.get("workloads", []):
            assert workload in CELLS, (metric["name"], workload)
    roofline = [x for x in MANIFEST["per_layer"]
                if x["name"].endswith("_roofline")]
    for metric in roofline:     # beside each kernel's roofline, a step's mfu
        assert metric["unit"] == "%"
        assert any("mfu" in x["name"] and x["moves"] == metric["moves"]
                   for x in MANIFEST["per_layer"]), metric["name"]


def test_every_name_in_the_manifest_resolves_to_its_file():
    for c in MANIFEST["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"] == []
    for w in MANIFEST["workloads"]:
        cell = cells.resolve(ROOT, MANIFEST, w["name"])
        assert cell["traffic"]["kind"] in ("train_steps", "closed_loop")
        assert len(cell["traffic"]["why"]) > 20 and cell["traffic"]["who"]
        limits = {k: v for k, v in cell["limits"].items()
                  if k not in ("readings", "tiny")}
        assert limits and all(v is not None for v in limits.values()), \
            w["name"]
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert callable(cells.load_reader(ROOT, MANIFEST, metric["name"]))
    with pytest.raises(KeyError):
        cells.peak_for(ROOT, MANIFEST, "TPU v9 imaginary", tiny=False)
    assert cells.peak_for(ROOT, MANIFEST, "TPU v5 lite", False)[
        "bf16_flops_per_s"] == 197e12


TOY_ADAPTER = '''"""A fixture architecture: its configuration spells its sizes with keys
of its own, and it maps them onto the program's GPT-2."""
from benchmarks.models import Dims, gpt2

init_params, logits, loss_and_grads, work = (
    gpt2.init_params, gpt2.logits, gpt2.loss_and_grads, gpt2.work)


def dims(config):
    heads, width = config["num_attention_heads"], config["head_dim"]
    if (config["num_key_value_heads"], heads * width) != (
            heads, config["hidden_size"]):
        raise ValueError("the program's GPT-2 has no such attention")
    return Dims(vocab_size=config["vocab_size"],
                n_positions=config["max_position_embeddings"],
                d_model=config["hidden_size"],
                n_layer=config["num_hidden_layers"], n_head=heads,
                d_ff=config["intermediate_size"])


def serving(config, serve):
    d = dims(config)
    return {"model": "gpt2", "model_config": {
        "vocab_size": d["vocab_size"], "n_positions": d["n_positions"],
        "d_model": d["d_model"], "n_layer": d["n_layer"],
        "n_head": d["n_head"], "seq_len": int(serve["max_seq_len"]),
        "dtype": serve["dtype"]}}


def hparams(config):
    return {"model_size": "tiny"}     # the preset of exactly these sizes
'''


def _snapshot(directory):
    """Every file under `directory` with its size and time of change."""
    out = {}
    for base, _, files in os.walk(directory):
        if "__pycache__" in base:
            continue
        for name in files:
            stat = os.stat(os.path.join(base, name))
            out[os.path.join(base, name)] = (stat.st_size, stat.st_mtime_ns)
    return out


@pytest.fixture()
def extra_root(tmp_path):
    """A checkout whose manifest has one more directory, `extra/`, with a
    second GPT-2 configuration, a traffic mix, a metric reader, and a
    configuration of an architecture of its own (`model_type` toy) with
    its adapter — all of them files added, none edited."""
    for name in ("benchmarks", "tests", "examples"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic", "metrics", "limits", "models"):
        (extra / sub).mkdir(parents=True)
    with open(os.path.join(ROOT, "benchmarks/configs/gpt2-large.json")) as f:
        config = json.load(f)
    config["tiny"]["serve"]["max_batch_size"] = 2
    (extra / "configs/dummy.json").write_text(json.dumps(config))
    tiny = cells.merged(config, config["tiny"])
    (extra / "configs/toy.json").write_text(json.dumps({
        "model_type": "toy", "vocab_size": 512,
        "max_position_embeddings": 128, "hidden_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 256,
        "serve": tiny["serve"], "train": tiny["train"],
        "optimizer": tiny["optimizer"]}))
    (extra / "models/toy.py").write_text(TOY_ADAPTER)
    # a peak for the CPU's device kind, so that a share of a peak has
    # something to be a share of; `extra` comes first among the paths
    (extra / "peaks.json").write_text(json.dumps({"cpu": {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
        "source": "a test"}}))
    (extra / "traffic/dummy-mix.json").write_text(json.dumps({
        "kind": "closed_loop", "callers": 2, "temperature": 0.0,
        "shapes": [[5, 2], [9, 3], [7, 2]], "check_requests": 2,
        "why": "a dummy", "who": "a test"}))
    (extra / "metrics/dummy_requests.py").write_text(
        "def read(run):\n    return float(len(run['requests']))\n")
    serve_limits = {"token_gap": 0.05, "tokens_missing": 0,
                    "never_answered": 0}
    (extra / "limits/dummy-cell.json").write_text(json.dumps(serve_limits))
    (extra / "limits/toy-serve.json").write_text(json.dumps(serve_limits))
    shutil.copy(os.path.join(ROOT, "benchmarks/limits/train-medium-1chip.json"),
                extra / "limits/toy-train.json")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["paths"].insert(0, "extra")
    manifest["configs"] += [
        {"name": "dummy", "source": "https://example.org/dummy",
         "file": "extra/configs/dummy.json", "reduced": [], "why": "a dummy"},
        {"name": "toy", "source": "https://example.org/toy",
         "file": "extra/configs/toy.json", "reduced": [], "why": "a toy"}]
    manifest["workloads"] += [
        {"name": "dummy-cell", "config": "dummy", "traffic": "dummy-mix",
         "chips": 1, "why": "a dummy"},
        {"name": "toy-serve", "config": "toy", "traffic": "dummy-mix",
         "chips": 1, "why": "a toy, served"},
        {"name": "toy-train", "config": "toy", "traffic": "fit-steady",
         "chips": 1, "why": "a toy, trained"}]
    added = {"serve_tokens_per_s": ["dummy-cell", "toy-serve"],
             "serve_step_mfu": ["toy-serve"],
             "train_tokens_per_s_per_chip": ["toy-train"],
             "train_step_mfu": ["toy-train"]}
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        metric.get("workloads", []).extend(added.get(metric["name"], []))
    manifest["per_layer"].append({
        "name": "dummy_requests", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "serve front",
        "moves": "serve_tokens_per_s", "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_a_new_config_traffic_and_metric_need_only_new_files(extra_root):
    line, run = run_cell("dummy-cell", seed=1, seconds=0.5, trace=True,
                         tiny=True, root=extra_root)
    assert line["correct"] is True
    assert line["metrics"]["dummy_requests"] == {
        "value": float(len(run["requests"])), "unit": "requests"}
    assert run["cell"]["config"]["serve"]["max_batch_size"] == 2
    line, _ = run_cell("dummy-cell", seed=1, seconds=0.5, trace=False,
                       tiny=True, root=extra_root)
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}


@pytest.mark.parametrize("workload,mfu", [
    ("toy-serve", "serve_step_mfu"), ("toy-train", "train_step_mfu")])
def test_a_new_architecture_needs_only_new_files(extra_root, workload, mfu):
    before = _snapshot(os.path.join(ROOT, "benchmarks"))
    line, run = run_cell(workload, seed=2 ** 31 + 5, seconds=0.5, trace=True,
                         tiny=True, root=extra_root)
    assert run["cell"]["model"].__file__ == os.path.join(
        extra_root, "extra", "models", "toy.py")
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    for value, limit in line["compared"].values():
        assert limit is not None and value <= limit
    # with a peak for this device the whole step's share of it is there,
    # counted on what the toy's own adapter says a token multiplies
    assert line["metrics"][mfu]["value"] > 0
    assert not [n for n in line["metrics"] if n.endswith("_roofline")]
    assert _snapshot(os.path.join(ROOT, "benchmarks")) == before


def test_no_file_of_the_harness_names_an_architecture():
    """Outside `models/` (the adapters) and `configs/` (the published
    keys), nothing under `benchmarks/` knows a model."""
    named = re.compile(r"gpt2|GPT2|n_embd|n_head|model_size")
    base = os.path.join(ROOT, "benchmarks")
    found = []
    for path in _snapshot(base):
        relative = os.path.relpath(path, base)
        if relative.split(os.sep)[0] in ("models", "configs"):
            continue
        with open(path, errors="replace") as f:
            found += [f"{relative}:{n}" for n, text in enumerate(f, 1)
                      if named.search(text)]
    assert not found, found


# ----------------------------------------------------- a broken timed path


def _state_left_unchanged(monkeypatch):
    from determined_tpu.train.state import TrainState

    monkeypatch.setattr(
        TrainState, "apply_gradients",
        lambda self, grads, tx, new_extra=None:
            self.replace(step=self.step + 1))


def _trial_class():
    """The class the configurations' `train.trial` names, as the harness
    loads it."""
    return cells.load_module(
        os.path.join(ROOT, "examples/gpt2/model_def.py")).GPT2Trial


def _half_of_the_batch_left_out(monkeypatch):
    from determined_tpu.models import gpt2

    def loss(self, params, batch, rng):
        rows = batch["tokens"]
        return gpt2.loss_fn(params, {"tokens": rows[:rows.shape[0] // 2]},
                            self.cfg, self.sharding_rules())

    monkeypatch.setattr(_trial_class(), "loss", loss)


def _exchange_between_chips_left_out(monkeypatch):
    """Without the exchange a chip steps on the gradient of its own rows:
    with four chips, of the first quarter of the batch."""
    from determined_tpu.models import gpt2

    def loss(self, params, batch, rng):
        rows = batch["tokens"]
        return gpt2.loss_fn(params, {"tokens": rows[:rows.shape[0] // 4]},
                            self.cfg, self.sharding_rules())

    monkeypatch.setattr(_trial_class(), "loss", loss)


def _token_altered_where_it_is_produced(monkeypatch):
    from determined_tpu.serve.engine import ServingEngine

    decode = ServingEngine.decode
    monkeypatch.setattr(
        ServingEngine, "decode",
        lambda self, tokens, positions, temperatures:
            (decode(self, tokens, positions, temperatures) + 1)
            % self.cfg.vocab_size)


def _first_token_altered(monkeypatch):
    from determined_tpu.serve.engine import ServingEngine

    first = ServingEngine._sample_first
    monkeypatch.setattr(
        ServingEngine, "_sample_first",
        lambda self, logits, temperature:
            (first(self, logits, temperature) + 1) % self.cfg.vocab_size)


@pytest.mark.parametrize("workload,fault,fails", [
    ("train-medium-1chip", _state_left_unchanged,
     ("grad_gap", "grad_diff", "change_gap")),
    ("train-medium-1chip", _half_of_the_batch_left_out, ("grad_diff",)),
    ("train-large-fsdp4", _state_left_unchanged,
     ("grad_gap", "grad_diff", "change_gap")),
    ("train-large-fsdp4", _half_of_the_batch_left_out, ("grad_diff",)),
    ("train-large-fsdp4", _exchange_between_chips_left_out, ("grad_diff",)),
    ("serve-large-decode", _token_altered_where_it_is_produced,
     ("token_gap",)),
    ("serve-large-prompts", _first_token_altered, ("token_gap",)),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, workload, fault,
                                            fails):
    if workload not in CELLS:
        pytest.skip(f"{workload} is not a cell of this manifest")
    fault(monkeypatch)
    line, _ = run_cell(workload, seed=7, seconds=0.5, trace=False, tiny=True)
    assert line["correct"] is False
    for name in fails:
        value, limit = line["compared"][name]
        assert isinstance(value, str) or value > limit, (name, value, limit)
