"""Finding a cell's files by the names in `BENCHMARK.json`.

The harness is driven by data: a configuration, its architecture's adapter
(`models/<model_type>.py`; the contract is `models/__init__.py`), a traffic
mix, a metric reader and a cell's limits are each a file of their own under
one of the manifest's `paths`, found by name. A later PR adds files and
entries and edits nothing here — also for a model of another architecture.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional


def load_manifest(root: str) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(root: str, manifest: Dict[str, Any], relative: str) -> str:
    """`<root>/<path>/<relative>` in the first of the manifest's `paths`
    that has it."""
    for path in manifest["paths"]:
        candidate = os.path.join(root, path, relative)
        if os.path.exists(candidate):
            return candidate
    raise FileNotFoundError(
        f"{relative} under none of {manifest['paths']} (in {root})")


def merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_json(path: str, tiny: bool) -> Dict[str, Any]:
    with open(path) as f:
        data = json.load(f)
    return merged(data, data.get("tiny", {})) if tiny else data


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """A file of the benchmark's or the program's, loaded by path — once a
    process, so that what it jits compiles once."""
    name = "bench_" + os.path.basename(path)[:-3].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_model(root: str, manifest: Dict[str, Any], config: Dict[str, Any]):
    """The adapter of the configuration's architecture."""
    return load_module(find(root, manifest,
                            f"models/{config['model_type']}.py"))


def resolve(root: str, manifest: Dict[str, Any], workload: str,
            tiny: bool = False) -> Dict[str, Any]:
    """The cell named `workload`: its entry, its configuration and traffic
    as run (the test-only tiny overrides applied when asked for), its
    architecture's adapter, and its limits."""
    entry = next((w for w in manifest["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in manifest['workloads']]}")
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    spec = load_json(find(root, manifest,
                          f"traffic/{entry['traffic']}.json"), tiny)
    try:
        limits = load_json(find(root, manifest,
                                f"limits/{workload}.json"), tiny)
    except FileNotFoundError:
        limits = {}
    config = load_json(os.path.join(root, config_entry["file"]), tiny)
    return {"name": workload, "chips": int(entry["chips"]), "tiny": tiny,
            "config": config, "model": load_model(root, manifest, config),
            "traffic": spec, "limits": limits, "peak": None}


def peak_for(root: str, manifest: Dict[str, Any], device_kind: str,
             tiny: bool) -> Optional[Dict[str, float]]:
    """The chip's published peaks. A device that is not in the table is an
    error, not a default — except at the test-only tiny size, where no
    share of a peak is reported at all."""
    with open(find(root, manifest, "peaks.json")) as f:
        table = json.load(f)
    if device_kind in table:
        return table[device_kind]
    if tiny:
        return None
    raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                   f"({sorted(table)}): add it with its source")


def metrics_for(manifest: Dict[str, Any], workload: str,
                group: str) -> List[Dict[str, Any]]:
    """The `end_to_end` or `per_layer` metrics this cell reports. A metric
    without a `workloads` key belongs to every cell (a per-layer one: to
    every cell that reports the metric it moves)."""
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if group == "end_to_end":
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def load_reader(root: str, manifest: Dict[str, Any],
                name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """`read(run)` of `metrics/<name>.py`, loaded by path."""
    return load_module(find(root, manifest, f"metrics/{name}.py")).read


def read_metrics(root: str, manifest: Dict[str, Any], workload: str,
                 group: str, run: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for metric in metrics_for(manifest, workload, group):
        value = load_reader(root, manifest, metric["name"])(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out
