"""python -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json, on the machine it is started on.
Set-up (weights on the device from the seed, compile or cache load, warming
the cell's own shapes) is timed from process start to the opening of the
window; the window lasts --seconds; then the timed path's output is held
against the plain reference. The last line of standard output is the
result. Without a TPU it exits 3 and prints nothing (`--tiny` is the
test-only size the CPU tests use; it is no measurement).
"""

import time

T0 = time.monotonic()

import argparse   # noqa: E402
import json       # noqa: E402
import math       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _jsonable(x):
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    return x


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             tiny: bool = False, root: str = ROOT, t0: float = None):
    """Drive one cell; returns (result line dict, run facts)."""
    from benchmarks import cells, check, loops

    t0 = T0 if t0 is None else t0
    if tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    manifest = cells.load_manifest(root)
    # the cell's adapter imports jax: the platform is chosen before it
    cell = cells.resolve(root, manifest, workload, tiny)
    import jax

    found = jax.devices()
    if not tiny and (found[0].platform != "tpu"
                     or len(found) < cell["chips"]):
        raise NoAccelerator(
            f"{workload} needs {cell['chips']} TPU chip(s); jax found "
            f"{len(found)} {found[0].platform} device(s)")
    devices = found[:cell["chips"]]
    cell["peak"] = cells.peak_for(root, manifest, found[0].device_kind, tiny)
    from determined_tpu.compile.runtime import enable_compilation_cache

    enable_compilation_cache()

    kind = cell["traffic"]["kind"]
    if kind == "train_steps":
        run = loops.train_steps(cell, seed, seconds, trace, root, t0, devices)
        ref = loops.train_standin(cell, seed, devices)
        numbers = check.compare_training(run["program"], ref)
        run["reference"] = ref
    elif kind == "closed_loop":
        run = loops.closed_loop(cell, seed, seconds, trace, root, t0, devices)
        sample = loops.sample_requests(run, cell, seed)
        run["sample"] = sample
        numbers = check.compare_serving(
            loops.serve_gaps(cell, seed, sample) if sample else [],
            run["never_answered"],
            sum(r["new"] for r in sample) if sample else 1)
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    if not tiny and run["attention_impl"] != "pallas":
        raise RuntimeError(
            f"the timed path resolved attention to {run['attention_impl']!r},"
            " not the Pallas kernel: this is not the path the cell measures")
    run.update(cell=cell, peak=cell["peak"], devices=devices)
    correct, compared = check.judge(numbers, cell["limits"])
    correct = correct and run["attempted"] > 0

    group, other = ("per_layer", "end_to_end") if trace \
        else ("end_to_end", "per_layer")
    metrics = cells.read_metrics(root, manifest, workload, group, run)
    print(json.dumps({"also": _jsonable(cells.read_metrics(
        root, manifest, workload, other, run))}), file=sys.stderr)
    device = {"platform": found[0].platform, "kind": found[0].device_kind,
              "count": len(found),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    line = {"correct": bool(correct), "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics,
            "device": device}
    reduced = run.get("trace")
    if trace and reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = reduced["breakdown"]
    line["compared"] = compared
    return _jsonable(line), run


class NoAccelerator(RuntimeError):
    pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="test-only: tiny model on the CPU, no measurement")
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        from benchmarks import cells

        seconds = cells.load_manifest(ROOT)["run_seconds"]
    try:
        line, _ = run_cell(args.workload, args.seed, seconds,
                           bool(args.trace), tiny=args.tiny)
    except NoAccelerator as e:
        print(f"benchmarks.run: {e}", file=sys.stderr)
        return 3
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
