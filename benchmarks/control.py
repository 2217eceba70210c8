"""python -m benchmarks.control --workload <name> --seeds a,b,c [--controls N]

The readings a cell's limits are set from, in one process on the chip:
for every seed the program's numbers against the plain reference (their
largest is a limit's lower reading), and for the first N seeds the
control's — the reference in the program's place, a precision lower — and
each planted fault's (their smallest is the upper reading). Training
needs no measured window; a served cell gets one long enough to finish
its longest requests. One JSON line per seed, also appended to
chiprun_out/control_<workload>.jsonl. The benchmark's own runs never come
here.
"""

import argparse
import json
import os
import sys
import time


def readings(workload: str, seed: int, seconds: float, with_control: bool,
             tiny: bool):
    from benchmarks import check, loops
    from benchmarks.run import run_cell

    line, run = run_cell(workload, seed, seconds, False, tiny=tiny,
                         t0=time.monotonic())
    out = {"workload": workload, "seed": seed, "correct": line["correct"],
           "program": {k: v[0] for k, v in line["compared"].items()},
           "metrics": {k: v["value"] for k, v in line["metrics"].items()}}
    cell = run["cell"]
    if not with_control:
        return out
    if run["kind"] == "train_steps":
        stand_ins = [("control_int8", {"quant": "int8"}),
                     ("control_fp8", {"quant": "fp8"}),
                     ("fault_half_batch", {"keep_rows": 0.5})]
        if run["chips"] > 1:
            stand_ins.append(("fault_no_exchange",
                              {"keep_rows": 1.0 / run["chips"]}))
        for name, kwargs in stand_ins:
            standin = loops.train_standin(cell, seed, run["devices"], **kwargs)
            out[name] = check.compare_training(standin, run["reference"])
    else:
        for quant in ("int8", "fp8"):
            gaps = loops.serve_gaps(cell, seed, run["sample"], control=quant)
            out["control_" + quant] = {"token_gap": max(gaps),
                                       "tokens": len(gaps)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    os.makedirs("chiprun_out", exist_ok=True)
    path = os.path.join("chiprun_out", f"control_{args.workload}.jsonl")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = readings(args.workload, seed, args.seconds,
                       i < args.controls, args.tiny)
        text = json.dumps(out)
        print(text, flush=True)
        with open(path, "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
