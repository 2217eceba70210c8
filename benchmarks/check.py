"""What decides `correct`: the timed path's own output against the plain
reference, each number beside a limit of its own.

The limits live in `limits/<workload>.json`, with the readings they were
set from (PERF.md section 2 has the table). A number whose limit is
missing there is printed and not held.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List

import numpy as np

# Columns whose reference gradient is under this share of the median
# leaf's median column are nought to rounding there (a key's bias under
# softmax): Adam's normalisation moves them by round-off alone, on either
# side, so they are left out of what is compared as vectors.
NEGLIGIBLE_GRADIENT = 1e-3


def worst_leaf_gap(program: Dict[str, float],
                   ref: Dict[str, float]) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's — the gap of the norms, not the norm of a difference —
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    median = statistics.median(ref.values())
    worst = 0.0
    for leaf in ref:
        if leaf not in program:
            return math.inf
        scale = max(ref[leaf], median)
        worst = max(worst, abs(program[leaf] - ref[leaf]) / scale
                    if scale > 0 else 0.0)
    return worst


def moving_columns(ref: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Per leaf, which columns the reference's first gradient moves."""
    columns = ref["grad1_columns"]
    floor = NEGLIGIBLE_GRADIENT * statistics.median(
        float(np.median(c)) for c in columns.values())
    return {leaf: c >= floor for leaf, c in columns.items()}


def _masked_norms(sketches, keep):
    return {leaf: float(np.linalg.norm(np.where(keep[leaf], v, 0.0)))
            for leaf, v in sketches.items()}


def _difference(a, b):
    return {leaf: a[leaf] - b[leaf] for leaf in b}


def compare_training(program: Dict[str, Any],
                     ref: Dict[str, Any]) -> Dict[str, float]:
    """A training cell's numbers, from the program's first three steps
    and the reference's (or a stand-in's, in the program's place):

    loss_gap     widest relative gap of the three losses;
    grad_gap     worst leaf's gap of norms of the first gradient;
    grad_diff    worst leaf's norm of the difference of the two first
                 gradients, by their sketches — it sees a wrong direction
                 where a gap of norms sees only a wrong length;
    change_gap   worst leaf's gap of norms of the parameters' change from
                 after step 1 to after step 3, by their sketches.
    The sketched numbers leave out the columns the reference's gradient
    does not move; each is measured against the reference's leaf or the
    median leaf, whichever is larger."""
    wanted = ("grad1", "grad1_sketch", "params1_sketch", "params3_sketch")
    if len(program.get("losses", [])) < 3 \
            or any(k not in program for k in wanted):
        return dict.fromkeys(
            ("loss_gap", "grad_gap", "grad_diff", "change_gap"), math.inf)
    keep = moving_columns(ref)
    ref_grad = _masked_norms(ref["grad1_sketch"], keep)
    diff = _masked_norms(_difference(program["grad1_sketch"],
                                     ref["grad1_sketch"]), keep)
    median = statistics.median(ref_grad.values())
    change = [_masked_norms(_difference(side["params3_sketch"],
                                        side["params1_sketch"]), keep)
              for side in (program, ref)]
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in
                        zip(program["losses"], ref["losses"])),
        "grad_gap": worst_leaf_gap(program["grad1"], ref["grad1"]),
        "grad_diff": max(diff[leaf] / max(ref_grad[leaf], median)
                         for leaf in ref_grad),
        "change_gap": worst_leaf_gap(*change),
    }


def compare_serving(gaps: List[float], never_answered: int,
                    expected_tokens: int) -> Dict[str, float]:
    """A served cell's numbers: the widest gap by which a served token's
    reference logit lies below the reference's best, over the sample; how
    many sampled tokens were missing; and how many requests never came."""
    finite = [g for g in gaps if math.isfinite(g)]
    return {"token_gap": max(finite) if len(finite) == len(gaps) and gaps
            else math.inf,
            "tokens_missing": float(max(0, expected_tokens - len(gaps))),
            "never_answered": float(never_answered)}


def judge(numbers: Dict[str, float], limits: Dict[str, Any]):
    """→ (correct, {name: [value, limit]}). A number with no limit in the
    cell's file is shown beside null and holds nothing."""
    compared, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        compared[name] = [value, limit]
        if limit is not None and not (math.isfinite(value)
                                      and value <= float(limit)):
            ok = False
    return ok, compared
