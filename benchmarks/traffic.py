"""One general traffic generator, driven by the data files in `traffic/`.

Every mix is a stratified seeded shuffle. The file writes out a fixed
grid of request shapes (quantiles of a log-uniform over the stated
ranges), and `--seed` only permutes that grid and draws the token ids:
every seed offers the same multiset of shapes in another order, so the
spread from run to run is the system's and not the sampler's.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def log_uniform_grid(lo: int, hi: int, n: int) -> List[int]:
    """The n mid-quantiles of a log-uniform distribution over [lo, hi]."""
    return [int(round(lo * (hi / lo) ** ((i + 0.5) / n))) for i in range(n)]


def shape_at(spec: Dict[str, Any], seed: int, k: int) -> Tuple[int, int]:
    """The k-th (prompt length, new tokens) of the endless stream: the
    grid shuffled by the seed, then shuffled again for every further
    pass over it."""
    shapes = spec["shapes"]
    cycle, i = divmod(k, len(shapes))
    order = np.random.default_rng([seed, 11, cycle]).permutation(len(shapes))
    prompt, new = shapes[int(order[i])]
    return int(prompt), int(new)


def prompt_at(spec: Dict[str, Any], seed: int, k: int, vocab: int):
    """Token ids of the k-th request: (prompt ids, new tokens)."""
    prompt, new = shape_at(spec, seed, k)
    ids = np.random.default_rng([seed, 13, k]).integers(
        0, vocab, prompt, dtype=np.int32)
    return ids, new


def caller_index(caller: int, turn: int, callers: int) -> int:
    """Caller c's turn-th request is element c + turn*callers of the
    stream: the deal is fixed by the seed, not by which thread ran first."""
    return caller + turn * callers


def train_rows(seed: int, step: int, batch: int, seq_len: int,
               vocab: int) -> np.ndarray:
    """The token rows [batch, seq_len + 1] of optimizer step `step`
    (0-based): uniform ids, every row different."""
    return np.random.default_rng([seed, 17, step]).integers(
        0, vocab, (batch, seq_len + 1), dtype=np.int32)
