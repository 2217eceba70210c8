"""Operations and bytes a latent (MLA) decode attention call needs, from its
shapes alone — what the absorbed form requires, not what an implementation
happens to do (`kernel_work.py` is the same for K/V attention).

Per live lane of context n and layer: the n cached tokens' latents
(`rank` numbers) and shared rotary keys (`rope` numbers) are read once —
every head reads the same ones —, the H absorbed queries read and the H
latent outputs written; scores cost 2 H n (rank + rope) operations and the
values 2 H n rank. What a padded row adds to the bytes (the pool's rows
are whole 128-lane groups) is the implementation's and is not in the
floor. At 20 heads that is ~38 operations a byte: the bytes bound it on a
chip whose ridge is 240, by a factor the MXU's 20 rows of 128 eat up.
"""

from __future__ import annotations

from typing import Dict


def mla_decode_work(live_lanes: int, context_tokens: int, heads: int,
                    rank: int, rope: int,
                    itemsize: int = 2) -> Dict[str, float]:
    """One decode call of one layer: `context_tokens` is the live lanes'
    contexts added up (the work is linear in each)."""
    row = rank + rope
    return {"flops": 2.0 * heads * context_tokens * (row + rank),
            "bytes": (context_tokens * row
                      + live_lanes * heads * (row + rank)) * float(itemsize)}
