"""Operations and bytes a state-space decode step's state update needs,
from its shapes alone — what the recurrence requires, not what an
implementation happens to do (`kernel_work.py` is the same for attention).

Per live lane and layer, with `S` the lane's state of heads x head_dim x
state numbers: `S <- exp(dt A) S + dt x (x) B`, `y = S C`. The state is
read once and written once; `x`, `dt`, `B`, `C` are read and `y` written;
an element of the state costs 6 operations (the decay's multiply, the
outer product's two, its add, and the multiply and add of `S C`). An idle
lane needs nothing. Bandwidth-bound by far: 6 operations to 8 bytes.
"""

from __future__ import annotations

from typing import Dict


def ssm_decode_work(live_lanes: int, heads: int, head_dim: int, state: int,
                    groups: int, state_itemsize: int = 4,
                    itemsize: int = 2) -> Dict[str, float]:
    """One decode call of one layer over `live_lanes` lanes."""
    elements = heads * head_dim * state
    moved = 2.0 * elements * state_itemsize              # S read, S written
    moved += (2.0 * heads * head_dim + 2.0 * groups * state) * itemsize \
        + 4.0 * heads                                    # x, y, B, C; dt
    return {"flops": 6.0 * elements * live_lanes,
            "bytes": moved * live_lanes}
