"""Operations and bytes a dropless expert layer's grouped matmuls need, from
the shapes alone (`kernel_work.py` is the same for attention).

One call of one expert layer over `tokens` tokens, each routed to `top_k`
of `experts` experts held here, an expert a SwiGLU of `width`: the three
matrices (gate, up, down: 3 d width numbers) of every expert that drew a
token are read once, the assignments' rows are read and written (d in, 2
width out; width in, d out), and an assignment costs 6 d width operations.
The experts that drew a token are counted as `min(experts, tokens x
top_k)`: **an overstatement by the experts that drew none** — with 256
assignments over 64 experts about one expert in 64 stays untouched
((63/64)^256 = 1.8%), so a decode call's floor is ~2% high and the share
reads that much too well; a prefill's thousands of assignments reach every
expert. A decode call is bound by the matrices' bytes, a long prefill by
its operations.
"""

from __future__ import annotations

from typing import Dict


def moe_experts_work(tokens: int, top_k: int, experts: int, d_model: int,
                     width: int, itemsize: int = 2) -> Dict[str, float]:
    assignments = tokens * top_k
    touched = min(experts, assignments)
    return {"flops": 6.0 * d_model * width * assignments,
            "bytes": (touched * 3.0 * d_model * width
                      + assignments * (2.0 * d_model + 3.0 * width))
            * itemsize}
