"""Work of one forward call of the ``ssd_scan`` kernel: the recurrence's
own operations, 4 B S H P N (decay and input into the state, state
against C), whatever algorithm computes them, and the bytes of its
inputs (x and B, C in the model dtype; dt, A in float32) and outputs
(y and the final state in float32)."""
from __future__ import annotations

from typing import Dict, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def work(m: Dict, batch: int, seq: int) -> Tuple[float, float]:
    dinner = m["ssm_expand"] * m["d_model"]
    p, n, g = m["ssm_headdim"], m["ssm_state"], m["ssm_ngroups"]
    h = dinner // p
    w = DTYPE_BYTES[m["dtype"]]
    flops = 4.0 * batch * seq * h * p * n
    nbytes = (batch * seq * h * p * w            # x
              + batch * seq * h * 4 + h * 4      # dt, A
              + 2 * batch * seq * g * n * w      # B, C
              + batch * seq * h * p * 4          # y
              + batch * h * p * n * 4)           # final state
    return flops, float(nbytes)
