"""Work of one forward call of the ``flash_attention`` kernel, as the
model calls it (K/V expanded to every query head): the causal half of
the score and value products, and the bytes of q, k, v and o."""
from __future__ import annotations

from typing import Dict, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def work(m: Dict, batch: int, seq: int) -> Tuple[float, float]:
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    h = m["num_heads"]
    flops = 2.0 * batch * h * seq * seq * hd          # 4 B H S^2 hd / 2
    nbytes = 4.0 * batch * seq * h * hd * DTYPE_BYTES[m["dtype"]]
    return flops, nbytes
