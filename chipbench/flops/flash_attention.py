"""Work of one forward call of the ``flash_attention`` kernel, as the
model calls it (K/V expanded to every query head): the causal half of
the score and value products, and the bytes of q, k, v and o.

Scores and values may have widths of their own, as in latent attention
(``qk_nope_head_dim + qk_rope_head_dim`` for q and k, ``v_head_dim``
for v and o, the published DeepSeek names); otherwise both are
``head_dim``."""
from __future__ import annotations

from typing import Dict, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def work(m: Dict, batch: int, seq: int) -> Tuple[float, float]:
    if "v_head_dim" in m:
        d_qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
        d_v = m["v_head_dim"]
    else:
        d_qk = d_v = m.get("head_dim") or m["d_model"] // m["num_heads"]
    h = m["num_heads"]
    # 2 B H S^2 (d_qk + d_v) for both products, half of it causal
    flops = float(batch * h * seq * seq * (d_qk + d_v))
    nbytes = float(batch * seq * h * (2 * d_qk + 2 * d_v)
                   * DTYPE_BYTES[m["dtype"]])
    return flops, nbytes
