"""Model FLOPs per trained token, from the configuration's shapes.

The convention that every count under ``flops/`` keeps (a family with a
count of its own has ``flops/<family>.py``; this one counts the rest):

- 6 x the matrix parameters that one token uses (forward and backward):
  in an expert layer, the experts it is routed to, the shared experts
  and the router; never capacity slots or padding;
- the LM head counted once, the embedding gather not at all;
- causal attention, 6 L S H hd (half of the S x S score and value
  products), and the SSD recurrence, 12 H P N a layer;
- recomputed operations not counted, and no kernel's block sizes.
"""
from __future__ import annotations

from typing import Dict


def matmul_params(m: Dict) -> int:
    d, L, V = m["d_model"], m["num_layers"], m["vocab_size"]
    if m["family"] == "dense":
        hd = m.get("head_dim") or d // m["num_heads"]
        q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
        per = d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"]
    elif m["family"] == "ssm":
        dinner = m["ssm_expand"] * d
        nh = dinner // m["ssm_headdim"]
        gn = m["ssm_ngroups"] * m["ssm_state"]
        per = d * (2 * dinner + 2 * gn + nh) + dinner * d
    else:
        raise NotImplementedError(
            f"flops/model.py counts the dense and ssm families, not "
            f"{m['family']!r}: add flops/{m['family']}.py with its "
            f"per_token(model, seq)")
    return L * per + d * V


def per_token(m: Dict, seq: int) -> float:
    f = 6.0 * matmul_params(m)
    L, d = m["num_layers"], m["d_model"]
    if m["family"] == "dense":
        hd = m.get("head_dim") or d // m["num_heads"]
        f += 6.0 * L * seq * m["num_heads"] * hd
    if m["family"] == "ssm":
        dinner = m["ssm_expand"] * d
        nh = dinner // m["ssm_headdim"]
        f += 12.0 * L * nh * m["ssm_headdim"] * m["ssm_state"]
    return f
