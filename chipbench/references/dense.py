"""Plain reference of the dense decoder (H2 / InternLM2 block): RMSNorm,
GQA attention with rotary positions (rotate-half), SwiGLU MLP, untied
head, next-token cross entropy.  float32 throughout; attention one head
at a time so that no layer holds every head's S x S scores."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references._common import Ops, cross_entropy_sum, rmsnorm

EMBED_PATH = "embed/tok"
HEAD_PATHS = ("final_norm/scale", "embed/head")
# a small model of this family, for the CPU test against the program
CPU_MODEL = dict(name="t", family="dense", num_layers=2, d_model=64,
                 num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
                 vocab_size=128, norm="rmsnorm", mlp="swiglu",
                 rope_theta=1e6, max_seq_len=64, dtype="float32")


def param_specs(m):
    d, L, V, ff = m["d_model"], m["num_layers"], m["vocab_size"], m["d_ff"]
    hd = m.get("head_dim") or d // m["num_heads"]
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    mat, f32 = m["dtype"], "float32"
    fan = ("fan_in",)
    return {
        "embed/tok": ((V, d), mat, ("normal", 0.02)),
        "embed/head": ((d, V), mat, fan),
        "final_norm/scale": ((d,), f32, ("ones",)),
        "blocks/ln1/scale": ((L, d), f32, ("ones",)),
        "blocks/ln2/scale": ((L, d), f32, ("ones",)),
        "blocks/attn/wq": ((L, d, q), mat, fan),
        "blocks/attn/wk": ((L, d, kv), mat, fan),
        "blocks/attn/wv": ((L, d, kv), mat, fan),
        "blocks/attn/wo": ((L, q, d), mat, fan),
        "blocks/mlp/wi": ((L, d, ff), mat, fan),
        "blocks/mlp/wg": ((L, d, ff), mat, fan),
        "blocks/mlp/wo": ((L, ff, d), mat, fan),
    }


def rope(x, theta):
    """x: (B, S, H, hd), rotate-half convention."""
    hd, S = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs       # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, ops):
    """Causal softmax attention; q (B,S,H,hd), k/v (B,S,KV,hd)."""
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_head(qkv):
        qh, kh, vh = qkv                                         # (B,S,hd)
        s = ops.einsum("bqd,bkd->bqk", qh, kh) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return ops.einsum("bqk,bkd->bqd", p, vh)

    heads = tuple(t.transpose(2, 0, 1, 3) for t in (q, k, v))   # (H,B,S,hd)
    o = jax.lax.map(jax.checkpoint(one_head), heads)
    return o.transpose(1, 2, 0, 3)


def block(m, p, x, ops: Ops, stack=None):
    """One layer; ``p`` maps the layer's paths under ``blocks/``, the
    one stack."""
    B, S, d = x.shape
    hd = m.get("head_dim") or d // m["num_heads"]
    h = rmsnorm(x, p["ln1/scale"])
    q = ops.mm(h, p["attn/wq"]).reshape(B, S, m["num_heads"], hd)
    k = ops.mm(h, p["attn/wk"]).reshape(B, S, m["num_kv_heads"], hd)
    v = ops.mm(h, p["attn/wv"]).reshape(B, S, m["num_kv_heads"], hd)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    o = attention(q, k, v, ops).reshape(B, S, -1)
    x = ops.act(x + ops.mm(o, p["attn/wo"]))
    h = rmsnorm(x, p["ln2/scale"])
    u = jax.nn.silu(ops.mm(h, p["mlp/wg"])) * ops.mm(h, p["mlp/wi"])
    return ops.act(x + ops.mm(u, p["mlp/wo"]))


def head_loss_sum(m, hp, x, tokens, ops: Ops):
    h = rmsnorm(x, hp["final_norm/scale"])
    return cross_entropy_sum(ops.mm(h, hp["embed/head"]), tokens)

