"""Block-level composition: decoder blocks for every family, stacked-param
init (leading layer dim) and scan-over-layers forward/decode drivers.

Block kinds
  dense/vlm : [norm -> self-attn -> +res] [norm -> mlp -> +res]
  moe       : [norm -> self-attn -> +res] [norm -> moe -> +res]
  ssm       : [norm -> mamba2 -> +res]
  hybrid    : groups of ssm blocks followed by one weight-shared attn block
  audio enc : bidirectional attn + mlp;  audio dec: self + cross + mlp
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention, layers, moe as moe_lib, ssm as ssm_lib
from ..obs import scopes
from ..sharding.ctx import constrain

PyTree = Any


# ---------------------------------------------------------------------------
# per-block init
# ---------------------------------------------------------------------------

def init_block(key, cfg, kind: str, dtype=jnp.bfloat16) -> PyTree:
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return {"ln1": layers.init_norm(cfg.norm, cfg.d_model),
                "ssm": ssm_lib.init_ssm(ks[0], cfg, dtype)}
    p = {"ln1": layers.init_norm(cfg.norm, cfg.d_model),
         "attn": attention.init_attention(ks[0], cfg, dtype),
         "ln2": layers.init_norm(cfg.norm, cfg.d_model)}
    if kind == "moe":
        p["moe"] = moe_lib.init_moe(ks[1], cfg, dtype)
    elif kind == "dec_cross":
        p["xattn"] = attention.init_cross_attention(ks[1], cfg, dtype)
        p["ln3"] = layers.init_norm(cfg.norm, cfg.d_model)
        p["mlp"] = layers.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    else:  # dense / enc
        p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    return p


def init_stacked_blocks(key, cfg, kind: str, n: int, dtype=jnp.bfloat16):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_block(k, cfg, kind, dtype))(keys)


# ---------------------------------------------------------------------------
# per-block forward
# ---------------------------------------------------------------------------

def block_forward(p, cfg, x, kind: str, *, positions=None, causal=True,
                  prefix_len=0, enc_kv=None, window=None, backend="auto"):
    """One block.  Returns (x, metrics) — metrics non-empty for MoE."""
    metrics = {}
    if kind == "ssm":
        h = layers.apply_norm(p["ln1"], x, cfg.norm)
        y, _ = ssm_lib.mamba2_forward(p["ssm"], cfg, h, backend=backend)
        return x + y, metrics
    h = layers.apply_norm(p["ln1"], x, cfg.norm)
    rope = cfg.family != "audio"
    a = attention.self_attention(p["attn"], cfg, h, positions=positions,
                                 causal=causal, prefix_len=prefix_len,
                                 rope=rope, window=window, backend=backend)
    x = x + a
    if kind == "dec_cross":
        h = layers.apply_norm(p["ln3"], x, cfg.norm)
        x = x + attention.cross_attention(p["xattn"], cfg, h, enc_kv, backend)
    h = layers.apply_norm(p["ln2"], x, cfg.norm)
    if kind == "moe":
        y, metrics = moe_lib.moe_block(p["moe"], cfg, h)
    else:
        y = layers.apply_mlp(p["mlp"], h, cfg.mlp)
    return x + y, metrics


def _maybe_remat(fn, remat: bool, policy=None):
    if not remat:
        return fn
    return jax.checkpoint(fn, policy=policy)


def run_stacked(blocks: PyTree, cfg, x, kind: str, *, remat=True,
                remat_policy=None, backend="auto", sp=True, **fwd_kw):
    """lax.scan over stacked block params, accumulating MoE aux losses.

    Inter-block activation sharding: sequence-parallel over `model` for
    attention stacks (``sp=True``), d_model-sharded for SSM stacks (their
    conv/scan structure wants the sequence dim local — §Perf hillclimb B),
    so the saved per-layer residuals are always model-sharded."""
    if kind == "ssm":
        cblk = lambda x: constrain(x, "batch", None, "model")
    else:
        cblk = lambda x: constrain(x, "batch", "seq_model" if sp else None,
                                   None)

    def one(x, p):
        x = cblk(x)
        x, m = block_forward(p, cfg, x, kind, backend=backend, **fwd_kw)
        aux = m.get("moe_aux_loss", 0.0) + m.get("moe_z_loss", 0.0)
        return x, jnp.asarray(aux, jnp.float32)

    body = _maybe_remat(one, remat, remat_policy)
    with jax.named_scope(scopes.LAYERS):
        x, auxs = jax.lax.scan(lambda c, p: body(c, p), x, blocks)
    x = cblk(x)
    return x, jnp.sum(auxs)


# ---------------------------------------------------------------------------
# per-block decode (single token, cache)
# ---------------------------------------------------------------------------

def block_decode(p, cfg, x, cache, pos, kind: str, *, ring=False, window=0,
                 enc_kv=None, backend="auto"):
    if kind == "ssm":
        h = layers.apply_norm(p["ln1"], x, cfg.norm)
        y, new_cache = ssm_lib.mamba2_decode_step(p["ssm"], cfg, h, cache)
        return x + y, new_cache
    h = layers.apply_norm(p["ln1"], x, cfg.norm)
    rope = cfg.family != "audio"
    a, new_cache = attention.decode_self_attention(
        p["attn"], cfg, h, cache, pos, ring=ring, rope=rope, window=window,
        backend=backend)
    x = x + a
    if kind == "dec_cross":
        h = layers.apply_norm(p["ln3"], x, cfg.norm)
        x = x + attention.cross_attention(p["xattn"], cfg, h, enc_kv,
                                          backend)
    h = layers.apply_norm(p["ln2"], x, cfg.norm)
    if kind == "moe":
        y, _ = moe_lib.moe_block(p["moe"], cfg, h)
    else:
        y = layers.apply_mlp(p["mlp"], h, cfg.mlp)
    return x + y, new_cache


def run_stacked_decode(blocks, cfg, x, caches, pos, kind: str, *, ring=False,
                       window=0, enc_kv=None, backend="auto"):
    """Scan over (stacked blocks, stacked caches)."""

    def step(x, inp):
        if enc_kv is not None:
            p, c, ekv = inp
        else:
            (p, c), ekv = inp, None
        x, c2 = block_decode(p, cfg, x, c, pos, kind, ring=ring,
                             window=window, enc_kv=ekv, backend=backend)
        return x, c2

    xs = (blocks, caches, enc_kv) if enc_kv is not None else (blocks, caches)
    x, new_caches = jax.lax.scan(step, x, xs)
    return x, new_caches
