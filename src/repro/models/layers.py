"""Basic building blocks: norms, MLPs, RoPE, embeddings, initializers.

All blocks are pure functions over pytree params.  Param initializers return
nested dicts of ``jnp`` arrays; every initializer has an ``abstract`` twin via
``jax.eval_shape`` (used by the dry-run so no memory is ever allocated).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import scopes


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.bfloat16):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(kind: str, d: int, dtype=jnp.float32):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype=dtype)}
    return {"scale": jnp.ones((d,), dtype=dtype), "bias": jnp.zeros((d,), dtype=dtype)}


def apply_norm(params, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, ff: int, kind: str, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu", "glu"):
        return {
            "wi": dense_init(ks[0], (d, ff), 0, dtype),
            "wg": dense_init(ks[1], (d, ff), 0, dtype),
            "wo": dense_init(ks[2], (ff, d), 0, dtype),
        }
    return {
        "wi": dense_init(ks[0], (d, ff), 0, dtype),
        "wo": dense_init(ks[2], (ff, d), 0, dtype),
    }


@jax.named_scope(scopes.MLP)
def apply_mlp(params, x, kind: str):
    h = x @ params["wi"]
    if kind == "swiglu" or kind == "glu":
        h = jax.nn.silu(x @ params["wg"]) * h
    elif kind == "geglu":
        h = jax.nn.gelu(x @ params["wg"], approximate=True) * h
    else:  # gelu
        h = jax.nn.gelu(h, approximate=True)
    return h @ params["wo"]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                       # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def init_embeddings(key, cfg, dtype=jnp.bfloat16):
    k1, k2 = jax.random.split(key)
    p = {"tok": embed_init(k1, (cfg.vocab_size, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(k2, (cfg.d_model, cfg.vocab_size), 0, dtype)
    return p


@jax.named_scope(scopes.EMBED)
def embed_tokens(params, tokens):
    return jnp.take(params["tok"], tokens, axis=0)


def unembed(params, x):
    if "head" in params:
        return x @ params["head"]
    return x @ params["tok"].T
