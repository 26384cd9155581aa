"""Attention: GQA + RoPE + (optional) QK-norm / bias / sliding window.

Three execution paths:
  * ``einsum``  — plain softmax(QK^T)V for short sequences,
  * ``chunked`` — flash-style lax.scan over query blocks (never materializes
                  the S×S score matrix; default for S >= CHUNK_THRESHOLD),
  * ``pallas``  — TPU Pallas flash kernel (see repro.kernels); selected via
                  ``backend='pallas'`` and used on real TPUs only.

Decode path operates on a KV cache; for sliding-window attention the cache is
a ring buffer of window size (used by long_500k).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import layers
from ..obs import scopes
from ..sharding import ctx as shctx
from ..sharding.ctx import constrain

CHUNK_THRESHOLD = 2048
Q_CHUNK = 512
NEG_INF = -1e30


def _constrain_qkv(q, k, v):
    """Pin the attention layout so GSPMD never partitions the score-matmul
    contraction dim (which would all-reduce full S×S scores):

      * heads divisible by the model axis -> Megatron attention (shard H),
      * otherwise -> sequence-parallel q with replicated (gathered) K/V.
    """
    model = shctx.axis_size("model")
    if model == 1:
        return q, k, v
    H = q.shape[2]
    if H % model == 0:
        q = constrain(q, "batch", None, "heads", None)
        k = constrain(k, "batch", None, "heads", None)
        v = constrain(v, "batch", None, "heads", None)
    else:
        q = constrain(q, "batch", "seq_model", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
    return q, k, v


def init_attention(key, cfg, dtype=jnp.bfloat16):
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, cfg.num_heads * hd), 0, dtype),
        "wk": layers.dense_init(ks[1], (d, cfg.num_kv_heads * hd), 0, dtype),
        "wv": layers.dense_init(ks[2], (d, cfg.num_kv_heads * hd), 0, dtype),
        "wo": layers.dense_init(ks[3], (cfg.num_heads * hd, d), 0, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = layers.init_norm("rmsnorm", hd)
        p["k_norm"] = layers.init_norm("rmsnorm", hd)
    return p


def _project_qkv(params, cfg, x, positions, rope: bool = True):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = layers.apply_norm(params["q_norm"], q, "rmsnorm")
        k = layers.apply_norm(params["k_norm"], k, "rmsnorm")
    if rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _expand_kv(k, num_heads):
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating each kv head."""
    B, S, KV, hd = k.shape
    rep = num_heads // KV
    return jnp.repeat(k, rep, axis=2)


def _mask_bias(q_pos, k_pos, causal, window, prefix_len):
    """Additive mask bias (..., Sq, Sk) from position vectors (fused by XLA)."""
    ok = jnp.ones(q_pos.shape[-1:] + k_pos.shape[-1:], dtype=bool)
    if causal:
        ok = k_pos[None, :] <= q_pos[:, None]
        if prefix_len:
            ok = ok | (k_pos[None, :] < prefix_len)
    if window:
        ok = ok & (k_pos[None, :] > q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF)


def _softcap(scores, cap):
    if cap and cap > 0:
        return jnp.tanh(scores / cap) * cap
    return scores


def _attend_einsum(q, k, v, bias, scale, softcap=0.0):
    """q: (B,Sq,H,hd); k,v: (B,Sk,H,hd); bias: (Sq,Sk) additive."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = _softcap(scores, softcap) + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attend_chunked(q, k, v, q_pos, k_pos, causal, window, prefix_len, scale,
                    softcap=0.0):
    """Flash-style streaming softmax over query chunks (memory O(Sq_blk*Sk))."""
    B, Sq, H, hd = q.shape
    nblk = max(1, Sq // Q_CHUNK)
    blk = Sq // nblk
    qb = q.reshape(B, nblk, blk, H, hd).swapaxes(0, 1)      # (nblk,B,blk,H,hd)
    qp = q_pos.reshape(nblk, blk)

    model = shctx.axis_size("model")
    head_sharded = H % model == 0

    def cblk(x):
        if head_sharded:
            return constrain(x, None, "batch", None, "heads", None)
        return constrain(x, None, "batch", "seq_model", None, None)

    qb = cblk(qb)

    def body(_, inp):
        qi, qpi = inp
        bias = _mask_bias(qpi, k_pos, causal, window, prefix_len)
        out = _attend_einsum(qi, k, v, bias, scale, softcap)
        return None, out

    _, outs = jax.lax.scan(body, None, (qb, qp))
    outs = cblk(outs)
    return outs.swapaxes(0, 1).reshape(B, Sq, H, hd)


@jax.named_scope(scopes.ATTENTION_CORE)
def attend(q, k, v, *, q_pos, k_pos, causal=True, window=0, prefix_len=0,
           softcap=0.0, backend="auto"):
    """Full attention dispatch.  q:(B,Sq,H,hd), k/v:(B,Sk,H,hd).

    ``backend="auto"`` resolves through ``kernels.ops.preferred_backend``:
    the Pallas flash kernel on a real TPU, the einsum/chunked jnp paths
    elsewhere (previously ``auto`` fell through to einsum/chunked even
    on TPU, so the kernels only ran when callers passed an explicit
    ``backend="pallas"`` nobody passed — and the profiler priced a model
    nobody executed)."""
    from ..kernels import ops as kops
    if backend == "auto" and kops.preferred_backend() == "pallas":
        backend = "pallas"
    scale = 1.0 / (q.shape[-1] ** 0.5)
    Sq, Sk = q.shape[1], k.shape[1]
    if backend == "pallas" and (softcap or prefix_len):
        # the prefill kernel expresses neither logit softcap nor a
        # bidirectional prefix — route those archs to the jnp paths
        # rather than silently dropping the mask/cap (DESIGN.md §11
        # backend matrix); the DECODE kernel does support softcap.
        backend = "einsum" if max(Sq, Sk) <= CHUNK_THRESHOLD else "chunked"
    if backend == "pallas":
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    q_offset=int(k_pos.shape[0] - q_pos.shape[0]))
    if backend == "einsum" or (backend == "auto" and max(Sq, Sk) <= CHUNK_THRESHOLD):
        bias = _mask_bias(q_pos, k_pos, causal, window, prefix_len)
        return _attend_einsum(q, k, v, bias, scale, softcap)
    return _attend_chunked(q, k, v, q_pos, k_pos, causal, window, prefix_len,
                           scale, softcap)


# ---------------------------------------------------------------------------
# forward (training / prefill) self-attention
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.ATTENTION)
def self_attention(params, cfg, x, *, positions=None, causal=True,
                   prefix_len=0, rope=True, window=None, backend="auto"):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = _project_qkv(params, cfg, x, positions, rope=rope)
    k = _expand_kv(k, cfg.num_heads)
    v = _expand_kv(v, cfg.num_heads)
    q, k, v = _constrain_qkv(q, k, v)
    win = cfg.sliding_window if window is None else window
    out = attend(q, k, v, q_pos=positions, k_pos=positions, causal=causal,
                 window=win, prefix_len=prefix_len,
                 softcap=cfg.attn_logit_softcap, backend=backend)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return out @ params["wo"]


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch, cache_len, dtype=jnp.bfloat16):
    """Cache layout: (B, KV, S_cache, hd).  ``ring=True`` when the cache is a
    sliding-window ring buffer (long_500k)."""
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, cache_len, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, cache_len, cfg.head_dim), dtype),
    }


def prefill_into_cache(cache, k, v, start=0):
    """k,v: (B, S, KV, hd) -> cache at [start:start+S]."""
    kc = k.swapaxes(1, 2)  # (B,KV,S,hd)
    vc = v.swapaxes(1, 2)
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice(cache["k"], kc, (0, 0, start, 0))
    cache["v"] = jax.lax.dynamic_update_slice(cache["v"], vc, (0, 0, start, 0))
    return cache


def decode_self_attention(params, cfg, x, cache, pos, *, ring=False,
                          rope=True, window=0, backend="auto"):
    """One-token decode step.

    x: (B, 1, d); pos: scalar int32 — current position (same for the batch).
    cache: dict(k,v) with layout (B, KV, S_cache, hd).
    ``backend="pallas"`` (or ``"auto"`` on TPU) routes the attention to
    the paged ``flash_decode`` kernel, which streams the cache in place;
    both paths keep the cache layout resident — transposing a 32k cache
    per layer would copy gigabytes per step.
    Returns (out (B,1,d), new_cache).
    """
    from ..kernels import ops as kops
    B = x.shape[0]
    hd = cfg.head_dim
    positions = jnp.full((1,), pos, dtype=jnp.int32)
    q, k, v = _project_qkv(params, cfg, x, positions, rope=rope)
    S_cache = cache["k"].shape[2]
    slot = jnp.where(ring, pos % S_cache, jnp.minimum(pos, S_cache - 1)) if ring else pos
    kc = k.swapaxes(1, 2)                                   # (B,KV,1,hd)
    vc = v.swapaxes(1, 2)
    new_k = jax.lax.dynamic_update_slice(cache["k"], kc, (0, 0, slot, 0))
    new_v = jax.lax.dynamic_update_slice(cache["v"], vc, (0, 0, slot, 0))

    if backend == "auto" and kops.preferred_backend() == "pallas":
        backend = "pallas"
    if backend == "pallas":
        out = kops.flash_decode(q[:, 0], new_k, new_v, pos, window=window,
                                softcap=cfg.attn_logit_softcap or 0.0,
                                ring=ring)
        out = out.reshape(B, 1, cfg.num_heads * hd) @ params["wo"]
        return out, {"k": new_k, "v": new_v}

    # positions held in each cache slot (shared ring semantics with the
    # flash_decode wrapper and its oracle — kernels/ref.py)
    from ..kernels.ref import decode_slot_positions
    k_pos = decode_slot_positions(pos, S_cache, ring=ring)
    valid = (k_pos >= 0) & (k_pos <= pos)
    if window:
        valid = valid & (k_pos > pos - window)
    bias = jnp.where(valid, 0.0, NEG_INF)[None, :]          # (1, S_cache)

    rep = cfg.num_heads // cfg.num_kv_heads
    kk = jnp.repeat(new_k, rep, axis=1) if rep > 1 else new_k  # (B,H,S,hd)
    vv = jnp.repeat(new_v, rep, axis=1) if rep > 1 else new_v
    scores = jnp.einsum("bqhd,bhsd->bhqs", q, kk).astype(jnp.float32)
    scores = scores * (1.0 / (hd ** 0.5))
    scores = _softcap(scores, cfg.attn_logit_softcap) + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqs,bhsd->bqhd", probs, vv)
    out = out.reshape(B, 1, cfg.num_heads * hd) @ params["wo"]
    return out, {"k": new_k, "v": new_v}


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg, dtype=jnp.bfloat16):
    return init_attention(key, cfg, dtype)


def cross_attention(params, cfg, x, enc_kv, backend="auto"):
    """x: (B, Sq, d) decoder states; enc_kv: (k, v) each (B, Se, KV, hd)."""
    B, Sq, _ = x.shape
    hd = cfg.head_dim
    q = (x @ params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"]
    q = q.reshape(B, Sq, cfg.num_heads, hd)
    k, v = enc_kv
    kk = _expand_kv(k, cfg.num_heads)
    vv = _expand_kv(v, cfg.num_heads)
    Se = k.shape[1]
    q_pos = jnp.arange(Sq, dtype=jnp.int32)
    k_pos = jnp.arange(Se, dtype=jnp.int32)
    out = attend(q, kk, vv, q_pos=q_pos, k_pos=k_pos, causal=False,
                 backend=backend)
    return out.reshape(B, Sq, cfg.num_heads * hd) @ params["wo"]


def encode_cross_kv(params, cfg, enc_out):
    """Precompute cross-attention K/V from encoder output (no RoPE)."""
    B, Se, _ = enc_out.shape
    hd = cfg.head_dim
    k = enc_out @ params["wk"]
    v = enc_out @ params["wv"]
    if cfg.qkv_bias:
        k, v = k + params["bk"], v + params["bv"]
    k = k.reshape(B, Se, cfg.num_kv_heads, hd)
    v = v.reshape(B, Se, cfg.num_kv_heads, hd)
    return k, v
