"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract:
numerics ground truth, no tiling, no VMEM concerns)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal=True, window=0, q_offset=0):
    """q/k/v: (B, Sq/Sk, H, hd), K/V already expanded to H heads."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(Sq)[:, None] + q_offset
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = k_pos <= q_pos
    if window:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_slot_positions(pos, cache_len, *, ring=False):
    """Position held by each cache slot at decode step ``pos``.

    Linear cache: slot i holds position i.  Ring cache (sliding-window
    buffer): slot i holds the latest p ≤ pos with p % cache_len == i —
    slots not yet written come out negative and must be masked.  Shared
    by the einsum decode path, the flash_decode wrapper and this oracle,
    so the three can never disagree on ring semantics."""
    idx = jnp.arange(cache_len, dtype=jnp.int32)
    if ring:
        return pos - ((pos - idx) % cache_len)
    return idx


def decode_attention_ref(q, k, v, pos, *, window=0, softcap=0.0,
                         ring=False):
    """Single-query decode attention oracle (the ``flash_decode`` ground
    truth).  q: (B, H, hd) — ONE query token per sequence; k/v:
    (B, KV, S, hd) cache layout (kv head i serves q heads
    [i·G, (i+1)·G)); pos: scalar int32 position of the query token.
    Returns (B, H, hd)."""
    B, H, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    rep = H // KV
    kk = jnp.repeat(k, rep, axis=1).astype(jnp.float32)    # (B, H, S, hd)
    vv = jnp.repeat(v, rep, axis=1).astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32), kk) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    k_pos = decode_slot_positions(pos, S, ring=ring)
    valid = (k_pos >= 0) & (k_pos <= pos)
    if window:
        valid = valid & (k_pos > pos - window)
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bhsd->bhd", p, vv)
    return out.astype(q.dtype)


def ssd_ref(x, dt, A, Bm, Cm, initial_state=None):
    """Sequential (non-chunked) SSD recurrence — the simplest possible
    ground truth for the ssd_scan kernel AND for kernels/ssd_chunked.

    x: (b, S, h, p); dt: (b, S, h); A: (h,); Bm/Cm: (b, S, g, n).
    Returns (y (b, S, h, p), final_state (b, h, p, n)).
    """
    b, S, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    Bh = jnp.repeat(Bm, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(Cm, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        decay = jnp.exp(A[None, :] * dt_t)               # (b, h)
        xd = x_t * dt_t[..., None]                       # (b, h, p)
        state = state * decay[..., None, None] + \
            jnp.einsum("bhp,bhn->bhpn", xd, B_t)
        y = jnp.einsum("bhpn,bhn->bhp", state, C_t)
        return state, y

    init = jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None \
        else initial_state
    xs = (xf.swapaxes(0, 1), dtf.swapaxes(0, 1),
          Bh.swapaxes(0, 1), Ch.swapaxes(0, 1))
    final, ys = jax.lax.scan(step, init, xs)
    return ys.swapaxes(0, 1), final


def rmsnorm_ref(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)
