"""Chunked SSD in plain jnp: the algorithm the ``ssd_scan`` kernel runs.

The chunked form of arXiv:2405.21060: quadratic within a chunk (chunk x
chunk matmuls), linear across chunks (a recurrence over S / chunk chunk
states).  It is the einsum path of ``models.ssm`` and the function the
``ssd_scan`` custom VJP differentiates for its backward, so the
backward does the recurrence's work as matmuls too, never one time
step at a time.

Heads are laid out as (g groups) x (h/g heads a group): ``Bm`` and
``Cm`` are contracted at group width and only the results are
broadcast over a group's heads.  Everything runs in float32, every
contraction at ``Precision.HIGHEST``.  It names no scope: its callers
do (``models.ssm.ssd_chunked``; ``ops.ssd_scan``, whose rule lies under
the outer one).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _segsum(a):
    """Stable segment-sum: a (..., l) -> (..., l, l) with
    out[i, j] = sum_{j < t <= i} a[t], -inf above diagonal."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(l)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, initial_state=None):
    """Chunked SSD.

    x:  (b, S, h, p)   inputs per head
    dt: (b, S, h)      positive step sizes (already softplus'd)
    A:  (h,)           negative decay rates
    Bm: (b, S, g, n)   input matrices  (g groups broadcast over heads)
    Cm: (b, S, g, n)   output matrices
    initial_state: (b, h, p, n) or None (zeros)
    Returns (y (b,S,h,p) fp32, final_state (b,h,p,n) fp32).
    """
    b, S, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc, r = S // chunk, h // g
    f32 = jnp.float32

    dtf = dt.astype(f32)
    xd = x.astype(f32) * dtf[..., None]
    Ad = A.astype(f32)[None, None, :] * dtf                   # (b,S,h)

    # chunked views, heads split as (g, r)
    xc = xd.reshape(b, nc, chunk, g, r, p)
    Ac = Ad.reshape(b, nc, chunk, g, r).transpose(0, 3, 4, 1, 2)  # (b,g,r,c,l)
    Bc = Bm.astype(f32).reshape(b, nc, chunk, g, n)
    Cc = Cm.astype(f32).reshape(b, nc, chunk, g, n)

    A_cum = jnp.cumsum(Ac, axis=-1)                           # (b,g,r,c,l)

    # 1. intra-chunk: (C B^T at group width) * L, then @ x
    L = jnp.exp(_segsum(Ac))                                  # (b,g,r,c,l,s)
    CB = jnp.einsum("bclgn,bcsgn->bgcls", Cc, Bc, precision=_HI)
    scores = CB[:, :, None] * L
    Y_diag = jnp.einsum("bgrcls,bcsgrp->bclgrp", scores, xc, precision=_HI)

    # 2. per-chunk final states
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)           # (b,g,r,c,l)
    xs = xc * decay_states.transpose(0, 3, 4, 1, 2)[..., None]
    states = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xs, Bc, precision=_HI)

    # 3. inter-chunk recurrence over the nc chunk states
    if initial_state is None:
        init = jnp.zeros((b, g, r, p, n), f32)
    else:
        init = initial_state.astype(f32).reshape(b, g, r, p, n)
    states = jnp.concatenate([init[:, None], states], axis=1)  # (b,c+1,g,r,p,n)
    chunk_sums = jnp.pad(A_cum[..., -1], ((0, 0),) * 3 + ((1, 0),))
    decay_chunk = jnp.exp(_segsum(chunk_sums))                # (b,g,r,c+1,c+1)
    new_states = jnp.einsum("bgrzc,bcgrpn->bzgrpn", decay_chunk, states,
                            precision=_HI)
    prev_states, final_state = new_states[:, :-1], new_states[:, -1]

    # 4. state contribution to outputs
    state_decay = jnp.exp(A_cum).transpose(0, 3, 4, 1, 2)     # (b,c,l,g,r)
    Y_off = jnp.einsum("bclgn,bcgrpn->bclgrp", Cc, prev_states,
                       precision=_HI) * state_decay[..., None]

    y = (Y_diag + Y_off).reshape(b, S, h, p)
    return y, final_state.reshape(b, h, p, n)
