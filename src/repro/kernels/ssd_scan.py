"""Mamba2 SSD chunk-scan Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the sequence is
processed in chunks; each grid step computes the intra-chunk quadratic part
on the MXU plus the contribution of the carried state, and updates the
running (headdim × state) recurrent state held in VMEM scratch.

Grid: (batch*heads, num_chunks) — chunks innermost so the state scratch
carries the recurrence across the sequence, exactly like the flash kernel
carries softmax statistics.  Block shapes: chunk × headdim and
chunk × state tiles (chunk defaults to 128 — lane-aligned).

Oracle: ``repro.kernels.ref.ssd_ref`` (sequential recurrence).  The
backward (``ops.ssd_scan``'s custom VJP) differentiates
``repro.kernels.ssd_chunked``, the same chunked algorithm in jnp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fin_ref,
                state_ref, *, chunk: int, num_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)           # (c, p)
    dt_row = dt_ref[0].astype(jnp.float32)     # (1, c)
    A = a_ref[0]                               # (1, 1) decay rate (<0)
    Bm = b_ref[0].astype(jnp.float32)          # (c, n)
    Cm = c_ref[0].astype(jnp.float32)          # (c, n)

    # Mosaic lowers neither cumsum nor a lane->sublane reshape, so the
    # in-chunk prefix sums and the column form of dt come from masked
    # reductions over the (c, c) triangle (exact fp32 adds)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dt_col = jnp.sum(jnp.where(i == j, dt_row, 0.0), axis=1,
                     keepdims=True)            # (c, 1)
    a_row = A * dt_row                         # (1, c)
    a_col = A * dt_col                         # (c, 1)
    cum_col = jnp.sum(jnp.where(j <= i, a_row, 0.0), axis=1,
                      keepdims=True)           # (c, 1): Σ_{k<=i} a_k
    cum_row = jnp.sum(jnp.where(i <= j, a_col, 0.0), axis=0,
                      keepdims=True)           # (1, c): Σ_{k<=j} a_k
    xd = x * dt_col                            # (c, p)

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i
    L = jnp.where(i >= j, jnp.exp(cum_col - cum_row), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y_intra = jax.lax.dot_general(scores, xd, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # carried-state contribution: y_off = (C * exp(cum)) @ state^T
    state = state_ref[...]                     # (p, n)
    c_dec = Cm * jnp.exp(cum_col)
    y_off = jax.lax.dot_general(c_dec, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

    y_ref[0] = (y_intra + y_off).astype(y_ref.dtype)

    # state update: state' = state * exp(sum a) + xd^T @ (B * exp(cum_last - cum))
    total = jnp.sum(a_row, axis=1, keepdims=True)          # (1, 1)
    b_dec = Bm * jnp.exp(total - cum_col)
    upd = jax.lax.dot_general(xd, b_dec, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (p, n)
    state_ref[...] = state * jnp.exp(total) + upd

    @pl.when(ci == num_chunks - 1)
    def _finish():
        fin_ref[0] = state_ref[...]


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 128, interpret: bool = True):
    """x: (b, S, h, p); dt: (b, S, h); A: (h,); Bm/Cm: (b, S, g, n) with g
    groups broadcast over heads.  Returns (y (b,S,h,p) fp32,
    final_state (b,h,p,n) fp32)."""
    b, S, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    # (b, S, h, p) -> (b*h, S, p); broadcast groups -> heads
    xr = x.transpose(0, 2, 1, 3).reshape(b * h, S, p)
    dtr = dt.transpose(0, 2, 1).reshape(b * h, 1, S)
    Br = jnp.repeat(Bm, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, S, n)
    Cr = jnp.repeat(Cm, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, S, n)
    Ar = jnp.tile(A.reshape(1, h), (b, 1)).reshape(b * h, 1, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    y, fin = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, c: (i, 0, c)),
            pl.BlockSpec((1, 1, 1), lambda i, c: (i, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, p, n), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, S, p), jnp.float32),
            jax.ShapeDtypeStruct((b * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xr, dtr, Ar, Br, Cr)
    y = y.reshape(b, h, S, p).transpose(0, 2, 1, 3)
    fin = fin.reshape(b, h, p, n)
    return y, fin
