"""Flash attention Pallas TPU kernel (causal / sliding-window, GQA-ready).

TPU-native adaptation (DESIGN.md §2): blockwise streaming softmax with
explicit VMEM tiling.  Q is tiled (BLOCK_Q, head_dim) per grid step; K/V
stream through VMEM in (BLOCK_K, head_dim) tiles; the running (m, l, acc)
statistics live in VMEM scratch.  Block shapes are MXU-aligned (multiples
of 128 on the lane dim, 8 on the sublane dim).

Grid: (batch*heads, num_q_blocks, num_k_blocks) — k innermost, so the
scratch accumulators carry across the k sweep of each (bh, q-block) pair.
Validated against ``repro.kernels.ref.attention_ref`` in interpret mode
(this container has no TPU; interpret=True executes the same kernel body).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tiling constants live in the jax-free constraints module so the
# static plan verifier can lint against them without importing pallas
from .constraints import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q  # noqa: F401

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 causal: bool, window: int, block_q: int, block_k: int,
                 num_k_blocks: int, sm_scale: float, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = (qi * block_q + q_offset +
             jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    # skip k blocks that are fully masked for this q block
    run = jnp.bool_(True)
    if causal:
        run = ki * block_k <= qi * block_q + q_offset + block_q - 1
    if window:
        run = jnp.logical_and(
            run, (ki + 1) * block_k - 1 > qi * block_q + q_offset - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = k_pos <= q_pos
        if window:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, q_offset: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = True) -> jax.Array:
    """q/k/v: (B, S, H, hd) with K/V already expanded to H heads.
    Returns (B, Sq, H, hd).  ``q_offset`` shifts q positions (e.g. decode
    with a prefix of cached tokens)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k

    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)

    kernel = functools.partial(
        _attn_kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, sm_scale=1.0 / math.sqrt(hd),
        q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running sum l
            pltpu.VMEM((block_q, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
