"""Single-query flash-attention Pallas TPU kernel over a block-paged KV
cache (the decode hot path).

Decode attention is one query row against a long KV cache: memory-bound,
so the kernel's job is to stream the cache through VMEM exactly once in
(PAGE, head_dim) pages with the online-softmax (m, l, acc) statistics in
VMEM scratch — never materializing the (H, S) score matrix and never
transposing the cache out of its resident (B, KV, S, hd) layout.

GQA is handled by folding the query-head group into the SUBLANE dim: the
q block for one kv head is (group, hd), so the score tile is
(group, PAGE) — lane-aligned in the page dim (PAGE = 128) and
MXU-friendly whenever group ≥ 8 (the wrapper pads smaller groups up to
the fp32 sublane tile).  Grid: (batch·kv_heads, num_pages) with pages
innermost, so the scratch accumulators carry across each row's page
sweep — the same carry structure as ``flash_attention``.

Masking (causal bound at ``pos``, sliding window, ring-buffer slot→
position mapping, sequence padding) arrives as a precomputed additive
bias row per batch element, laid out (B, 1, S) so that its (1, PAGE)
block is legal on the chip for any batch: position logic stays in cheap
O(S) jnp in the wrapper (``ops.flash_decode``), the kernel body only
adds a (1, PAGE) slice — which also means per-sequence lengths (a paged cache with
ragged batches) need no kernel change, just a per-row bias.  Pages that
are fully masked (outside the window, or padding) are skipped via a
``pl.when`` guard on the page's bias maximum.

Softcap (``tanh(s/c)·c``, Gemma-style) is applied pre-bias, matching
``ref.decode_attention_ref``.  Validated against that oracle in
interpret mode (no TPU in this container; interpret=True executes the
same kernel body).
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
from .constraints import DEFAULT_PAGE, MIN_GROUP  # noqa: F401 (re-export)

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   sm_scale: float, softcap: float, num_pages: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bias = bias_ref[0]                                     # (1, PAGE)
    # a page whose every slot is masked contributes nothing — skip it
    live = jnp.max(bias) > 0.5 * NEG_INF

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)                   # (G, hd)
        k = k_ref[0].astype(jnp.float32)                   # (PAGE, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = s + bias                                       # (G, PAGE)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                   # (PAGE, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == num_pages - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 bias: jax.Array, *, softcap: float = 0.0,
                 page_size: int = DEFAULT_PAGE,
                 interpret: bool = True) -> jax.Array:
    """q: (B, KV, G, hd) — one query token, heads grouped per kv head;
    k/v: (B, KV, S, hd) cache layout; bias: (B, 1, S) additive fp32 mask
    (0 for attendable slots, NEG_INF for masked/padded).  S must be a
    multiple of ``page_size`` (the wrapper pads).  Returns
    (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    S = k.shape[2]
    assert S % page_size == 0, (S, page_size)
    assert bias.shape == (B, 1, S), (bias.shape, B, S)
    num_pages = S // page_size

    qr = q.reshape(B * KV, G, hd)
    kr = k.reshape(B * KV, S, hd)
    vr = v.reshape(B * KV, S, hd)

    kernel = functools.partial(
        _decode_kernel, sm_scale=1.0 / math.sqrt(hd),
        softcap=float(softcap), num_pages=num_pages)

    out = pl.pallas_call(
        kernel,
        grid=(B * KV, num_pages),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, page_size, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, page_size, hd), lambda b, j: (b, j, 0)),
            # bias is per BATCH row, shared by that row's kv heads
            pl.BlockSpec((1, 1, page_size), lambda b, j: (b // KV, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),     # running max m
            pltpu.VMEM((G, 1), jnp.float32),     # running sum l
            pltpu.VMEM((G, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        name="flash_decode",
    )(qr, kr, vr, bias.astype(jnp.float32))
    return out.reshape(B, KV, G, hd)
