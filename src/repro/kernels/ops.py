"""Jit'd public wrappers for the Pallas kernels.

Models call these through ``backend="pallas"``; on non-TPU hosts the kernels
execute in interpret mode (same kernel body, Python evaluation) so the whole
model path is testable on CPU.  Wrappers handle GQA expansion, sequence
padding to block multiples, and dtype plumbing.

Training kernels (``flash_attention``, ``ssd_scan``, ``rmsnorm``) carry a
``custom_vjp``: forward runs the Pallas kernel, backward differentiates a
jnp form of it (recompute-style, XLA-fused) — the ``ref.py`` oracle for
``flash_attention`` and ``rmsnorm``, the chunked SSD of ``ssd_chunked.py``
(the kernel's own algorithm, as matmuls over chunks) for ``ssd_scan`` — so
``jax.grad`` through a ``backend="pallas"`` model works without a
hand-written backward kernel.
``flash_decode`` is inference-only and defines no VJP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import constraints as _con
from . import flash_attention as _fa
from . import flash_decode as _fd
from . import ref as _ref
from . import rmsnorm as _rn
from . import ssd_scan as _ssd
from .ssd_chunked import ssd_chunked
from ..obs import scopes

NEG_INF = _ref.NEG_INF


def _is_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except RuntimeError:  # pragma: no cover
        return False


def preferred_backend() -> str:
    """What ``backend="auto"`` should execute: the Pallas kernels on a
    real TPU, the einsum/chunked jnp paths elsewhere (interpret-mode
    Pallas is a CORRECTNESS tool, far too slow to be a CPU default).
    The single probe point the model dispatch sites share — tests
    monkeypatch this to steer ``auto`` without faking the jax backend."""
    return "pallas" if _is_tpu() else "einsum"


def _pad_seq(x, multiple, axis):
    S = x.shape[axis]
    pad = (-S) % multiple
    if not pad:
        return x, S
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), S


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fa_core(q, k, v, causal, window, q_offset, bq, bk):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, block_q=bq, block_k=bk,
                               interpret=not _is_tpu())


def _fa_core_fwd(q, k, v, causal, window, q_offset, bq, bk):
    return _fa_core(q, k, v, causal, window, q_offset, bq, bk), (q, k, v)


def _fa_core_bwd(causal, window, q_offset, bq, bk, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: _ref.attention_ref(q, k, v, causal=causal,
                                           window=window,
                                           q_offset=q_offset), q, k, v)
    return vjp(g)


_fa_core.defvjp(_fa_core_fwd, _fa_core_bwd)


@jax.named_scope(scopes.ATTENTION_CORE)
@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0):
    """q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd) — expands GQA internally."""
    H = q.shape[2]
    if k.shape[2] != H:
        rep = H // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    bq = min(_fa.DEFAULT_BLOCK_Q, max(q.shape[1], 1))
    bk = min(_fa.DEFAULT_BLOCK_K, max(k.shape[1], 1))
    if not causal:
        # padded k rows would win the softmax (no causal bound masks
        # them) — shrink the k block to a divisor of Sk instead of
        # padding (non-causal callers: cross-attention, encoders); the
        # rule lives in the jax-free constraints module so the plan
        # verifier lints against the same legalization
        bk = _con.shrink_block_k(k.shape[1], bk)
    q, Sq = _pad_seq(q, bq, 1)
    k, Sk = _pad_seq(k, bk, 1)
    v, _ = _pad_seq(v, bk, 1)
    # causal: padded k rows sit at positions > every real q position, so
    # the causal bound masks them; padded q rows are sliced off below
    out = _fa_core(q, k, v, causal, window, q_offset, bq, bk)
    return out[:, :Sq]


@functools.partial(jax.jit,
                   static_argnames=("window", "softcap", "ring",
                                    "page_size"))
def flash_decode(q, k, v, pos, *, window=0, softcap=0.0, ring=False,
                 page_size=_fd.DEFAULT_PAGE):
    """Single-token decode attention against the resident KV cache.

    q: (B, 1, H, hd) or (B, H, hd) — the current token's query heads;
    k/v: (B, KV, S, hd) cache layout (NOT transposed — the kernel
    streams the cache in place); pos: traced scalar int32 position.
    ``ring=True`` applies the sliding-window ring-buffer slot→position
    mapping (long_500k).  Handles GQA grouping, sublane padding of
    small groups, and padding S up to the page size (padded slots are
    masked through the bias, so they can never win the softmax).
    Returns (B, H, hd)."""
    if q.ndim == 4:
        q = q[:, 0]
    B, H, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    gpad = (-G) % _fd.MIN_GROUP
    if gpad:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gpad), (0, 0)))

    k_pos = _ref.decode_slot_positions(pos, S, ring=ring)
    valid = (k_pos >= 0) & (k_pos <= pos)
    if window:
        valid = valid & (k_pos > pos - window)
    bias = jnp.where(valid, 0.0, NEG_INF)[None, None, :]   # (1, 1, S)
    bias = jnp.broadcast_to(bias, (B, 1, S))
    spad = (-S) % page_size
    if spad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, spad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, spad), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, spad)),
                       constant_values=NEG_INF)
    out = _fd.flash_decode(qg, k, v, bias, softcap=softcap,
                           page_size=page_size, interpret=not _is_tpu())
    return out[:, :, :G].reshape(B, H, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_core(x, dt, A, Bm, Cm, chunk):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         interpret=not _is_tpu())


def _ssd_core_fwd(x, dt, A, Bm, Cm, chunk):
    return _ssd_core(x, dt, A, Bm, Cm, chunk), (x, dt, A, Bm, Cm)


def _ssd_core_bwd(chunk, res, g):
    x, dt, A, Bm, Cm = res
    # backward through the chunked form the kernel runs (same chunk):
    # chunk x chunk matmuls and a recurrence over S / chunk chunk states,
    # in float32; g carries the cotangents of y and final_state
    _, vjp = jax.vjp(lambda *a: ssd_chunked(*a, chunk), x, dt, A, Bm, Cm)
    return vjp(g)


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


@jax.named_scope(scopes.SSD_CORE)
@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128, initial_state=None):
    """Chunked SSD; signature mirrors ssd_chunked.ssd_chunked."""
    del initial_state  # kernel starts from zero state (prefill/train path)
    return _ssd_core(x, dt, A, Bm, Cm, chunk)


@jax.custom_vjp
def _rn_core(x, scale):
    return _rn.rmsnorm(x, scale, interpret=not _is_tpu())


def _rn_core_fwd(x, scale):
    return _rn_core(x, scale), (x, scale)


def _rn_core_bwd(res, g):
    x, scale = res
    _, vjp = jax.vjp(_ref.rmsnorm_ref, x, scale)
    return vjp(g)


_rn_core.defvjp(_rn_core_fwd, _rn_core_bwd)


@jax.jit
def rmsnorm(x, scale):
    return _rn_core(x, scale)
