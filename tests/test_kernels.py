"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode executes the kernel body on CPU), plus hypothesis property
tests on the invariants."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import attention_ref, rmsnorm_ref, ssd_ref
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,S,H,hd,bq,bk", [
    (2, 256, 4, 64, 64, 64),
    (1, 512, 2, 128, 128, 128),
    (2, 128, 3, 64, 32, 64),
    (1, 384, 1, 64, 128, 128),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, hd, bq, bk, causal, window, dtype):
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), dtype=dtype)
               for kk in jax.random.split(key, 3))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    assert err < TOL[dtype], err


def test_flash_attention_decode_offset():
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (2, 1, 4, 64))
    k, v = (jax.random.normal(kk, (2, 128, 4, 64))
            for kk in jax.random.split(key, 2))
    out = flash_attention(q, k, v, causal=True, q_offset=127,
                          block_q=1, block_k=64)
    ref = attention_ref(q, k, v, causal=True, q_offset=127)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_flash_attention_gqa_wrapper():
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (2, 128, 8, 64))
    k, v = (jax.random.normal(kk, (2, 128, 2, 64))
            for kk in jax.random.split(key, 2))
    out = ops.flash_attention(q, k, v, causal=True)
    ref = attention_ref(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                        causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


@given(st.sampled_from([32, 64, 128]), st.sampled_from([16, 32, 64]),
       st.integers(1, 3), st.sampled_from([8, 16]))
@settings(max_examples=12, deadline=None)
def test_ssd_scan_property(S, p, h, n):
    key = jax.random.PRNGKey(S * p + h)
    ks = jax.random.split(key, 5)
    b, g = 1, 1
    x = jax.random.normal(ks[0], (b, S, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, S, g, n)) * 0.3
    Cm = jax.random.normal(ks[4], (b, S, g, n)) * 0.3
    y, fin = ssd_scan(x, dt, A, Bm, Cm, chunk=min(32, S))
    yr, fr = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fr),
                               rtol=1e-3, atol=1e-4)


def test_ssd_matches_model_chunked_form():
    """Kernel oracle == the model's einsum-chunked SSD (two derivations)."""
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
    b, S, h, p, g, n = 2, 128, 4, 32, 2, 16
    x = jax.random.normal(ks[0], (b, S, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, S, g, n)) * 0.3
    Cm = jax.random.normal(ks[4], (b, S, g, n)) * 0.3
    y1, f1 = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    y2, f2 = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rows,d", [(64, 256), (128, 512), (37, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel(rows, d, dtype):
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (rows, d), dtype=dtype)
    s = jax.random.normal(jax.random.fold_in(key, 1), (d,), dtype=dtype)
    out = rmsnorm(x, s)
    ref = rmsnorm_ref(x, s)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    assert err < TOL[dtype]


# ---------------------------------------------------------------------------
# flash-decode: single-query paged attention vs its oracle
# ---------------------------------------------------------------------------

def _decode_inputs(key, B, H, kv, S, hd=64):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, kv, S, hd))
    v = jax.random.normal(ks[2], (B, kv, S, hd))
    return q, k, v


@pytest.mark.parametrize("kv", [1, 2, 8])        # GQA 8:1, 4:1, MHA
@pytest.mark.parametrize("S", [96, 128, 200, 300])
def test_flash_decode_oracle(kv, S):
    """KV lengths straddle the 128 page boundary and the lane tile
    (96/200/300 are not multiples of 128 — exercises ``_pad_seq`` +
    NEG_INF bias padding); kv sweeps the GQA group fold."""
    from repro.kernels.ref import decode_attention_ref
    q, k, v = _decode_inputs(jax.random.PRNGKey(S + kv), 2, 8, kv, S)
    for pos in (S - 1, S // 2):                  # full + partially-written
        out = ops.flash_decode(q, k, v, jnp.int32(pos))
        ref = decode_attention_ref(q, k, v, jnp.int32(pos))
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5, (kv, S, pos)


@pytest.mark.parametrize("kwargs,pos", [
    (dict(window=64), 199),                      # sliding window
    (dict(softcap=30.0), 199),                   # gemma-style logit cap
    (dict(window=200, ring=True), 237),          # ring buffer, wrapped
])
def test_flash_decode_variants(kwargs, pos):
    from repro.kernels.ref import decode_attention_ref
    S = 200 if not kwargs.get("ring") else 200
    q, k, v = _decode_inputs(jax.random.PRNGKey(pos), 2, 8, 2, S)
    out = ops.flash_decode(q, k, v, jnp.int32(pos), **kwargs)
    ref = decode_attention_ref(q, k, v, jnp.int32(pos), **kwargs)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_flash_decode_prefill_consistency():
    """Decode at position p == row p of the full prefill attention: the
    kernel's paged/bias masking agrees with the causal prefill mask."""
    B, H, kv, S, hd = 2, 8, 2, 130, 64
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, kv, hd))
    v = jax.random.normal(ks[2], (B, S, kv, hd))
    full = attention_ref(q, jnp.repeat(k, H // kv, 2),
                         jnp.repeat(v, H // kv, 2), causal=True)
    kc, vc = k.swapaxes(1, 2), v.swapaxes(1, 2)   # cache layout (B,KV,S,hd)
    for p in (0, 64, S - 1):
        out = ops.flash_decode(q[:, p], kc, vc, jnp.int32(p))
        assert float(jnp.max(jnp.abs(out - full[:, p]))) < 1e-5, p


def test_pallas_kernels_custom_vjp():
    """jax.grad through the Pallas wrappers == grad of the oracle (the
    custom_vjp backward differentiates ref.py, so pallas models train)."""
    key = jax.random.PRNGKey(4)
    q, k, v = (jax.random.normal(kk, (1, 128, 2, 64))
               for kk in jax.random.split(key, 3))
    g_pal = jax.grad(lambda q: ops.flash_attention(q, k, v).sum())(q)
    g_ref = jax.grad(lambda q: attention_ref(q, k, v).sum())(q)
    assert float(jnp.max(jnp.abs(g_pal - g_ref))) < 1e-4

    x = jax.random.normal(key, (32, 256))
    s = jnp.ones((256,))
    gx = jax.grad(lambda x: ops.rmsnorm(x, s).sum())(x)
    gr = jax.grad(lambda x: rmsnorm_ref(x, s).sum())(x)
    assert float(jnp.max(jnp.abs(gx - gr))) < 1e-4


def _ssd_inputs(b, S, h, g, p, n, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, S, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (b, S, g, n)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (b, S, g, n)) * 0.3).astype(dtype)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("b,S,h,g,p,n,chunk", [
    (2, 64, 4, 1, 16, 16, 32),       # one group over 4 heads
    (1, 64, 6, 3, 8, 16, 32),        # 3 groups of 2 heads
    (1, 256, 2, 1, 16, 8, 32),       # 8 chunks
    (1, 64, 4, 2, 16, 16, 64),       # S is one chunk
], ids=["g1_heads", "groups", "chunks", "one_chunk"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_custom_vjp(b, S, h, g, p, n, chunk, dtype):
    """jax.grad through ops.ssd_scan (its backward differentiates the
    chunked form) == grad of the sequential oracle, with cotangents on
    both y and final_state.  bf16: the oracle sums the heads' dB and dC
    in bf16, so it is held to 2e-2 of the largest cotangent, and the
    rule, which sums in float32, to bf16 rounding of the float32
    oracle's cotangents."""
    args = _ssd_inputs(b, S, h, g, p, n, dtype, seed=S + h + g)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    wy = jax.random.normal(ks[0], (b, S, h, p))
    wf = jax.random.normal(ks[1], (b, h, p, n))

    def loss(fn):
        def f(*a):
            y, fin = fn(*a)
            return jnp.sum(y * wy) + jnp.sum(fin * wf)
        return f

    grad = functools.partial(jax.grad, argnums=(0, 1, 2, 3, 4))
    got = grad(loss(lambda *a: ops.ssd_scan(*a, chunk=chunk)))(*args)
    ref = grad(loss(ssd_ref))(*args)
    names = ("x", "dt", "A", "Bm", "Cm")
    for name, a, r, arg in zip(names, got, ref, args):
        assert a.dtype == arg.dtype, name
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        scale = float(np.max(np.abs(r)))
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, r, rtol=1e-4, atol=1e-5 * scale,
                                       err_msg=name)
        else:
            assert float(np.max(np.abs(a - r))) <= 2e-2 * scale, name
    if dtype == jnp.bfloat16:
        f32 = [a.astype(jnp.float32) for a in args]
        exact = grad(loss(ssd_ref))(*f32)
        for name, a, r in zip(names, got, exact):
            r = np.asarray(r)
            np.testing.assert_allclose(
                np.asarray(a, np.float32), r, rtol=2 ** -8,
                atol=1e-5 * float(np.max(np.abs(r))), err_msg=name)


def test_ssd_scan_backward_is_chunked():
    """The backward must not stack a state per time step: at S 1024,
    8 heads of 64 x 128 the per-step states would take S·h·p·n·4 B
    (268 MB); the compiled gradient's temporaries stay under a quarter
    of that."""
    b, S, h, p, n, chunk = 1, 1024, 8, 64, 128, 128
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in
            jax.eval_shape(lambda: _ssd_inputs(b, S, h, 1, p, n,
                                               jnp.float32))]

    def loss(*a):
        y, fin = ops.ssd_scan(*a, chunk=chunk)
        return y.sum() + fin.sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    temp = step.lower(*args).compile().memory_analysis().temp_size_in_bytes
    assert temp < S * h * p * n * 4 // 4, temp


# ---------------------------------------------------------------------------
# backend="auto" resolution (the probe the dispatch sites share)
# ---------------------------------------------------------------------------

def test_preferred_backend_probe(monkeypatch):
    monkeypatch.setattr(ops, "_is_tpu", lambda: True)
    assert ops.preferred_backend() == "pallas"
    monkeypatch.setattr(ops, "_is_tpu", lambda: False)
    assert ops.preferred_backend() == "einsum"


def test_auto_resolves_to_pallas_on_tpu(monkeypatch):
    """Regression: ``auto`` must reach the kernels when the probe says
    TPU (it used to fall through to einsum everywhere).  Monkeypatching
    ``preferred_backend`` — NOT ``_is_tpu`` — keeps interpret mode on,
    so the kernels still execute on CPU."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models import attention as A
    calls = []
    real = ops.flash_decode
    monkeypatch.setattr(ops, "preferred_backend", lambda: "pallas")
    monkeypatch.setattr(
        ops, "flash_decode",
        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])
    cfg = dataclasses.replace(get_smoke_config("granite_8b"),
                              dtype="float32")
    params = A.init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    cache = A.init_kv_cache(cfg, 2, 64, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.d_model))
    out, _ = A.decode_self_attention(params, cfg, x, cache, jnp.int32(5),
                                     backend="auto")
    assert calls, "auto did not route decode to the pallas kernel"
    ref, _ = A.decode_self_attention(params, cfg, x, cache, jnp.int32(5),
                                     backend="einsum")
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


# ---------------------------------------------------------------------------
# end-to-end decode: pallas backend == einsum cache path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["granite_8b", "zamba2_2p7b"])
def test_decode_step_pallas_matches_einsum(arch):
    import dataclasses
    from conftest import make_batch
    from repro.configs import get_smoke_config
    from repro.models import model as M
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    batch = make_batch(cfg, key, 2, 16)
    outs = {}
    for be in ("einsum", "pallas"):
        cache, logits, plen = M.prefill(params, cfg, batch, cache_len=32,
                                        backend=be)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        step, _ = M.decode_step(params, cfg, tok, cache, jnp.int32(plen),
                                backend=be)
        outs[be] = step
    err = float(jnp.max(jnp.abs(outs["pallas"] - outs["einsum"])))
    assert err < 1e-3, err


def test_model_attention_pallas_backend_matches_auto():
    """End-to-end: model self-attention with backend='pallas' == jnp path."""
    import dataclasses
    from conftest import make_batch
    from repro.configs import get_smoke_config
    from repro.models import model as M
    cfg = dataclasses.replace(get_smoke_config("granite_8b"), dtype="float32")
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    batch = make_batch(cfg, key, 2, 128)
    ref, _ = M.forward(params, cfg, batch, remat=False, backend="auto")
    out, _ = M.forward(params, cfg, batch, remat=False, backend="pallas")
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-3
