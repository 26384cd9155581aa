"""Mamba2 (SSD — state-space duality) block, chunked-parallel + recurrent.

Training/prefill uses the chunked SSD form of arXiv:2405.21060 (quadratic
within a chunk, linear across chunks), ``repro.kernels.ssd_chunked``, or
the Pallas kernel ``repro.kernels.ssd_scan`` that runs the same algorithm;
decode is the O(1) recurrent update.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers
from ..kernels import ssd_chunked as _ssd_chunked
from ..obs import scopes
from ..sharding.ctx import constrain


def init_ssm(key, cfg, dtype=jnp.bfloat16):
    d = cfg.d_model
    dinner, ng, st = cfg.ssm_dinner, cfg.ssm_ngroups, cfg.ssm_state
    nh = cfg.ssm_nheads
    conv_dim = dinner + 2 * ng * st
    ks = jax.random.split(key, 4)
    in_dim = 2 * dinner + 2 * ng * st + nh
    p = {
        "in_proj": layers.dense_init(ks[0], (d, in_dim), 0, dtype),
        "conv_w": layers.dense_init(ks[1], (cfg.ssm_conv_width, conv_dim), 0, dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": layers.init_norm("rmsnorm", dinner),
        "out_proj": layers.dense_init(ks[3], (dinner, d), 0, dtype),
    }
    return p


def _split_in_proj(cfg, zxbcdt):
    dinner, ng, st, nh = (cfg.ssm_dinner, cfg.ssm_ngroups, cfg.ssm_state,
                          cfg.ssm_nheads)
    z = zxbcdt[..., :dinner]
    x = zxbcdt[..., dinner:2 * dinner]
    Bm = zxbcdt[..., 2 * dinner:2 * dinner + ng * st]
    Cm = zxbcdt[..., 2 * dinner + ng * st:2 * dinner + 2 * ng * st]
    dt = zxbcdt[..., -nh:]
    return z, x, Bm, Cm, dt


def _causal_conv(u, w, b):
    """Depthwise causal conv. u: (B, S, C); w: (W, C)."""
    W = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(u)
    for i in range(W):
        out = out + pad[:, i:i + u.shape[1], :] * w[i]
    return out + b


# the einsum path's chunked SSD, under the layer's scope
ssd_chunked = jax.named_scope(scopes.SSD_CORE)(_ssd_chunked.ssd_chunked)


def ssd_recurrent_step(state, x_t, dt_t, A, B_t, C_t):
    """One decode step.  state: (b,h,p,n); x_t: (b,h,p); dt_t: (b,h);
    B_t/C_t: (b,g,n).  Returns (y_t (b,h,p), new_state)."""
    h = x_t.shape[1]
    g = B_t.shape[1]
    rep = h // g
    Bh = jnp.repeat(B_t, rep, axis=1).astype(jnp.float32)      # (b,h,n)
    Ch = jnp.repeat(C_t, rep, axis=1).astype(jnp.float32)
    decay = jnp.exp(A[None, :] * dt_t).astype(jnp.float32)     # (b,h)
    xd = (x_t * dt_t[..., None]).astype(jnp.float32)
    new_state = state * decay[..., None, None] + \
        jnp.einsum("bhp,bhn->bhpn", xd, Bh)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y, new_state


# ---------------------------------------------------------------------------
# full Mamba2 block
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.SSD)
def mamba2_forward(params, cfg, u, *, initial_state=None, backend="auto"):
    """u: (B, S, d) -> (y (B, S, d), final ssm state)."""
    B, S, d = u.shape
    dinner, nh, hp = cfg.ssm_dinner, cfg.ssm_nheads, cfg.ssm_headdim
    ng, st = cfg.ssm_ngroups, cfg.ssm_state
    zxbcdt = u @ params["in_proj"]
    z, x, Bm, Cm, dt = _split_in_proj(cfg, zxbcdt)
    # SSM layout (DESIGN.md / §Perf hillclimb B): the depthwise conv is
    # channel-local and the SSD scan is head-local, so shard CHANNELS/HEADS
    # over `model` and keep the sequence dim unsharded — seq sharding here
    # costs halo collective-permutes per conv shift and all-to-alls per
    # chunk-boundary reshape.  The conv is depthwise, hence separable: run
    # it per segment so slice boundaries align with shard boundaries.
    x = constrain(x, "batch", None, "model")
    z = constrain(z, "batch", None, "model")
    BC = jnp.concatenate([Bm, Cm], axis=-1)               # (B, S, 2·ng·st)
    x = jax.nn.silu(_causal_conv(x, params["conv_w"][:, :dinner],
                                 params["conv_b"][:dinner]))
    BC = jax.nn.silu(_causal_conv(BC, params["conv_w"][:, dinner:],
                                  params["conv_b"][dinner:]))
    Bm = BC[..., : ng * st]
    Cm = BC[..., ng * st:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])

    xh = x.reshape(B, S, nh, hp)
    Bg = Bm.reshape(B, S, ng, st)
    Cg = Cm.reshape(B, S, ng, st)
    xh = constrain(xh, "batch", None, "heads", None)

    chunk = min(cfg.ssm_chunk, S)
    from ..kernels import ops as kops
    if backend == "auto" and initial_state is None \
            and kops.preferred_backend() == "pallas":
        # auto picks the Pallas SSD kernel on TPU (the kernel starts
        # from zero state, so a carried initial_state stays on jnp)
        backend = "pallas"
    if backend == "pallas":
        y, final = kops.ssd_scan(xh, dt, A, Bg, Cg, chunk=chunk,
                                 initial_state=initial_state)
    else:
        y, final = ssd_chunked(xh, dt, A, Bg, Cg, chunk, initial_state)
    y = y + xh.astype(jnp.float32) * params["D"][None, None, :, None]
    y = y.reshape(B, S, dinner).astype(u.dtype)

    y = layers.apply_norm(params["norm"], y * jax.nn.silu(z), "rmsnorm")
    return y @ params["out_proj"], final


def init_ssm_cache(cfg, batch, dtype=jnp.bfloat16):
    dinner, ng, st = cfg.ssm_dinner, cfg.ssm_ngroups, cfg.ssm_state
    conv_dim = dinner + 2 * ng * st
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
        "state": jnp.zeros((batch, cfg.ssm_nheads, cfg.ssm_headdim, st),
                           jnp.float32),
    }


def mamba2_decode_step(params, cfg, u, cache):
    """u: (B, 1, d); cache: {conv, state} -> (y (B,1,d), new cache)."""
    B = u.shape[0]
    dinner, nh, hp = cfg.ssm_dinner, cfg.ssm_nheads, cfg.ssm_headdim
    ng, st = cfg.ssm_ngroups, cfg.ssm_state
    zxbcdt = u[:, 0] @ params["in_proj"]                       # (B, in_dim)
    z, x, Bm, Cm, dt = _split_in_proj(cfg, zxbcdt)
    xBC = jnp.concatenate([x, Bm, Cm], axis=-1)                # (B, conv_dim)
    window = jnp.concatenate([cache["conv"], xBC[:, None]], axis=1)  # (B,W,conv)
    conv_out = jnp.sum(window * params["conv_w"][None], axis=1) + params["conv_b"]
    xBC = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]

    x = xBC[..., :dinner]
    Bm = xBC[..., dinner:dinner + ng * st]
    Cm = xBC[..., dinner + ng * st:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B, nh)
    A = -jnp.exp(params["A_log"])

    xh = x.reshape(B, nh, hp)
    Bg = Bm.reshape(B, ng, st)
    Cg = Cm.reshape(B, ng, st)
    y, new_state = ssd_recurrent_step(cache["state"], xh, dt, A, Bg, Cg)
    y = y + xh.astype(jnp.float32) * params["D"][None, :, None]
    y = y.reshape(B, dinner).astype(u.dtype)
    y = layers.apply_norm(params["norm"], y * jax.nn.silu(z), "rmsnorm")
    out = (y @ params["out_proj"])[:, None]
    return out, {"conv": new_conv, "state": new_state}
