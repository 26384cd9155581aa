"""Plain reference of the Mamba2 block (arXiv:2405.21060): RMSNorm,
in-projection to (z, x, B, C, dt), depthwise causal conv + SiLU on
(x, B, C), the SSD recurrence written in its quadratic dual form with
exact segment sums, D skip, gated RMSNorm, out-projection; tied head,
next-token cross entropy.  float32 throughout; the SSD one head at a
time."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references._common import Ops, cross_entropy_sum, rmsnorm

EMBED_PATH = "embed/tok"
HEAD_PATHS = ("final_norm/scale", "embed/tok")
# a small model of this family, for the CPU test against the program
CPU_MODEL = dict(name="t", family="ssm", num_layers=2, d_model=64,
                 num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=128,
                 ssm_state=16, ssm_expand=2, ssm_headdim=16, ssm_ngroups=1,
                 ssm_conv_width=4, ssm_chunk=16, norm="rmsnorm",
                 tie_embeddings=True, max_seq_len=64, dtype="float32")


def _sizes(m):
    dinner = m["ssm_expand"] * m["d_model"]
    nh = dinner // m["ssm_headdim"]
    gn = m["ssm_ngroups"] * m["ssm_state"]
    return dinner, nh, gn


def param_specs(m):
    d, L, V, W = m["d_model"], m["num_layers"], m["vocab_size"], \
        m["ssm_conv_width"]
    dinner, nh, gn = _sizes(m)
    mat, f32 = m["dtype"], "float32"
    fan = ("fan_in",)
    return {
        "embed/tok": ((V, d), mat, ("normal", 0.02)),
        "final_norm/scale": ((d,), f32, ("ones",)),
        "blocks/ln1/scale": ((L, d), f32, ("ones",)),
        "blocks/ssm/in_proj": ((L, d, 2 * dinner + 2 * gn + nh), mat, fan),
        "blocks/ssm/conv_w": ((L, W, dinner + 2 * gn), mat, fan),
        "blocks/ssm/conv_b": ((L, dinner + 2 * gn), mat, ("zeros",)),
        "blocks/ssm/A_log": ((L, nh), f32, ("log_linspace", 16.0)),
        "blocks/ssm/D": ((L, nh), f32, ("ones",)),
        # dt starts in the published range, 1e-3 to 1e-1
        "blocks/ssm/dt_bias": ((L, nh), f32,
                               ("softplus_inv_geomspace", 1e-3, 1e-1)),
        "blocks/ssm/norm/scale": ((L, dinner), f32, ("ones",)),
        "blocks/ssm/out_proj": ((L, dinner, d), mat, fan),
    }


def causal_conv(u, w, b):
    """Depthwise causal conv; u (B,S,C), w (W,C)."""
    W, S = w.shape[0], u.shape[1]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(pad[:, i:i + S] * w[i] for i in range(W)) + b


def segsum(a):
    """(B, S) -> (B, S, S): out[t, s] = sum_{s < r <= t} a[r], -inf for
    s > t; summed directly, not as a difference of long cumulative sums."""
    S = a.shape[-1]
    strict = jnp.tril(jnp.ones((S, S), bool), -1)
    x = jnp.where(strict, a[..., :, None], 0.0)           # x[t', s] = a[t']
    seg = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), seg, -jnp.inf)


def ssd(x, dt, A, Bm, Cm, ops: Ops):
    """y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} A dt_r) dt_s x_s.
    x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N) (one group)."""
    CB = ops.einsum("btn,bsn->bts", Cm, Bm)

    def one_head(args):
        xh, dth, ah = args                        # (B,S,P), (B,S), ()
        L = jnp.exp(segsum(ah * dth))
        return ops.einsum("bts,bsp->btp", CB * L, xh * dth[..., None])

    heads = (x.transpose(2, 0, 1, 3), dt.transpose(2, 0, 1), A)
    return jax.lax.map(jax.checkpoint(one_head), heads).transpose(1, 2, 0, 3)


def block(m, p, x, ops: Ops, stack=None):
    B, S, d = x.shape
    dinner, nh, gn = _sizes(m)
    if m["ssm_ngroups"] != 1:
        raise NotImplementedError("the reference holds one B/C group")
    h = rmsnorm(x, p["ln1/scale"])
    zx = ops.mm(h, p["ssm/in_proj"])
    z, xc = zx[..., :dinner], zx[..., dinner:2 * dinner]
    bc = zx[..., 2 * dinner:2 * dinner + 2 * gn]
    dt = zx[..., 2 * dinner + 2 * gn:]
    xbc = jax.nn.silu(causal_conv(jnp.concatenate([xc, bc], -1),
                                  p["ssm/conv_w"], p["ssm/conv_b"]))
    xc, Bm, Cm = (xbc[..., :dinner], xbc[..., dinner:dinner + gn],
                  xbc[..., dinner + gn:])
    dt = jax.nn.softplus(dt + p["ssm/dt_bias"])
    A = -jnp.exp(p["ssm/A_log"])
    xh = xc.reshape(B, S, nh, m["ssm_headdim"])
    y = ssd(xh, dt, A, Bm, Cm, ops) + xh * p["ssm/D"][:, None]
    y = rmsnorm(y.reshape(B, S, dinner) * jax.nn.silu(z), p["ssm/norm/scale"])
    return ops.act(x + ops.mm(y, p["ssm/out_proj"]))


def head_loss_sum(m, hp, x, tokens, ops: Ops):
    h = rmsnorm(x, hp["final_norm/scale"])
    return cross_entropy_sum(ops.mm(h, hp["embed/tok"].T), tokens)

