"""A toy reference with layers of two kinds, for the harness's tests: one
leading dense layer (SwiGLU MLP of width ``d_ff``), then sparse-expert
layers (``n_experts`` SwiGLU experts of width ``expert_ff``, sigmoid
scores, top ``top_k`` picked by score plus a selection bias that no
gradient trains, gates normalised over the picked).  Each expert layer
returns, beside its output, the sequence-wise balance loss of its
sequences (mean over them) and the number of tokens each expert took
(``stats``); ``update`` moves the selection bias towards even load.
It is no published model and no program computes it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references._common import Ops, cross_entropy_sum, rmsnorm

EMBED_PATH = "embed/tok"
HEAD_PATHS = ("final_norm/scale", "embed/head")
DENSE, MOE = "dense_blocks/", "moe_blocks/"

MODEL = dict(d_model=16, vocab_size=48, dense_layers=1, moe_layers=2,
             d_ff=24, n_experts=4, expert_ff=8, top_k=2, aux_alpha=0.1,
             bias_rate=0.01, dtype="float32")


def stacks(m):
    return [(DENSE, m["dense_layers"]), (MOE, m["moe_layers"])]


def param_specs(m):
    d, V, ff = m["d_model"], m["vocab_size"], m["d_ff"]
    Ld, Lm, E, eff = (m["dense_layers"], m["moe_layers"], m["n_experts"],
                      m["expert_ff"])
    mat, f32, fan = m["dtype"], "float32", ("fan_in",)
    return {
        "embed/tok": ((V, d), mat, ("normal", 0.5)),
        "embed/head": ((d, V), mat, fan),
        "final_norm/scale": ((d,), f32, ("ones",)),
        DENSE + "ln/scale": ((Ld, d), f32, ("ones",)),
        DENSE + "mlp/wi": ((Ld, d, ff), mat, fan),
        DENSE + "mlp/wg": ((Ld, d, ff), mat, fan),
        DENSE + "mlp/wo": ((Ld, ff, d), mat, fan),
        MOE + "ln/scale": ((Lm, d), f32, ("ones",)),
        MOE + "router/w": ((Lm, d, E), mat, fan),
        MOE + "router/bias": ((Lm, E), f32, ("zeros",)),
        MOE + "experts/wi": ((Lm, E, d, eff), mat, fan),
        MOE + "experts/wg": ((Lm, E, d, eff), mat, fan),
        MOE + "experts/wo": ((Lm, E, eff, d), mat, fan),
    }


def _swiglu(h, wi, wg, wo, ops: Ops):
    return ops.mm(jax.nn.silu(ops.mm(h, wg)) * ops.mm(h, wi), wo)


def block(m, p, x, ops: Ops, stack):
    """One layer of ``stack`` on x (B, S, d); an expert layer returns
    ``(x, {"loss": mean over the B sequences, "stats": {"load"}})``."""
    h = rmsnorm(x, p["ln/scale"])
    if stack == DENSE:
        return ops.act(x + _swiglu(h, p["mlp/wi"], p["mlp/wg"],
                                   p["mlp/wo"], ops))
    E, k = m["n_experts"], m["top_k"]
    scores = jax.nn.sigmoid(ops.mm(h, p["router/w"]))          # (B,S,E)
    _, picked = jax.lax.top_k(scores + p["router/bias"], k)
    chosen = jax.nn.one_hot(picked, E).sum(-2)                 # (B,S,E)
    gates = scores * chosen
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    y = sum(gates[..., e:e + 1] * _swiglu(
        h, p["experts/wi"][e], p["experts/wg"][e], p["experts/wo"][e], ops)
        for e in range(E))
    # sequence-wise balance: alpha * sum_e f_e P_e, f_e the share of the
    # sequence's picks that went to e times E / k, P_e its mean score
    S = x.shape[1]
    f = jnp.sum(chosen, 1) * E / (k * S)                       # (B,E)
    prob = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), 1)
    loss = m["aux_alpha"] * jnp.mean(jnp.sum(f * prob, -1))
    return ops.act(x + y), {"loss": loss,
                            "stats": {"load": jnp.sum(chosen, (0, 1))}}


def update(m, stack, p, stats):
    """The selection bias moves by ``bias_rate`` towards the mean load."""
    load = stats["load"]
    return {"router/bias": p["router/bias"] + m["bias_rate"] * jnp.sign(
        jnp.mean(load) - load)}


def head_loss_sum(m, hp, x, tokens, ops: Ops):
    h = rmsnorm(x, hp["final_norm/scale"])
    return cross_entropy_sum(ops.mm(h, hp["embed/head"]), tokens)
