"""AdamW with fp32 master weights (mixed-precision training).

The optimizer is a pure pytree transform; ZeRO-1 is realized at the sharding
layer (opt-state PartitionSpecs add the ``data`` axis — see
``repro.sharding.rules.opt_state_specs``), exactly mirroring the paper's
"ZeRO-1 enabled by default" setup.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..obs import scopes

PyTree = Any


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1


def lr_at(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = cfg.lr * (step + 1.0) / max(cfg.warmup_steps, 1)
    prog = jnp.clip((step - cfg.warmup_steps) /
                    max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.lr * cos)


def init_opt_state(params: PyTree) -> PyTree:
    # a copy even where the param is already fp32: a master that aliases
    # its param cannot be donated alongside it
    f32 = lambda p: jnp.array(p, jnp.float32, copy=True)
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "master": jax.tree.map(f32, params),
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
    }


def global_norm(tree: PyTree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


@jax.named_scope(scopes.OPTIMIZER)
def apply_update(opt_cfg: AdamWConfig, opt_state: PyTree, grads: PyTree,
                 step, params: PyTree, *,
                 grad_norm=None) -> tuple[PyTree, PyTree, dict]:
    """Returns (new params (model dtype), new opt_state, metrics).

    ``params`` is only used as the dtype reference for the bf16 cast.
    ``grad_norm`` overrides the locally computed global norm for the
    clip scale — callers running inside ``shard_map`` (the HeteroPP dp
    train step) pass the cross-device norm, since the local leaves there
    are shards/replicas whose naive norm would be wrong."""
    gnorm = global_norm(grads) if grad_norm is None else grad_norm
    scale = jnp.minimum(1.0, opt_cfg.grad_clip / (gnorm + 1e-9)) \
        if opt_cfg.grad_clip > 0 else 1.0
    lr = lr_at(opt_cfg, step)
    b1, b2 = opt_cfg.b1, opt_cfg.b2
    bc1 = 1 - b1 ** (step.astype(jnp.float32) + 1)
    bc2 = 1 - b2 ** (step.astype(jnp.float32) + 1)

    def upd(master, m, v, g):
        g = g.astype(jnp.float32) * scale
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m2 / bc1
        vhat = v2 / bc2
        delta = mhat / (jnp.sqrt(vhat) + opt_cfg.eps)
        if opt_cfg.weight_decay:
            delta = delta + opt_cfg.weight_decay * master
        return master - lr * delta, m2, v2

    out = jax.tree.map(upd, opt_state["master"], opt_state["m"],
                       opt_state["v"], grads)
    master = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
    m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
    v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda t: isinstance(t, tuple))
    new_params = jax.tree.map(lambda ms, p: ms.astype(p.dtype), master, params)
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, {"master": master, "m": m, "v": v}, metrics
