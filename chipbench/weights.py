"""Weights made from the seed, the same for the program and the reference.

A configuration's reference module lists its parameters as specs:
``{path: (shape, dtype, init)}`` with ``init`` one of ``("normal", std)``,
``("fan_in",)`` (normal over the square root of the second-to-last dim),
``("ones",)``, ``("zeros",)``, ``("log_linspace", hi)`` (log of
1..hi spread over the last dim) or ``("softplus_inv_geomspace", lo, hi)``
(a bias under which softplus starts at lo..hi, spread geometrically
over the last dim).  Leaf ``path`` is drawn from ``fold_in(key, its
index in sorted path order)``, so one leaf can be drawn again alone.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Spec = Tuple[Tuple[int, ...], str, tuple]


def seed_key(seed: int):
    """A JAX key from any whole number the benchmark may be given."""
    word = np.random.SeedSequence(seed % (1 << 64)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def draw(specs: Dict[str, Spec], key, path: str) -> jnp.ndarray:
    shape, dtype, init = specs[path]
    index = sorted(specs).index(path)
    kind = init[0]
    if kind in ("normal", "fan_in"):
        std = init[1] if kind == "normal" else 1.0 / math.sqrt(shape[-2])
        x = jax.random.normal(jax.random.fold_in(key, index), shape,
                              jnp.float32) * std
    elif kind == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif kind == "zeros":
        x = jnp.zeros(shape, jnp.float32)
    elif kind == "log_linspace":
        x = jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, init[1], shape[-1], dtype=jnp.float32)),
            shape)
    elif kind == "softplus_inv_geomspace":
        v = jnp.exp(jnp.linspace(math.log(init[1]), math.log(init[2]),
                                 shape[-1], dtype=jnp.float32))
        x = jnp.broadcast_to(v + jnp.log(-jnp.expm1(-v)), shape)
    else:
        raise ValueError(f"unknown init {init!r} for {path}")
    return x.astype(dtype)


def generate(specs: Dict[str, Spec], key) -> Dict[str, jnp.ndarray]:
    """Every leaf, flat by path, in its spec dtype (trace under jit)."""
    return {p: draw(specs, key, p) for p in sorted(specs)}


def nest(flat: Dict[str, jnp.ndarray]) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *heads, last = path.split("/")
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = leaf
    return out


def flatten(tree) -> Dict[str, object]:
    """``{"a": {"b": x}}`` -> ``{"a/b": x}`` (dict trees only)."""
    out = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(k.key) for k in kp)] = leaf
    return out
