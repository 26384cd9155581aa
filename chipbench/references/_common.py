"""Plain float32 building blocks shared by the references.

Every matrix product goes through :class:`Ops`, at ``HIGHEST`` precision
(a TPU otherwise multiplies float32 in bfloat16).  ``Ops("fp8")`` is the
control, float8 training as a later change might bring it: the operands
and the result of every product rounded to float8 e4m3 with a per-tensor
scale, and the residual stream rounded the same way after each sublayer
(as the program keeps its activations in bfloat16); in the backward
pass, the cotangent of each of those, and so each gradient product's
operands and result, rounded to float8 e5m2 with a per-tensor scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# precision -> (forward format, backward format)
FORMATS = {"fp8": (jnp.float8_e4m3fn, jnp.float8_e5m2)}


def round_to(a, dtype):
    """``a`` rounded to the float8 ``dtype`` and back to float32, with a
    per-tensor scale that maps the largest magnitude to the format's
    largest."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
    # clipped, since amax / scale may round above the format's largest,
    # which a float8 without infinities turns into NaN
    return jnp.clip(a / scale, -top, top).astype(dtype).astype(
        jnp.float32) * scale


def _rounder(fwd, bwd):
    @jax.custom_vjp
    def q(a):
        return round_to(a, fwd)

    q.defvjp(lambda a: (q(a), None), lambda _, g: (round_to(g, bwd),))
    return q


class Ops:
    def __init__(self, quant: str = ""):
        if quant and quant not in FORMATS:
            raise ValueError(f"unknown control precision {quant!r}")
        self.quant = quant
        self.q = _rounder(*FORMATS[quant]) if quant else (lambda a: a)

    def act(self, x):
        """The residual stream after a sublayer, as the precision keeps it."""
        return self.q(x)

    def mm(self, a, b):
        return self.q(jnp.matmul(self.q(a), self.q(b), precision=HIGHEST))

    def einsum(self, spec, a, b):
        return self.q(jnp.einsum(spec, self.q(a), self.q(b),
                                 precision=HIGHEST))


def rmsnorm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def cross_entropy_sum(logits, tokens):
    """Next-token CE summed over every position but the last of each row."""
    logz = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    tgt = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(logz - tgt)
