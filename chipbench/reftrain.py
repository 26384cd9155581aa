"""Drives a plain reference through a cell's first training steps.

The reference regenerates the weights from the seed (``weights``) and
the batches from the traffic mix (``traffic``); it takes nothing from
the program.  It runs one layer at a time, each layer's float32 state
on the device the layer's index maps to (all on one device for a
one-chip cell), with the backward pass one layer at a time through
``jax.vjp`` on the saved layer input.  The optimizer is AdamW as the
configuration trains it: global-norm clipping, warm-up and cosine
schedule, bias correction, decoupled weight decay on every leaf.

It returns per step the loss; the global norm of the first gradient and,
per parameter path, its norm (before clipping, as the optimizer gets
it); and, after the last step, the norm of each path's change from its
initial value.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic as traffic_lib
from chipbench import weights
from chipbench.references._common import Ops

BLOCKS = "blocks/"
FAULTS = ("half_batch", "no_exchange")


def lr_at(opt: Dict, step: int) -> float:
    warm = opt["lr"] * (step + 1.0) / max(opt["warmup_steps"], 1)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return warm if step < opt["warmup_steps"] else opt["lr"] * cos


def half_batch(tokens: np.ndarray) -> np.ndarray:
    """The fault "half of the batch left out": the first half of the
    rows, or of the positions where there is one row."""
    rows, seq = tokens.shape
    return tokens[: rows // 2] if rows >= 2 else tokens[:, : seq // 2]


def _adam(master, m, v, g, scale, lr, b1, b2, bc1, bc2, eps, wd):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * master
    return master - lr * delta, m, v


_adam_donating = jax.jit(_adam, donate_argnums=(0, 1, 2))


@jax.jit
def _sumsq(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


@jax.jit
def _diff_sumsq(a, b):
    return jnp.sum(jnp.square(a - b.astype(jnp.float32)))


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)


class Reference:
    """One reference model, its float32 state placed layer by layer."""

    def __init__(self, ref, model: Dict, devices: Sequence, *,
                 quant: str = "", stages: int = 1):
        self.ref, self.m, self.devices = ref, model, list(devices)
        self.L = model["num_layers"]
        self.stages = stages
        self.specs = ref.param_specs(model)
        ops = Ops(quant)
        blk = lambda p, x: ref.block(model, p, x, ops)
        self._fwd = jax.jit(blk)
        self._bwd = jax.jit(lambda p, x, g: jax.vjp(blk, p, x)[1](g))
        self._head = jax.jit(lambda hp, x, toks, n: jax.value_and_grad(
            lambda hp, x: ref.head_loss_sum(model, hp, x, toks, ops) / n,
            argnums=(0, 1))(hp, x))
        self._embed = jax.jit(lambda tok, toks: tok[toks])
        self._embed_bwd = jax.jit(
            lambda tok, toks, g: jax.vjp(lambda t: t[toks], tok)[1](g)[0])
        self._f32 = jax.jit(lambda a: a.astype(jnp.float32))
        self._layer = jax.jit(lambda a, l: a[l].astype(jnp.float32))

    # -- placement ------------------------------------------------------
    def dev(self, layer: int):
        return self.devices[layer * len(self.devices) // self.L]

    @property
    def embed_dev(self):
        return self.devices[0]

    @property
    def head_dev(self):
        return self.devices[-1]

    def _global_dev(self, path: str):
        return self.embed_dev if path == self.ref.EMBED_PATH \
            else self.head_dev

    # -- state ----------------------------------------------------------
    def init(self, key) -> None:
        self.key = key
        flat = jax.jit(lambda k: weights.generate(self.specs, k))(key)
        self.master: Dict = {}
        for path, leaf in flat.items():
            if path.startswith(BLOCKS):
                for l in range(self.L):
                    self.master[(path, l)] = jax.device_put(
                        self._layer(leaf, l), self.dev(l))
            else:
                self.master[(path, None)] = jax.device_put(
                    self._f32(leaf), self._global_dev(path))
        del flat
        self.mom = {k: jnp.zeros_like(v) for k, v in self.master.items()}
        self.vel = {k: jnp.zeros_like(v) for k, v in self.master.items()}

    def layer_params(self, l: int) -> Dict:
        return {p[len(BLOCKS):]: v for (p, i), v in self.master.items()
                if i == l}

    def head_params(self) -> Dict:
        return {p: jax.device_put(self.master[(p, None)], self.head_dev)
                for p in self.ref.HEAD_PATHS}

    def free(self) -> None:
        for d in (self.master, self.mom, self.vel):
            for v in d.values():
                v.delete()
            d.clear()

    # -- one step ---------------------------------------------------------
    def grads(self, tokens: np.ndarray, fault: Optional[str]):
        """(loss, {(path, layer): grad}) of the mean next-token loss."""
        rows, seq = tokens.shape
        n = jnp.float32(rows * (seq - 1))
        first = 0
        if fault == "no_exchange":
            # every stage but the last computes on nothing it received:
            # only the last stage's layers stand between embedding and loss
            first = self.L - self.L // self.stages
        layers = range(first, self.L)
        tok_table = self.master[(self.ref.EMBED_PATH, None)]
        saved = []
        for r in range(rows):
            toks = jax.device_put(tokens[r:r + 1], self.embed_dev)
            x = self._embed(tok_table, toks)
            xs = []                       # each layer's input, on its device
            for l in layers:
                x = jax.device_put(x, self.dev(l))
                xs.append(x)
                x = self._fwd(self.layer_params(l), x)
            saved.append((toks, xs, x))
        grads: Dict = {}

        def acc(key, g):
            grads[key] = g if key not in grads else _add(grads[key], g)

        loss = 0.0
        hp = self.head_params()
        for r in range(rows):
            toks, xs, out = saved[r]
            htoks = jax.device_put(toks, self.head_dev)
            ls, (ghp, gx) = self._head(
                hp, jax.device_put(out, self.head_dev), htoks, n)
            loss = loss + ls
            for p, g in ghp.items():
                acc((p, None), jax.device_put(g, self._global_dev(p)))
            for i, l in reversed(list(enumerate(layers))):
                gp, gx = self._bwd(self.layer_params(l), xs[i],
                                   jax.device_put(gx, self.dev(l)))
                for sub, g in gp.items():
                    acc((BLOCKS + sub, l), g)
            acc((self.ref.EMBED_PATH, None), jax.device_put(
                self._embed_bwd(tok_table, toks,
                                jax.device_put(gx, self.embed_dev)),
                self._global_dev(self.ref.EMBED_PATH)))
            saved[r] = None
        for key, v in self.master.items():
            if key not in grads:                  # layers a fault cut off
                grads[key] = jnp.zeros_like(v)
        return loss, grads

    def train(self, traffic: Dict, seed: int, opt: Dict, steps: int,
              fault: Optional[str] = None) -> Dict:
        if fault not in (None,) + FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        vocab = self.m["vocab_size"]
        losses: List[float] = []
        grad_norms: Dict[str, float] = {}
        for t in range(steps):
            tokens = traffic_lib.batch_tokens(traffic, vocab, seed, t)
            if fault == "half_batch":
                tokens = half_batch(tokens)
            loss, grads = self.grads(tokens, fault)
            sumsq = {k: float(_sumsq(g)) for k, g in grads.items()}
            gnorm = math.sqrt(sum(sumsq.values()))
            scale = min(1.0, opt["grad_clip"] / (gnorm + 1e-9)) \
                if opt["grad_clip"] > 0 else 1.0
            if t == 0:
                grad_norms, first_gnorm = _by_path(sumsq, 1.0), gnorm
            lr = lr_at(opt, t)
            b1, b2 = opt["b1"], opt["b2"]
            bc1, bc2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
            for k in list(self.master):
                self.master[k], self.mom[k], self.vel[k] = _adam_donating(
                    self.master[k], self.mom[k], self.vel[k], grads.pop(k),
                    *(jnp.float32(s) for s in (scale, lr, b1, b2, bc1, bc2,
                                               opt["eps"],
                                               opt["weight_decay"])))
            losses.append(float(loss))
        return {"losses": losses, "grad": grad_norms, "gnorm": first_gnorm,
                "change": self.change_norms()}

    def change_norms(self) -> Dict[str, float]:
        sumsq: Dict = {}
        for path in sorted(self.specs):
            w0 = jax.jit(lambda k, p=path: weights.draw(self.specs, k, p))(
                self.key)
            for (p, l), v in self.master.items():
                if p != path:
                    continue
                init = w0[l] if l is not None else w0
                sumsq[(p, l)] = float(_diff_sumsq(
                    v, jax.device_put(init, v.devices().pop())))
            del w0
        return _by_path(sumsq, 1.0)


def _by_path(sumsq: Dict, scale: float) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for (p, _), s in sumsq.items():
        out[p] = out.get(p, 0.0) + s
    return {p: math.sqrt(s) * scale for p, s in out.items()}


def run(ref, model: Dict, devices: Sequence, traffic: Dict, seed: int,
        opt: Dict, steps: int, *, quant: str = "", stages: int = 1,
        fault: Optional[str] = None) -> Dict:
    """Readings of one reference run (see the module docstring)."""
    r = Reference(ref, model, devices, quant=quant, stages=stages)
    r.init(weights.seed_key(seed))
    try:
        return r.train(traffic, seed, opt, steps, fault)
    finally:
        r.free()
