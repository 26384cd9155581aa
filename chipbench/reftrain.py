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
it); after the last step, the norm of each path's change from its
initial value; and the paths that ``update`` sets.

A reference module gives ``EMBED_PATH``, ``HEAD_PATHS``,
``param_specs(m)``, ``block(m, p, x, ops, stack)`` and
``head_loss_sum``.  It may also give:

- ``stacks(m)``: its layer stacks in order, ``[(path prefix, layer
  count), ...]``; without it, ``[("blocks/", num_layers)]``.  Layer i
  of the whole model takes the leaves under its stack's prefix at its
  index within that stack, lives on the device of i over the total
  count, and its ``block`` gets the stack's prefix as ``stack``.  A
  parameter in no stack that is neither the embedding nor the head is
  an error: nothing would compute it.
- a ``block`` that returns ``(x, extras)``: ``extras["loss"]``, the
  layer's loss term for the one sequence it was given (the loss is the
  mean token cross entropy plus the mean over the batch's sequences of
  these terms); ``extras["stats"]``, a pytree that is not
  differentiated, summed over the batch's sequences.
- ``update(m, stack, layer_params, stats)``: each step, from a layer's
  summed ``stats`` and its parameters as the step found them, new
  values of the leaves that no gradient trains (a router's selection
  bias).  Those leaves take them in place of an AdamW step.
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


def _parts(out):
    """A block's (output, extras); a block may return its output alone."""
    return out if isinstance(out, tuple) else (out, {})


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
        self.stacks = ref.stacks(model) if hasattr(ref, "stacks") \
            else [(BLOCKS, model["num_layers"])]
        # layer i of the whole model: (its stack's prefix, index in it)
        self.layers = [(prefix, j) for prefix, n in self.stacks
                       for j in range(n)]
        self.L = len(self.layers)
        self.stages = stages
        self.specs = ref.param_specs(model)
        self.stack_of = {p: self._stack_of(p) for p in self.specs}
        ops = Ops(quant)

        def blk(p, x, stack):
            return _parts(ref.block(model, p, x, ops, stack))

        def out_and_loss(p, x, stack):
            # a layer with no loss term adds a constant: no cotangent
            x, extras = blk(p, x, stack)
            return x, extras.get("loss", jnp.float32(0))

        self._fwd = jax.jit(blk, static_argnums=2)
        self._bwd = jax.jit(lambda p, x, g, stack: jax.vjp(
            lambda p, x: out_and_loss(p, x, stack), p, x)[1](g),
            static_argnums=3)
        self._update = jax.jit(lambda p, s, stack: ref.update(
            model, stack, p, s), static_argnums=2)
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

    def _stack_of(self, path: str) -> Optional[str]:
        """The prefix of the one stack that holds ``path``; None for the
        embedding and the head."""
        hits = [prefix for prefix, _ in self.stacks
                if path.startswith(prefix)]
        if len(hits) == 1:
            return hits[0]
        if not hits and (path == self.ref.EMBED_PATH
                         or path in self.ref.HEAD_PATHS):
            return None
        raise ValueError(
            f"parameter {path!r} lies in {len(hits)} of the layer stacks "
            f"{[p for p, _ in self.stacks]} and is neither the embedding "
            f"nor the head: nothing would compute it")

    # -- state ----------------------------------------------------------
    def init(self, key) -> None:
        self.key = key
        flat = jax.jit(lambda k: weights.generate(self.specs, k))(key)
        self.master: Dict = {}
        for path, leaf in flat.items():
            prefix = self.stack_of[path]
            if prefix is not None:
                for l, (p, j) in enumerate(self.layers):
                    if p == prefix:
                        self.master[(path, j)] = jax.device_put(
                            self._layer(leaf, j), self.dev(l))
            else:
                self.master[(path, None)] = jax.device_put(
                    self._f32(leaf), self._global_dev(path))
        del flat
        self.mom = {k: jnp.zeros_like(v) for k, v in self.master.items()}
        self.vel = {k: jnp.zeros_like(v) for k, v in self.master.items()}

    def layer_params(self, l: int) -> Dict:
        prefix, j = self.layers[l]
        return {p[len(prefix):]: v for (p, i), v in self.master.items()
                if i == j and self.stack_of[p] == prefix}

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
        """(loss, {(path, index in stack): grad}, {layer: stats}) of the
        mean next-token loss plus the mean of the layers' loss terms."""
        rows, seq = tokens.shape
        n = jnp.float32(rows * (seq - 1))
        first = 0
        if fault == "no_exchange":
            # every stage but the last computes on nothing it received:
            # only the last stage's layers stand between embedding and loss
            first = self.L - self.L // self.stages
        layers = range(first, self.L)
        tok_table = self.master[(self.ref.EMBED_PATH, None)]
        saved, terms, stats = [], [], {}
        for r in range(rows):
            toks = jax.device_put(tokens[r:r + 1], self.embed_dev)
            x = self._embed(tok_table, toks)
            xs = []                       # each layer's input, on its device
            for l in layers:
                x = jax.device_put(x, self.dev(l))
                xs.append(x)
                x, extras = self._fwd(self.layer_params(l), x,
                                      self.layers[l][0])
                if "loss" in extras:
                    terms.append(jax.device_put(extras["loss"],
                                                self.head_dev))
                if "stats" in extras:
                    stats[l] = extras["stats"] if l not in stats \
                        else _add(stats[l], extras["stats"])
            saved.append((toks, xs, x))
        grads: Dict = {}

        def acc(key, g):
            grads[key] = g if key not in grads else _add(grads[key], g)

        # each sequence's loss terms enter the loss over the rows
        dterm = jnp.float32(1.0 / rows)
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
                prefix, j = self.layers[l]
                gp, gx = self._bwd(self.layer_params(l), xs[i],
                                   (jax.device_put(gx, self.dev(l)), dterm),
                                   prefix)
                for sub, g in gp.items():
                    acc((prefix + sub, j), g)
            acc((self.ref.EMBED_PATH, None), jax.device_put(
                self._embed_bwd(tok_table, toks,
                                jax.device_put(gx, self.embed_dev)),
                self._global_dev(self.ref.EMBED_PATH)))
            saved[r] = None
        for key, v in self.master.items():
            if key not in grads:                  # layers a fault cut off
                grads[key] = jnp.zeros_like(v)
        if terms:
            loss = loss + sum(terms) / rows
        return loss, grads, stats

    def updates(self, stats: Dict) -> Dict:
        """{(path, index in stack): value} of the leaves that ``update``
        sets from each layer's stats of this step."""
        new: Dict = {}
        for l, s in stats.items():
            prefix, j = self.layers[l]
            for sub, v in self._update(self.layer_params(l), s,
                                       prefix).items():
                if (prefix + sub, j) not in self.master:
                    raise KeyError(f"update sets {prefix + sub!r}, which "
                                   f"is no parameter of layer {l}")
                new[(prefix + sub, j)] = v
        return new

    def train(self, traffic: Dict, seed: int, opt: Dict, steps: int,
              fault: Optional[str] = None) -> Dict:
        if fault not in (None,) + FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        vocab = self.m["vocab_size"]
        losses: List[float] = []
        grad_norms: Dict[str, float] = {}
        updated = set()
        for t in range(steps):
            tokens = traffic_lib.batch_tokens(traffic, vocab, seed, t)
            if fault == "half_batch":
                tokens = half_batch(tokens)
            loss, grads, stats = self.grads(tokens, fault)
            new = self.updates(stats)
            updated.update(p for p, _ in new)
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
                if k in new:
                    self.master[k] = new.pop(k)
                    grads.pop(k)
                    continue
                self.master[k], self.mom[k], self.vel[k] = _adam_donating(
                    self.master[k], self.mom[k], self.vel[k], grads.pop(k),
                    *(jnp.float32(s) for s in (scale, lr, b1, b2, bc1, bc2,
                                               opt["eps"],
                                               opt["weight_decay"])))
            losses.append(float(loss))
        return {"losses": losses, "grad": grad_norms, "gnorm": first_gnorm,
                "change": self.change_norms(), "updated": sorted(updated)}

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
