"""One-chip (or data-parallel) GSPMD step, built as ``launch/train.py``
builds it: ``training.train_step.make_train_step`` under ``jax.jit``
with the ``sharding.rules`` shardings, the state donated, fed by the
``data.pipeline`` loader."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh

from chipbench import traffic as traffic_lib
from chipbench import weights
from repro.data import pipeline as data_pipeline
from repro.optim import adamw
from repro.sharding import ctx, rules
from repro.training import train_step as ts


class StepMode:
    def __init__(self, cfg, cell, specs, devices, opt):
        w, t = cell["workload"], cell["traffic"]
        self.cfg, self.specs = cfg, specs
        n = len(devices)
        self.mesh = Mesh(np.array(devices).reshape(n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        self.tokens_shape = (t["rows"], t["seq"])
        self.tokens_per_step = t["rows"] * t["seq"]
        with ctx.use_mesh(self.mesh):
            self.state_sh = rules.train_state_shardings(
                ts.abstract_train_state(cfg), self.mesh,
                hybrid=cfg.family == "hybrid")
            self.batch_sh = rules.batch_shardings(
                {"tokens": jax.ShapeDtypeStruct(self.tokens_shape,
                                                jnp.int32)}, self.mesh)
            step = ts.make_train_step(
                cfg, adamw.AdamWConfig(**opt),
                accum_steps=w.get("accum", 1), backend=w["backend"])
            self.step_fn = jax.jit(step, in_shardings=(self.state_sh,
                                                       self.batch_sh),
                                   out_shardings=(self.state_sh, None),
                                   donate_argnums=(0,))
            self._init = jax.jit(self._make_state,
                                 out_shardings=self.state_sh)
        self.compiled = None

    def initial_params(self, key):
        return weights.nest(weights.generate(self.specs, key))

    def _make_state(self, key):
        params = self.initial_params(key)
        return ts.TrainState(params=params,
                             opt_state=adamw.init_opt_state(params),
                             step=jnp.zeros((), jnp.int32))

    def init(self, key):
        with ctx.use_mesh(self.mesh):
            return self._init(key)

    def compile(self, state) -> None:
        batch = {"tokens": jax.ShapeDtypeStruct(
            self.tokens_shape, jnp.int32,
            sharding=self.batch_sh["tokens"])}
        with ctx.use_mesh(self.mesh):
            self.compiled = self.step_fn.lower(state, batch).compile()

    def loader(self, traffic, vocab, seed):
        return data_pipeline.DataLoader(
            traffic_lib.TokenSource(traffic, vocab, seed), self.batch_sh,
            prefetch=2)

    def step(self, state, batch):
        return self.compiled(state, batch)

    @staticmethod
    def opt_state(state):
        return state.opt_state
