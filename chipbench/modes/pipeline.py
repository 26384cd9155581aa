"""HeteroPP ``shard_map`` pipeline step, built as ``launch/train.py``'s
``run_pipeline`` builds it for ``--pipeline-parallel N --schedule S``:
``core.heteropp.make_spmd_pipeline_train_step`` on a ``pipe`` mesh, the
``split_stage_params`` layout, a uniform layer split, the state donated,
fed by the ``data.pipeline`` loader."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import traffic as traffic_lib
from chipbench import weights
from repro.core import heteropp as HP
from repro.core.schedules import get_schedule
from repro.data import pipeline as data_pipeline
from repro.optim import adamw
from repro.sharding import rules


class StepMode:
    def __init__(self, cfg, cell, specs, devices, opt):
        w, t = cell["workload"], cell["traffic"]
        pw = w["pipeline"]
        self.cfg, self.specs = cfg, specs
        stages, mb = pw["stages"], pw["microbatches"]
        sched = get_schedule(pw["schedule"])
        base, rem = divmod(cfg.num_layers, stages)
        phys = [base + (1 if i < rem else 0) for i in range(stages)]
        self.spec = HP.PipelineSpec(
            stages, HP.chunk_layer_counts(phys, sched), microbatches=mb,
            schedule=sched.name, n_chunks=sched.n_chunks)
        self.mesh = Mesh(np.array(devices[:stages]), ("pipe",))
        if t["rows"] % mb:
            raise ValueError(f"{t['rows']} rows do not split into {mb} "
                             f"microbatches")
        self.tokens_shape = (mb, t["rows"] // mb, t["seq"])
        self.tokens_per_step = t["rows"] * t["seq"]
        aps = HP.abstract_stage_params(cfg, self.spec)
        blk = rules.stage_block_specs(
            aps["blocks"], pipe_axis="pipe", tp_axis=None,
            stacked_prefix=1 + (1 if self.spec.n_chunks == 1 else 2))
        rep = NamedSharding(self.mesh, P())
        sp = {"blocks": jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                     blk),
              "embed": jax.tree.map(lambda _: rep, aps["embed"]),
              "final_norm": jax.tree.map(lambda _: rep, aps["final_norm"])}
        self.state_sh = (sp, {"master": sp, "m": sp, "v": sp}, rep)
        self.mask_sh = NamedSharding(self.mesh, P("pipe"))
        self.batch_sh = {"tokens": rep}
        self.step_fn = jax.jit(HP.make_spmd_pipeline_train_step(
            cfg, self.spec, self.mesh, adamw.AdamWConfig(**opt)),
            donate_argnums=(0,))
        self._init = jax.jit(self._make_state,
                             out_shardings=(self.state_sh, self.mask_sh))
        self.compiled = self.mask = None

    def initial_params(self, key):
        params = weights.nest(weights.generate(self.specs, key))
        return HP.split_stage_params(params, self.cfg, self.spec)[0]

    def _make_state(self, key):
        params = weights.nest(weights.generate(self.specs, key))
        sp, mask = HP.split_stage_params(params, self.cfg, self.spec)
        return (sp, adamw.init_opt_state(sp), jnp.zeros((), jnp.int32)), mask

    def init(self, key):
        state, self.mask = self._init(key)
        return state

    def compile(self, state) -> None:
        batch = {"tokens": jax.ShapeDtypeStruct(
            self.tokens_shape, jnp.int32, sharding=self.batch_sh["tokens"])}
        self.compiled = self.step_fn.lower(state, self.mask, batch).compile()

    def loader(self, traffic, vocab, seed):
        shape = self.tokens_shape
        return data_pipeline.DataLoader(
            traffic_lib.TokenSource(traffic, vocab, seed,
                                    lambda t: t.reshape(shape)),
            self.batch_sh, prefetch=2)

    def step(self, state, batch):
        return self.compiled(state, self.mask, batch)

    @staticmethod
    def opt_state(state):
        return state[1]
