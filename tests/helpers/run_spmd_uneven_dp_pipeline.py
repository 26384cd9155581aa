"""Subprocess helper: NON-UNIFORM batch domains on the 3-D
(dp × pipe × tp) SPMD pipeline, 8 virtual devices (DESIGN.md §13).

The ISSUE 8 tentpole acceptance: an uneven domain (dp=2, allocations
(5, 3)) executes for real — each dp replica runs the schedule's tick
program for ITS OWN allocation, padded with bit-inert no-op ticks to
the pacing replica's length.  Checks:

* the uneven dp=2 loss matches the dp=1 pipeline on the same GLOBAL
  batch (the global-batch-mean objective weighs replica r by
  allocations[r]/total automatically) and the monolithic model;
* gradients match the dp=1 pipeline leaf-by-leaf to ≈1e-8;
* pad slots are bit-inert: clobbering the padded token slots changes
  NOTHING (loss and grads bitwise identical);
* executed == priced: the stacked domain program runs exactly the
  pacing replica's tick count — the b = max(domain) the cost model
  charges (mirrors PR 7's reshard-strategy pin);
* one train step under BOTH grad-sync modes produces matching params,
  which also match the dp=1 train step on the same global batch;
* a plan carrying the domain runs bit-identically through
  ``from_plan(execute_dp=True)``, and ``launch/train.py --plan``
  drives the same path end to end.

Run as a script (spawned by tests/test_uneven_dp_exec.py) so the forced
device count never leaks into the main pytest process.
"""
from repro.launch.hostdevices import force_host_device_count

force_host_device_count(8)

import dataclasses
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import heteropp as HP
from repro.core.dataparallel import pad_index_map
from repro.models import model as M
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.launch.mesh import auto_mesh

DOMAIN = (5, 3)                # dp=2: pacing replica 0, light replica 1
TOTAL = sum(DOMAIN)
BMAX = max(DOMAIN)


def _tree_rel_err(a, b):
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        num += float(np.sum(np.abs(x - y)))
        den += float(np.sum(np.abs(y)))
    return num / max(den, 1e-12)


def main():
    cfg = get_smoke_config("granite_8b")
    cfg = dataclasses.replace(cfg, dtype="float32", num_layers=4)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)

    mb, S_seq = 2, 32
    tokens = jax.random.randint(key, (TOTAL, mb, S_seq), 0, cfg.vocab_size)
    phys = (2, 2)

    mesh2d = auto_mesh((2, 2), ("pipe", "tp"))
    mesh3d = auto_mesh((2, 2, 2), ("dp", "pipe", "tp"))

    # dp=1 reference: ONE pipeline streaming the whole global batch
    spec1 = HP.PipelineSpec(2, phys, microbatches=TOTAL,
                            tensor_parallel=2)
    sp, mask = HP.split_stage_params(params, cfg, spec1)
    loss_fn1 = HP.make_spmd_pipeline_loss(cfg, spec1, mesh2d)
    loss1 = float(loss_fn1(sp, mask, tokens))
    g1 = jax.grad(lambda p: loss_fn1(p, mask, tokens))(sp)

    # the uneven domain on the 3-D mesh: replica 0 runs 5 microbatches,
    # replica 1 runs 3, inside ONE shard_map
    spec = HP.PipelineSpec(2, phys, microbatches=BMAX, tensor_parallel=2,
                           data_parallel=2, batch_domain=DOMAIN)
    assert spec.batch_allocations == DOMAIN
    assert spec.total_microbatches == TOTAL
    loss_fn = HP.make_spmd_pipeline_loss(cfg, spec, mesh3d)
    loss = float(loss_fn(sp, mask, tokens))
    err1 = abs(loss - loss1) / max(abs(loss1), 1e-9)
    print(f"uneven dp=2 {DOMAIN} loss={loss:.6f} vs dp1 rel={err1:.2e}")
    assert err1 < 1e-6, (loss, loss1)

    ref_losses = []
    for i in range(TOTAL):
        l, _ = M.loss_fn(params, cfg, {"tokens": tokens[i]}, remat=False)
        ref_losses.append(float(l))
    ref = float(np.mean(ref_losses))
    errm = abs(loss - ref) / max(abs(ref), 1e-9)
    print(f"vs monolithic rel={errm:.2e}")
    assert errm < 2e-3, (loss, ref)

    g = jax.grad(lambda p: loss_fn(p, mask, tokens))(sp)
    gerr = _tree_rel_err(g, g1)
    print(f"grad rel err vs dp1: {gerr:.2e}")
    assert gerr < 1e-6, gerr

    # ---- pad slots are bit-inert (the §13 masked-tick contract) ----------
    idx = jnp.asarray(pad_index_map(DOMAIN))
    padded = jnp.take(tokens, idx, axis=0)         # (dp·bmax, mb, seq)
    # replica 1's pad slots are the tail of the second bmax-block;
    # clobber them with garbage — nothing may change
    garbage = padded.at[BMAX + DOMAIN[1]:].set(0)
    la, lb = float(loss_fn(sp, mask, padded)), \
        float(loss_fn(sp, mask, garbage))
    assert la == loss, (la, loss)     # tight and padded layouts agree
    assert la == lb, (la, lb)
    ga = jax.grad(lambda p: loss_fn(p, mask, padded))(sp)
    gb = jax.grad(lambda p: loss_fn(p, mask, garbage))(sp)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert (np.asarray(x) == np.asarray(y)).all()
    print("pad slots bit-inert: loss and grads unchanged under clobber")

    # ---- executed == priced: pacing tick count (PR 7-style pin) ----------
    stacked = HP.domain_tick_tables("1f1b", 2, DOMAIN)
    pacing = HP.spmd_tick_tables("1f1b", 2, BMAX)
    assert stacked.ticks == pacing.ticks, (stacked.ticks, pacing.ticks)
    print(f"executed ticks={stacked.ticks} == priced pacing "
          f"b={BMAX} ticks={pacing.ticks}")

    # ---- train step: both grad-sync modes, vs the dp=1 step --------------
    opt = AdamWConfig(lr=1e-3, total_steps=10, warmup_steps=2)
    states = {}
    for mode in ("psum", "reduce_scatter"):
        step_fn = HP.make_spmd_pipeline_train_step(cfg, spec, mesh3d, opt,
                                                   grad_sync=mode)
        state = (sp, adamw.init_opt_state(sp), jnp.int32(0))
        state, mets = jax.jit(step_fn)(state, mask, {"tokens": tokens})
        states[mode] = state
        err = abs(float(mets["loss"]) - loss) / max(abs(loss), 1e-9)
        print(f"train[{mode}] loss={float(mets['loss']):.6f} "
              f"gnorm={float(mets['grad_norm']):.4f} loss rel={err:.2e}")
        assert err < 1e-6, (mode, float(mets["loss"]), loss)
        assert int(state[2]) == 1
    err_modes = _tree_rel_err(states["psum"][0],
                              states["reduce_scatter"][0])
    print(f"psum vs reduce_scatter params rel err: {err_modes:.2e}")
    assert err_modes == 0.0, err_modes    # bit-identical across modes

    step1 = HP.make_spmd_pipeline_train_step(cfg, spec1, mesh2d, opt)
    st1 = (sp, adamw.init_opt_state(sp), jnp.int32(0))
    st1, m1 = jax.jit(step1)(st1, mask, {"tokens": tokens})
    err_dp1 = _tree_rel_err(states["psum"][0], st1[0])
    print(f"uneven dp2 vs dp1 one-step params rel err: {err_dp1:.2e} "
          f"(dp1 gnorm={float(m1['grad_norm']):.4f})")
    assert err_dp1 < 1e-5, err_dp1

    # ---- plan path: from_plan + launch/train.py drive the same spec ------
    from repro.core import chips
    from repro.core.cost_model import ParallelPlan, StagePlan
    plan = ParallelPlan(
        [StagePlan(chips.ChipGroup(chips.CHIPS["A"], 4), 2, 1, 2, False),
         StagePlan(chips.ChipGroup(chips.CHIPS["B"], 4), 2, 1, 2, False)],
        dp=2, microbatches=BMAX, schedule="1f1b", batch_domain=DOMAIN)
    pspec = HP.from_plan(plan, execute_tp=True, execute_dp=True)
    assert pspec.batch_domain == DOMAIN and pspec.microbatches == BMAX
    psp, pmask = HP.split_stage_params(params, cfg, pspec)
    plan_loss = float(HP.make_spmd_pipeline_loss(cfg, pspec, mesh3d)(
        psp, pmask, tokens))
    assert plan_loss == loss, (plan_loss, loss)
    print(f"from_plan uneven dp loss={plan_loss:.6f} "
          f"(bit-exact vs direct spec)")

    # launch/train.py --plan: the full launcher path on the uneven
    # winner — smoke granite_8b has 2 layers, so a 2-stage 1-layer plan
    with tempfile.TemporaryDirectory() as td:
        lplan = ParallelPlan(
            [StagePlan(chips.ChipGroup(chips.CHIPS["A"], 4), 2, 1, 1,
                       False),
             StagePlan(chips.ChipGroup(chips.CHIPS["B"], 4), 2, 1, 1,
                       False)],
            dp=2, microbatches=BMAX, schedule="1f1b",
            batch_domain=DOMAIN)
        path = os.path.join(td, "uneven_plan.json")
        with open(path, "w") as f:
            json.dump(lplan.to_dict(), f)
        from repro.launch import train as T
        argv = sys.argv
        sys.argv = ["train", "--arch", "granite_8b", "--smoke",
                    "--plan", path, "--steps", "2", "--batch", str(TOTAL),
                    "--seq", "32", "--log-every", "1"]
        try:
            T.main()
        finally:
            sys.argv = argv
    print("launch/train.py --plan ran the uneven domain")
    print("UNEVEN_DP_OK")


if __name__ == "__main__":
    main()
