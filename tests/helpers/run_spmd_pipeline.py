"""Subprocess helper: SPMD HeteroPP pipeline on 4 virtual devices.

Covers the schedule/runtime contract (DESIGN.md §7): single-chunk
schedules (1f1b/gpipe/zb_h1), chunked v=2 schedules (interleaved, zb_v)
via the tick tables + chunked parameter layout, and the searched-plan
path (ParallelPlan -> from_plan -> SPMD) — all bit-identical to each
other and matching the monolithic model / simulate_pipeline_forward.

Run as a script (spawned by tests/test_heteropp.py) so the forced device
count never leaks into the main pytest process.
"""
from repro.launch.hostdevices import force_host_device_count

force_host_device_count(4)

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import heteropp as HP
from repro.models import model as M
from repro.launch.mesh import auto_mesh


def main():
    cfg = get_smoke_config("granite_8b")
    cfg = dataclasses.replace(cfg, dtype="float32", num_layers=2)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)

    b, mb, S = 4, 2, 32
    tokens = jax.random.randint(key, (b, mb, S), 0, cfg.vocab_size)

    mesh = auto_mesh((4,), ("pipe",))
    # 4 stages over 2 layers won't sum; use padded non-uniform split of 2
    phys = (1, 0, 0, 1)
    spec = HP.PipelineSpec(4, phys, microbatches=b)

    stage_params, mask = HP.split_stage_params(params, cfg, spec)
    losses = {}
    for schedule in ("1f1b", "gpipe", "zb_h1"):
        loss_fn = HP.make_spmd_pipeline_loss(cfg, spec, mesh, remat=True,
                                             schedule=schedule)
        losses[schedule] = float(loss_fn(stage_params, mask, tokens))
    loss = losses["1f1b"]
    # single-chunk schedules share the diagonal-stream injection order:
    # identical program, bit-identical loss
    assert losses["gpipe"] == loss == losses["zb_h1"], losses

    # chunked (virtual-stage) schedules: v chunk slots per device, same
    # per-layer math in the same order -> still bit-identical (wave's
    # v=4 W placement rides the same generic tick tables)
    for schedule, v in (("interleaved", 2), ("zb_v", 2), ("wave", 4)):
        cspec = HP.PipelineSpec(
            4, HP.chunk_layer_counts(phys, schedule), microbatches=b,
            schedule=schedule, n_chunks=v)
        csp, cmask = HP.split_stage_params(params, cfg, cspec)
        loss_fn = HP.make_spmd_pipeline_loss(cfg, cspec, mesh, remat=True)
        losses[schedule] = float(loss_fn(csp, cmask, tokens))
    assert losses["interleaved"] == loss == losses["zb_v"] \
        == losses["wave"], losses
    print(f"chunked losses bit-exact vs single-chunk: "
          f"{losses['interleaved']:.6f}")

    # reference 1: monolithic forward loss over all microbatches
    ref_losses = []
    for i in range(b):
        batch = {"tokens": tokens[i]}
        l, _ = M.loss_fn(params, cfg, batch, remat=False)
        ref_losses.append(float(l))
    ref = float(np.mean(ref_losses))
    err = abs(loss - ref) / max(abs(ref), 1e-9)
    print(f"pipeline_loss={loss:.6f} ref={ref:.6f} rel_err={err:.2e}")
    assert err < 2e-3, (loss, ref)

    # reference 2: the schedule-ordered scan must match the sequential
    # numerics oracle simulate_pipeline_forward per microbatch
    sim_losses = []
    for i in range(b):
        logits, _ = HP.simulate_pipeline_forward(params, cfg, spec,
                                                 {"tokens": tokens[i]})
        toks = tokens[i]
        targets = jnp.concatenate(
            [toks[:, 1:], jnp.zeros_like(toks[:, :1])], axis=1)
        lmask = jnp.ones_like(toks, jnp.float32).at[:, -1].set(0.0)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
        sim_losses.append(float(jnp.sum(nll * lmask) / jnp.sum(lmask)))
    sim_ref = float(np.mean(sim_losses))
    err_sim = abs(loss - sim_ref) / max(abs(sim_ref), 1e-9)
    print(f"simulate_pipeline_forward ref={sim_ref:.6f} rel_err={err_sim:.2e}")
    assert err_sim < 2e-3, (loss, sim_ref)

    # end-to-end: a ParallelPlan with a chunked schedule and non-uniform
    # layers through from_plan -> SPMD run vs simulate_pipeline_forward
    from repro.core import chips
    from repro.core.cost_model import ParallelPlan, StagePlan
    plan = ParallelPlan(
        [StagePlan(chips.ChipGroup(chips.CHIPS["A"], 2), 1, 2, 1, False),
         StagePlan(chips.ChipGroup(chips.CHIPS["B"], 2), 1, 2, 1, False)],
        dp=1, microbatches=b, schedule="zb_v")
    pspec = HP.from_plan(plan)
    assert pspec.n_chunks == 2 and pspec.num_stages == 4
    assert pspec.total_layers == cfg.num_layers
    psp, pmask = HP.split_stage_params(params, cfg, pspec)
    loss_fn = HP.make_spmd_pipeline_loss(cfg, pspec, mesh, remat=True)
    plan_loss = float(loss_fn(psp, pmask, tokens))
    plan_sim = []
    for i in range(b):
        logits, _ = HP.simulate_pipeline_forward(params, cfg, pspec,
                                                 {"tokens": tokens[i]})
        toks = tokens[i]
        targets = jnp.concatenate(
            [toks[:, 1:], jnp.zeros_like(toks[:, :1])], axis=1)
        lmask = jnp.ones_like(toks, jnp.float32).at[:, -1].set(0.0)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
        plan_sim.append(float(jnp.sum(nll * lmask) / jnp.sum(lmask)))
    plan_ref = float(np.mean(plan_sim))
    err_plan = abs(plan_loss - plan_ref) / max(abs(plan_ref), 1e-9)
    print(f"from_plan v=2 loss={plan_loss:.6f} sim_ref={plan_ref:.6f} "
          f"rel_err={err_plan:.2e}")
    assert err_plan < 2e-3, (plan_loss, plan_ref)
    assert plan_loss == loss, (plan_loss, loss)  # same layers, same math

    # gradients flow through ppermute (single-chunk and chunked paths)
    loss_fn = HP.make_spmd_pipeline_loss(cfg, spec, mesh, remat=True)
    g = jax.grad(lambda sp: loss_fn(sp, mask, tokens))(stage_params)
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
    loss_fn = HP.make_spmd_pipeline_loss(cfg, pspec, mesh, remat=True)
    g = jax.grad(lambda sp: loss_fn(sp, pmask, tokens))(psp)
    gn2 = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn2) and gn2 > 0
    print(f"grad_abs_sum={gn:.3e} chunked={gn2:.3e}")
    print("OK")


if __name__ == "__main__":
    main()
