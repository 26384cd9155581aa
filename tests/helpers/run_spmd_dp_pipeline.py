"""Subprocess helper: 3-D (dp × pipe × tp) SPMD HeteroPP pipeline on 8
virtual devices (DESIGN.md §9).

Covers the dp axis of the runtime: pipeline replicas over the leading dp
mesh axis, tokens sharded over dp (uniform batch domain), loss closed by
a dp psum, gradients closed by the explicit bucketed dp sync inside the
full-step shard_map.  Checks:

* dp=2 losses are bit-identical across schedules (incl. chunked zb_v)
  and match the dp=1 pipeline on the same GLOBAL batch and the
  monolithic model to fp32 reduction tolerance;
* gradients of the dp=2 loss match the dp=1 pipeline's leaf-by-leaf;
* one train step under BOTH grad-sync modes (flat psum vs ZeRO-1
  reduce-scatter + all-gather) produces matching params/metrics, which
  also match the dp=1 train step on the same global batch;
* a uniform-dp plan runs end to end via ``from_plan(execute_dp=True)``
  bit-identically to the direct spec; a plan with a non-uniform batch
  domain maps to a per-replica-program spec (numerics in
  ``run_spmd_uneven_dp_pipeline.py`` — DESIGN.md §13).

Run as a script (spawned by tests/test_dataparallel.py) so the forced
device count never leaks into the main pytest process.
"""
from repro.launch.hostdevices import force_host_device_count

force_host_device_count(8)

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import heteropp as HP
from repro.core.schedules import get_schedule
from repro.models import model as M
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.launch.mesh import auto_mesh

DP, B = 2, 4          # dp replicas × microbatches per replica


def _spec(phys, schedule, *, dp=1, tp=2, b=B):
    sched = get_schedule(schedule)
    return HP.PipelineSpec(
        len(phys), HP.chunk_layer_counts(phys, sched), microbatches=b,
        schedule=schedule, n_chunks=sched.n_chunks, tensor_parallel=tp,
        data_parallel=dp)


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
    tokens = jax.random.randint(key, (DP * B, mb, S_seq), 0, cfg.vocab_size)
    phys = (2, 2)

    mesh2d = auto_mesh((2, 2), ("pipe", "tp"))
    mesh3d = auto_mesh((2, 2, 2), ("dp", "pipe", "tp"))

    # dp=1 references: ONE pipeline streaming the whole global batch
    # (per schedule — chunked schedules lay parameters out differently)
    spec1 = _spec(phys, "1f1b", b=DP * B)
    sp, mask = HP.split_stage_params(params, cfg, spec1)
    loss_fn1 = HP.make_spmd_pipeline_loss(cfg, spec1, mesh2d)
    loss1 = float(loss_fn1(sp, mask, tokens))
    g1 = {}
    for schedule in ("1f1b", "zb_v", "wave"):
        s1 = _spec(phys, schedule, b=DP * B)
        sp1, mask1 = HP.split_stage_params(params, cfg, s1)
        lf1 = HP.make_spmd_pipeline_loss(cfg, s1, mesh2d)
        g1[schedule] = jax.grad(lambda p: lf1(p, mask1, tokens))(sp1)

    # dp=2 on the 3-D mesh: the per-replica microbatch count halves
    # (wave rides along: the v=4 W placement runs on the same 8-device
    # runtime through the generic tick tables — ISSUE 5 acceptance)
    losses = {}
    grads = {}
    for schedule in ("1f1b", "zb_v", "wave"):
        spec = _spec(phys, schedule, dp=DP)
        spd, maskd = HP.split_stage_params(params, cfg, spec)
        loss_fn = HP.make_spmd_pipeline_loss(cfg, spec, mesh3d)
        losses[schedule] = float(loss_fn(spd, maskd, tokens))
        grads[schedule] = jax.grad(
            lambda p: loss_fn(p, maskd, tokens))(spd)
    # same per-layer math in the same order -> bit-identical across
    # schedules at fixed dp
    assert losses["1f1b"] == losses["zb_v"] == losses["wave"], losses

    # global-batch semantics: dp=2 == dp=1 up to fp32 reduction order
    ref_losses = []
    for i in range(DP * B):
        l, _ = M.loss_fn(params, cfg, {"tokens": tokens[i]}, remat=False)
        ref_losses.append(float(l))
    ref = float(np.mean(ref_losses))
    for name, l in sorted(losses.items()):
        err1 = abs(l - loss1) / max(abs(loss1), 1e-9)
        errm = abs(l - ref) / max(abs(ref), 1e-9)
        print(f"dp2 {name} loss={l:.6f} vs dp1 rel={err1:.2e} "
              f"vs monolithic rel={errm:.2e}")
        assert err1 < 1e-6, (name, l, loss1)
        assert errm < 2e-3, (name, l, ref)

    for schedule in ("1f1b", "zb_v", "wave"):
        err = _tree_rel_err(grads[schedule], g1[schedule])
        print(f"dp2 {schedule} grad rel err vs dp1: {err:.2e}")
        assert err < 1e-6, (schedule, err)

    # ---- train step: explicit grad sync, both modes ----------------------
    opt = AdamWConfig(lr=1e-3, total_steps=10, warmup_steps=2)
    spec = _spec(phys, "1f1b", dp=DP)
    spd, maskd = HP.split_stage_params(params, cfg, spec)
    states = {}
    for mode in ("psum", "reduce_scatter"):
        step_fn = HP.make_spmd_pipeline_train_step(cfg, spec, mesh3d, opt,
                                                   grad_sync=mode)
        state = (spd, adamw.init_opt_state(spd), jnp.int32(0))
        state, mets = jax.jit(step_fn)(state, maskd, {"tokens": tokens})
        states[mode] = state
        err = abs(float(mets["loss"]) - losses["1f1b"]) / \
            max(abs(losses["1f1b"]), 1e-9)
        print(f"train[{mode}] loss={float(mets['loss']):.6f} "
              f"gnorm={float(mets['grad_norm']):.4f} loss rel={err:.2e}")
        assert err < 1e-6, (mode, float(mets["loss"]), losses["1f1b"])
        assert int(state[2]) == 1

    err_modes = _tree_rel_err(states["psum"][0], states["reduce_scatter"][0])
    print(f"psum vs reduce_scatter params rel err: {err_modes:.2e}")
    assert err_modes < 1e-6, err_modes

    # bucketed psum (DESIGN.md §10): fused per-bucket all-reduces in
    # wgrad-completion order are the SAME element-wise sums — params
    # after one step must be bit-identical to the per-leaf psum program
    bspec = dataclasses.replace(spec, bucket_bytes=64 * 1024)
    step_b = HP.make_spmd_pipeline_train_step(cfg, bspec, mesh3d, opt,
                                              grad_sync="psum")
    state_b = (spd, adamw.init_opt_state(spd), jnp.int32(0))
    state_b, mets_b = jax.jit(step_b)(state_b, maskd, {"tokens": tokens})
    err_bucket = _tree_rel_err(state_b[0], states["psum"][0])
    print(f"bucketed vs per-leaf psum params rel err: {err_bucket:.2e}")
    assert err_bucket == 0.0, err_bucket
    # and on a CHUNKED layout the chunk-sliced bucket stream reassembles
    # correctly (wave: 4 chunk slots per device)
    wspec = dataclasses.replace(_spec(phys, "wave", dp=DP),
                                bucket_bytes=48 * 1024)
    wsp, wmask = HP.split_stage_params(params, cfg, wspec)
    step_w = HP.make_spmd_pipeline_train_step(cfg, wspec, mesh3d, opt,
                                              grad_sync="psum")
    state_w0 = (wsp, adamw.init_opt_state(wsp), jnp.int32(0))
    state_w, _ = jax.jit(step_w)(state_w0, wmask, {"tokens": tokens})
    step_w1 = HP.make_spmd_pipeline_train_step(
        cfg, dataclasses.replace(wspec, bucket_bytes=0), mesh3d, opt,
        grad_sync="psum")
    state_w1, _ = jax.jit(step_w1)(state_w0, wmask, {"tokens": tokens})
    err_wave = _tree_rel_err(state_w[0], state_w1[0])
    print(f"wave bucketed vs per-leaf psum params rel err: {err_wave:.2e}")
    assert err_wave == 0.0, err_wave

    # dp=1 train step on the same global batch must land on the same
    # params (up to dp reduction order)
    step1 = HP.make_spmd_pipeline_train_step(cfg, spec1, mesh2d, opt)
    st1 = (sp, adamw.init_opt_state(sp), jnp.int32(0))
    st1, m1 = jax.jit(step1)(st1, mask, {"tokens": tokens})
    err_dp1 = _tree_rel_err(states["psum"][0], st1[0])
    print(f"dp2 vs dp1 one-step params rel err: {err_dp1:.2e} "
          f"(dp1 gnorm={float(m1['grad_norm']):.4f})")
    assert err_dp1 < 1e-5, err_dp1

    # ---- plan path: uniform AND non-uniform dp domains execute -----------
    from repro.core import chips
    from repro.core.cost_model import ParallelPlan, StagePlan
    plan = ParallelPlan(
        [StagePlan(chips.ChipGroup(chips.CHIPS["A"], 4), 2, 1, 2, False),
         StagePlan(chips.ChipGroup(chips.CHIPS["B"], 4), 2, 1, 2, False)],
        dp=DP, microbatches=B, schedule="zb_v")
    pspec = HP.from_plan(plan, execute_tp=True, execute_dp=True)
    assert pspec.data_parallel == DP and pspec.tensor_parallel == 2
    psp, pmask = HP.split_stage_params(params, cfg, pspec)
    plan_loss = float(HP.make_spmd_pipeline_loss(cfg, pspec, mesh3d)(
        psp, pmask, tokens))
    assert plan_loss == losses["zb_v"], (plan_loss, losses)
    print(f"from_plan dp=2 loss={plan_loss:.6f} (bit-exact vs direct spec)")

    # a SEARCHED plan with dp=2 executes end-to-end through from_plan
    from repro.core import heteroauto
    groups = chips.cluster(("A", 4), ("B", 4))
    r = heteroauto.search(groups, cfg, (DP * B) * S_seq, S_seq,
                          two_stage=False, dp_candidates=[DP],
                          schedule="1f1b")
    assert r.plan is not None and r.plan.dp == DP, r.plan
    tps = {s.tp for s in r.plan.stages}
    sspec = HP.from_plan(r.plan, execute_dp=True,
                         execute_tp=len(tps) == 1)
    assert sspec.data_parallel == DP
    smesh = auto_mesh((DP, sspec.num_stages, sspec.tensor_parallel)
                          if sspec.tensor_parallel > 1
                          else (DP, sspec.num_stages),
                          ("dp", "pipe", "tp")
                          if sspec.tensor_parallel > 1 else ("dp", "pipe"))
    ssp, smask = HP.split_stage_params(params, cfg, sspec)
    sloss = float(HP.make_spmd_pipeline_loss(cfg, sspec, smesh)(
        ssp, smask, tokens))
    serr = abs(sloss - ref) / max(abs(ref), 1e-9)
    print(f"searched plan [{r.plan.describe()}] dp loss={sloss:.6f} "
          f"rel_err={serr:.2e}")
    assert serr < 2e-3, (sloss, ref)

    # a non-uniform batch domain now EXECUTES (per-replica tick
    # programs — DESIGN.md §13; numerics covered end-to-end by
    # run_spmd_uneven_dp_pipeline.py)
    het = dataclasses.replace(plan, batch_domain=(5, 3), microbatches=5,
                              schedule="1f1b")
    hspec = HP.from_plan(het, execute_dp=True)
    assert hspec.batch_domain == (5, 3) and hspec.microbatches == 5
    assert hspec.total_microbatches == 8
    print("non-uniform batch domain maps to a per-replica spec")
    # and the historical default still maps it (dp stays cost-model-only)
    assert HP.from_plan(het).data_parallel == 1
    print("DP_OK")


if __name__ == "__main__":
    main()
