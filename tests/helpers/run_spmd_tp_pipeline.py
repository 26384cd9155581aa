"""Subprocess helper: 2-D (pipe × tp) SPMD HeteroPP pipeline on 8
virtual devices (DESIGN.md §8).

Covers the tp axis of the runtime: stage params sharded Megatron-style
inside each pipe row (column-parallel QKV/MLP-up, row-parallel wo with a
psum over tp), activations streaming along pipe rows only.  Checks:

* tp=2 losses are bit-identical across schedules (same per-layer math in
  the same order) and match the tp=1 pipeline / monolithic model to fp32
  reduction tolerance (the psum splits the contraction, so bitwise
  equality across DIFFERENT tp degrees is not expected);
* gradients flow through psum + ppermute to the tp-sharded params;
* a searched-plan (uniform tp) runs end to end via
  ``from_plan(execute_tp=True)`` bit-identically to the direct spec;
* a non-uniform-tp plan maps to a grouped spec (DESIGN.md §12; executed
  in run_spmd_grouped_tp_pipeline.py), and the refusal survives only
  for the chunked-schedule layouts the group runtime cannot express.

Run as a script (spawned by tests/test_heteropp.py) so the forced device
count never leaks into the main pytest process.
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
from repro.launch.mesh import auto_mesh


def _monolithic_ref(params, cfg, tokens):
    refs = []
    for i in range(tokens.shape[0]):
        l, _ = M.loss_fn(params, cfg, {"tokens": tokens[i]}, remat=False)
        refs.append(float(l))
    return float(np.mean(refs))


def main():
    cfg = get_smoke_config("granite_8b")
    cfg = dataclasses.replace(cfg, dtype="float32", num_layers=4)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)

    b, mb, S = 4, 2, 32
    tokens = jax.random.randint(key, (b, mb, S), 0, cfg.vocab_size)

    mesh1d = auto_mesh((2,), ("pipe",))
    mesh2d = auto_mesh((2, 2), ("pipe", "tp"))

    # tp=1 reference on the 1-D pipe mesh
    phys = (2, 2)
    spec1 = HP.PipelineSpec(2, phys, microbatches=b)
    sp1, mask1 = HP.split_stage_params(params, cfg, spec1)
    loss1 = float(HP.make_spmd_pipeline_loss(cfg, spec1, mesh1d)(
        sp1, mask1, tokens))

    # tp=2 on the 2-D mesh: single-chunk and chunked schedules
    losses = {}
    for schedule in ("1f1b", "zb_v"):
        spec = HP.PipelineSpec(
            2, HP.chunk_layer_counts(phys, schedule), microbatches=b,
            schedule=schedule, n_chunks=get_schedule(schedule).n_chunks,
            tensor_parallel=2)
        sp, mask = HP.split_stage_params(params, cfg, spec)
        loss_fn = HP.make_spmd_pipeline_loss(cfg, spec, mesh2d)
        losses[schedule] = float(loss_fn(sp, mask, tokens))
        if schedule == "1f1b":
            g = jax.grad(lambda p: loss_fn(p, mask, tokens))(sp)
            gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
            assert np.isfinite(gn) and gn > 0, gn
            print(f"tp2 grad_abs_sum={gn:.3e}")
    assert losses["1f1b"] == losses["zb_v"], losses

    ref = _monolithic_ref(params, cfg, tokens)
    for name, l in [("tp1", loss1)] + sorted(losses.items()):
        err = abs(l - ref) / max(abs(ref), 1e-9)
        print(f"{name} loss={l:.6f} ref={ref:.6f} rel_err={err:.2e}")
        assert err < 2e-3, (name, l, ref)
    # tp only re-associates the psum'd contractions: tp=2 must agree with
    # tp=1 to fp32 reduction tolerance
    np.testing.assert_allclose(losses["1f1b"], loss1, rtol=1e-5)

    # all 8 devices: pipe=4 × tp=2, zb_v V placement
    mesh8 = auto_mesh((4, 2), ("pipe", "tp"))
    spec8 = HP.PipelineSpec(
        4, HP.chunk_layer_counts((1, 1, 1, 1), "zb_v"), microbatches=b,
        schedule="zb_v", n_chunks=2, tensor_parallel=2)
    sp8, mask8 = HP.split_stage_params(params, cfg, spec8)
    loss8 = float(HP.make_spmd_pipeline_loss(cfg, spec8, mesh8)(
        sp8, mask8, tokens))
    err8 = abs(loss8 - ref) / max(abs(ref), 1e-9)
    print(f"pp4xtp2 zb_v loss={loss8:.6f} rel_err={err8:.2e}")
    assert err8 < 2e-3, (loss8, ref)

    # searched-plan path: uniform tp executes, non-uniform is refused
    from repro.core import chips
    from repro.core.cost_model import ParallelPlan, StagePlan
    plan = ParallelPlan(
        [StagePlan(chips.ChipGroup(chips.CHIPS["A"], 4), 2, 1, 2, False),
         StagePlan(chips.ChipGroup(chips.CHIPS["B"], 4), 2, 1, 2, False)],
        dp=1, microbatches=b, schedule="zb_v")
    pspec = HP.from_plan(plan, execute_tp=True)
    assert pspec.tensor_parallel == 2 and pspec.num_stages == 2
    psp, pmask = HP.split_stage_params(params, cfg, pspec)
    plan_loss = float(HP.make_spmd_pipeline_loss(cfg, pspec, mesh2d)(
        psp, pmask, tokens))
    assert plan_loss == losses["zb_v"], (plan_loss, losses)
    print(f"from_plan tp=2 loss={plan_loss:.6f} (bit-exact vs direct spec)")

    mixed = ParallelPlan(
        [StagePlan(chips.ChipGroup(chips.CHIPS["A"], 8), 4, 1, 2, False),
         StagePlan(chips.ChipGroup(chips.CHIPS["B"], 4), 2, 1, 2, False)],
        dp=1, microbatches=b, schedule="1f1b")
    # non-uniform tp now maps to the grouped stage runtime (DESIGN.md
    # §12 — executed end to end in run_spmd_grouped_tp_pipeline.py)
    gspec = HP.from_plan(mixed, execute_tp=True)
    assert gspec.grouped and gspec.stage_tp == (4, 2), gspec
    print(f"non-uniform tp plan grouped: stage_tp={gspec.stage_tp} "
          f"reshard={gspec.reshard}")
    # the historical default still maps it (tp stays cost-model-only)
    assert HP.from_plan(mixed).tensor_parallel == 1
    # chunked schedules are the surviving refusal: no grouped tick
    # program for v > 1 chunk slots
    chunked = dataclasses.replace(mixed, schedule="zb_v")
    try:
        HP.from_plan(chunked, execute_tp=True)
    except ValueError as e:
        assert "non-uniform" in str(e), e
        print("chunked x non-uniform tp refused")
    else:
        raise AssertionError("chunked non-uniform plan was not refused")
    print("TP_OK")


if __name__ == "__main__":
    main()
