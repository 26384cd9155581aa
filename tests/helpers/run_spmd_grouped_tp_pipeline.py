"""Subprocess helper: NON-uniform per-stage tp on 8 virtual devices via
the grouped stage runtime (DESIGN.md §12).

The asymmetric layout of ISSUE 7's acceptance: stage_tp = (4, 2, 1, 1)
on a flat 8-device pipe mesh, each stage running Megatron tp inside its
own device group, with the §5 reshard collective at every tp-differing
boundary.  Checks:

* the asymmetric pipeline's loss matches the monolithic model to fp32
  reduction tolerance (different tp degrees re-associate the psum'd
  contractions, so bitwise equality vs tp=1 is not expected);
* a grouped spec with UNIFORM stage_tp matches the legacy 2-D
  (pipe × tp) runtime to the same tolerance — the two express one
  layout through different collectives (group-masked gather vs psum);
* a searched-plan with non-uniform tp runs end to end through
  ``from_plan(execute_tp=True)`` BIT-identically to the direct spec;
* three AdamW train steps decrease the loss, gradients flow to every
  real shard, and the zero-padded phantom shards (the width equalizer
  across tp degrees) stay EXACTLY zero through training;
* genuinely inexpressible layouts still refuse with the word
  "non-uniform" in the error (chunked schedule × non-uniform tp).

Run as a script (spawned by tests/test_heteropp.py) so the forced
device count never leaks into the main pytest process.
"""
from repro.launch.hostdevices import force_host_device_count

force_host_device_count(8)

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import chips, heteropp as HP
from repro.core.cost_model import ParallelPlan, StagePlan
from repro.models import model as M
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.sharding import rules
from repro.launch.mesh import auto_mesh


def _monolithic_ref(params, cfg, tokens):
    refs = []
    for i in range(tokens.shape[0]):
        l, _ = M.loss_fn(params, cfg, {"tokens": tokens[i]}, remat=False)
        refs.append(float(l))
    return float(np.mean(refs))


def _phantom_slices(blocks, stage_tp):
    """Yield (path, device, zero-padded phantom region) for every
    grouped block leaf — the rows/columns a tp_k > tp_min device carries
    only to equalize shard widths across the flat mesh."""
    layout = HP.group_layout(stage_tp)
    flat, _ = jax.tree_util.tree_flatten_with_path(blocks)
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        d = rules.tp_body_dim(path, leaf.ndim - 2)
        if d is None:
            continue
        axis = 2 + d                       # leaf is (N, Lmax, *body)
        local = leaf.shape[axis]
        full = local * layout.tp_min
        for i in range(layout.num_devices):
            keep = full // int(layout.tp_of[i])
            if keep < local:
                sl = [slice(None)] * leaf.ndim
                sl[0] = i
                sl[axis] = slice(keep, None)
                yield path, i, np.asarray(leaf[tuple(sl)])


def main():
    cfg = get_smoke_config("granite_8b")
    cfg = dataclasses.replace(cfg, dtype="float32", num_layers=4,
                              num_heads=4, num_kv_heads=4)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)

    b, mb, S = 4, 2, 32
    tokens = jax.random.randint(key, (b, mb, S), 0, cfg.vocab_size)
    ref = _monolithic_ref(params, cfg, tokens)

    mesh8 = auto_mesh((8,), ("pipe",))

    # ---- asymmetric grouped pipeline: tp = 4, 2, 1, 1 over 8 devices ----
    spec = HP.PipelineSpec(4, (1, 1, 1, 1), microbatches=b,
                           stage_tp=(4, 2, 1, 1))
    assert spec.grouped and spec.pipe_width == 8
    assert spec.reshard == ("sr_ag", "sr_ag", "none"), spec.reshard
    HP.validate_spec_tp(cfg, spec)
    sp, mask = HP.split_stage_params(params, cfg, spec)
    loss_fn = HP.make_spmd_pipeline_loss(cfg, spec, mesh8)
    loss = float(loss_fn(sp, mask, tokens))
    err = abs(loss - ref) / max(abs(ref), 1e-9)
    print(f"grouped tp(4,2,1,1) loss={loss:.6f} ref={ref:.6f} "
          f"rel_err={err:.2e}")
    assert err < 2e-3, (loss, ref)

    # every real shard gets gradient signal
    g = jax.grad(lambda p: loss_fn(p, mask, tokens))(sp)
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0, gn
    print(f"grouped grad_abs_sum={gn:.3e}")

    # ---- grouped-uniform vs the legacy 2-D (pipe, tp) runtime ----------
    spec_gu = HP.PipelineSpec(4, (1, 1, 1, 1), microbatches=b,
                              stage_tp=(2, 2, 2, 2))
    sp_gu, mask_gu = HP.split_stage_params(params, cfg, spec_gu)
    loss_gu = float(HP.make_spmd_pipeline_loss(cfg, spec_gu, mesh8)(
        sp_gu, mask_gu, tokens))
    mesh2d = auto_mesh((4, 2), ("pipe", "tp"))
    spec_2d = HP.PipelineSpec(4, (1, 1, 1, 1), microbatches=b,
                              tensor_parallel=2)
    sp_2d, mask_2d = HP.split_stage_params(params, cfg, spec_2d)
    loss_2d = float(HP.make_spmd_pipeline_loss(cfg, spec_2d, mesh2d)(
        sp_2d, mask_2d, tokens))
    print(f"grouped-uniform tp2 loss={loss_gu:.6f} legacy-2d "
          f"loss={loss_2d:.6f}")
    np.testing.assert_allclose(loss_gu, loss_2d, rtol=1e-5)
    assert abs(loss_gu - ref) / max(abs(ref), 1e-9) < 2e-3

    # ---- searched-plan path executes bit-identically -------------------
    plan = ParallelPlan(
        [StagePlan(chips.ChipGroup(chips.CHIPS["A"], 4), 4, 1, 1, False),
         StagePlan(chips.ChipGroup(chips.CHIPS["B"], 2), 2, 1, 1, False),
         StagePlan(chips.ChipGroup(chips.CHIPS["B"], 1, "B1"), 1, 1, 1,
                   False),
         StagePlan(chips.ChipGroup(chips.CHIPS["C"], 1), 1, 1, 1, False)],
        dp=1, microbatches=b, schedule="1f1b")
    pspec = HP.from_plan(plan, execute_tp=True)
    assert pspec.stage_tp == (4, 2, 1, 1), pspec.stage_tp
    assert all(r in ("none", "naive", "sr_ag") for r in pspec.reshard)
    psp, pmask = HP.split_stage_params(params, cfg, pspec)
    plan_loss = float(HP.make_spmd_pipeline_loss(cfg, pspec, mesh8)(
        psp, pmask, tokens))
    assert plan_loss == loss, (plan_loss, loss)
    print(f"from_plan tp(4,2,1,1) loss={plan_loss:.6f} "
          f"reshard={pspec.reshard} (bit-exact vs direct spec)")

    # ---- training: loss decreases, phantoms stay exactly zero ----------
    for path, i, region in _phantom_slices(sp["blocks"], spec.stage_tp):
        assert np.abs(region).max() == 0.0, (path, i)
    step_fn = jax.jit(HP.make_spmd_pipeline_train_step(
        cfg, spec, mesh8, AdamWConfig(lr=1e-3, total_steps=10,
                                      warmup_steps=1)))
    state = (sp, adamw.init_opt_state(sp), jnp.int32(0))
    losses = []
    for _ in range(3):
        state, m = step_fn(state, mask, {"tokens": tokens})
        losses.append(float(m["loss"]))
    print(f"train losses={['%.6f' % l for l in losses]}")
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses
    phantoms = 0
    for path, i, region in _phantom_slices(state[0]["blocks"],
                                           spec.stage_tp):
        assert np.abs(region).max() == 0.0, ("after training", path, i)
        phantoms += 1
    assert phantoms > 0
    print(f"{phantoms} phantom shard regions exactly zero after 3 steps")

    # ---- inexpressible layouts still refuse clearly --------------------
    bad = dataclasses.replace(plan, schedule="zb_v")
    try:
        HP.from_plan(bad, execute_tp=True)
    except ValueError as e:
        assert "non-uniform" in str(e), e
        print("chunked x non-uniform tp refused")
    else:
        raise AssertionError("chunked non-uniform plan was not refused")
    print("GROUPED_TP_OK")


if __name__ == "__main__":
    main()
