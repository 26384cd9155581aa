"""HeteroPP runtime: simulate-mode numerics vs the monolithic model,
non-uniform layer splits, plan->spec conversion, and the SPMD shard_map
pipeline (subprocess with virtual devices)."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch
from repro.configs import get_config, get_smoke_config
from repro.core import chips, heteroauto, heteropp as HP
from repro.models import model as M
from repro.launch.mesh import auto_mesh


@pytest.mark.parametrize("arch,splits", [
    ("granite_8b", (1, 1)),
    ("granite_8b", (2, 0)),
    ("qwen3_moe_30b_a3b", (1, 1)),
    ("mamba2_780m", (1, 1)),
])
def test_simulate_matches_monolithic(arch, splits):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32",
                              moe_capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    batch = make_batch(cfg, key, 2, 32)
    ref, _ = M.forward(params, cfg, batch, remat=False)
    spec = HP.PipelineSpec(len(splits), splits, microbatches=2)
    sim, _ = HP.simulate_pipeline_forward(params, cfg, spec, batch)
    np.testing.assert_allclose(np.asarray(sim), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_split_stage_params_shapes():
    cfg = get_smoke_config("granite_8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = HP.PipelineSpec(2, (1, 1), microbatches=4)
    sp, mask = HP.split_stage_params(params, cfg, spec)
    for leaf in jax.tree.leaves(sp["blocks"]):
        assert leaf.shape[0] == 2 and leaf.shape[1] == 1
    assert mask.shape == (2, 1) and bool(mask.all())


def test_from_plan_expands_stages():
    cfg = get_config("h2_100b")
    groups = chips.cluster(("A", 256), ("B", 256))
    r = heteroauto.search(groups, cfg, 2 * 2 ** 20, 4096, two_stage=False)
    assert r.plan is not None
    spec = HP.from_plan(r.plan)
    assert spec.total_layers == cfg.num_layers
    assert spec.num_stages == r.plan.total_pp
    assert spec.microbatches == r.plan.microbatches
    from repro.core.schedules import get_schedule
    assert spec.n_chunks == get_schedule(r.plan.schedule).n_chunks


def test_from_plan_chunked_layout():
    """Chunked schedules: layers spread over v chunk slots per device in
    ascending global-stage order, preserving the searched non-uniform
    split per physical stage."""
    cfg = get_config("h2_100b")
    groups = chips.cluster(("A", 256), ("B", 256))
    r = heteroauto.search(groups, cfg, 2 * 2 ** 20, 4096, two_stage=False,
                          schedule="zb_v")
    assert r.plan is not None and r.plan.schedule == "zb_v"
    spec = HP.from_plan(r.plan)
    S, v = spec.num_stages, spec.n_chunks
    assert v == 2 and len(spec.layers_per_stage) == S * v
    assert spec.total_layers == cfg.num_layers
    # per-device totals must match the plan's physical split
    from repro.core.schedules import get_schedule
    sched = get_schedule("zb_v")
    phys = [0] * S
    for g, l in enumerate(spec.layers_per_stage):
        phys[sched.device_of(g, S)] += l
    want, i = [], 0
    for st in r.plan.stages:
        left = st.layers
        for _ in range(st.pp):
            take = min(st.layers_per_stage, left)
            want.append(take)
            left -= take
    assert phys == want
    # plan JSON roundtrip preserves the spec
    import json
    from repro.core.cost_model import ParallelPlan
    p2 = ParallelPlan.from_dict(json.loads(json.dumps(r.plan.to_dict())))
    assert HP.from_plan(p2) == spec


def test_spmd_tick_tables_wave_stream():
    """The W placement admits a collision-free tight tick stream: every
    (mb, chunk) forward appears exactly once per device, never two ops
    on one device in one tick (asserted inside spmd_tick_tables), and
    all leg-turn hops route as SRC_LOCAL."""
    for S, b in ((2, 4), (4, 4), (3, 6)):
        t = HP.spmd_tick_tables("wave", S, b)
        assert t.active.sum() == S * 4 * b          # v=4 chunk-forwards
        # the three leg turns are device-local routes
        assert (t.src[t.active] == HP.SRC_LOCAL).sum() >= 3 * b


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs ≥4 devices (CI runs an 8-device job)")
def test_spmd_wave_pipeline_in_process():
    """The wave schedule on the REAL process devices (ISSUE 5
    acceptance rides the 8-virtual-device CI job): v=4 chunk slots per
    device, loss matches the monolithic model."""
    cfg = dataclasses.replace(get_smoke_config("granite_8b"),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 16), 0,
                                cfg.vocab_size)
    mesh = auto_mesh((4,), ("pipe",))
    phys = (1, 0, 0, 1)
    spec = HP.PipelineSpec(4, HP.chunk_layer_counts(phys, "wave"),
                           microbatches=4, schedule="wave", n_chunks=4)
    sp, mask = HP.split_stage_params(params, cfg, spec)
    loss = float(HP.make_spmd_pipeline_loss(cfg, spec, mesh)(
        sp, mask, tokens))
    refs = [float(M.loss_fn(params, cfg, {"tokens": tokens[i]},
                            remat=False)[0]) for i in range(4)]
    ref = float(np.mean(refs))
    assert abs(loss - ref) / max(abs(ref), 1e-9) < 2e-3, (loss, ref)


def test_schedule_injection_order_diagonal_view():
    """The compact single-chunk view of spmd_tick_tables: diagonal
    streams inject microbatches in order; chunked schedules have no
    single injection order."""
    for name in ("1f1b", "gpipe", "zb_h1"):
        assert HP.schedule_injection_order(name, 4, 6) == list(range(6))
    with pytest.raises(NotImplementedError):
        HP.schedule_injection_order("interleaved", 4, 8)


@pytest.mark.e2e
def test_manual_dp_zero1_subprocess():
    """Manual-collective ZeRO-1 (shard_map over data, auto over model):
    loss/grad-norm/trajectory match the GSPMD step on 8 virtual devices."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    script = os.path.join(tests_dir, "helpers", "run_manual_dp.py")
    root = os.path.dirname(tests_dir)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + ":" + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=600, env=env, cwd=root)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "MANUAL_DP_OK" in r.stdout


@pytest.mark.e2e
def test_spmd_pipeline_subprocess():
    """Full shard_map pipeline on 4 virtual devices: loss == monolithic,
    grads flow through ppermute."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    script = os.path.join(tests_dir, "helpers", "run_spmd_pipeline.py")
    root = os.path.dirname(tests_dir)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + ":" + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=600, env=env, cwd=root)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK" in r.stdout


@pytest.mark.e2e
def test_spmd_tp_pipeline_subprocess():
    """2-D (pipe × tp) pipeline on 8 virtual devices: tp-sharded stages
    match the tp=1 pipeline and the monolithic model; uniform-tp plans
    execute on this mesh, non-uniform ones route to the grouped stage
    runtime (DESIGN.md §12)."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    script = os.path.join(tests_dir, "helpers", "run_spmd_tp_pipeline.py")
    root = os.path.dirname(tests_dir)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + ":" + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=600, env=env, cwd=root)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "TP_OK" in r.stdout


@pytest.mark.e2e
def test_spmd_grouped_tp_pipeline_subprocess():
    """NON-uniform per-stage tp (4, 2, 1, 1) on 8 virtual devices via
    the grouped stage runtime: asymmetric loss matches the monolithic
    model, a searched plan executes bit-identically to the direct spec,
    training decreases the loss with phantom shards staying exactly
    zero (DESIGN.md §12 — the ISSUE 7 acceptance layout)."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    script = os.path.join(tests_dir, "helpers",
                          "run_spmd_grouped_tp_pipeline.py")
    root = os.path.dirname(tests_dir)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + ":" + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=600, env=env, cwd=root)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "GROUPED_TP_OK" in r.stdout


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs ≥4 devices (CI runs an 8-device job)")
def test_spmd_grouped_tp_pipeline_in_process():
    """The grouped (non-uniform per-stage tp) runtime on the REAL
    process devices: stage_tp = (2, 1, 1) over 4 devices, loss matches
    the monolithic model (DESIGN.md §12)."""
    cfg = dataclasses.replace(get_smoke_config("granite_8b"),
                              dtype="float32", num_layers=3)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 16), 0,
                                cfg.vocab_size)
    mesh = auto_mesh((4,), ("pipe",))
    spec = HP.PipelineSpec(3, (1, 1, 1), microbatches=2,
                           stage_tp=(2, 1, 1))
    assert spec.reshard == ("sr_ag", "none")
    sp, mask = HP.split_stage_params(params, cfg, spec)
    loss = float(HP.make_spmd_pipeline_loss(cfg, spec, mesh)(
        sp, mask, tokens))
    refs = [float(M.loss_fn(params, cfg, {"tokens": tokens[i]},
                            remat=False)[0]) for i in range(2)]
    ref = float(np.mean(refs))
    assert abs(loss - ref) / max(abs(ref), 1e-9) < 2e-3, (loss, ref)


def test_from_plan_tp_modes():
    """from_plan: tp stays a cost-model dimension by default; with
    execute_tp=True a uniform plan keeps the legacy bit-exact
    (pipe × tp) path and a NON-uniform one becomes a grouped spec
    (DESIGN.md §12) with a reshard strategy per tp-differing boundary."""
    from repro.core.cost_model import ParallelPlan, StagePlan
    g = lambda n, c: chips.ChipGroup(chips.CHIPS[n], c)
    uni = ParallelPlan([StagePlan(g("A", 4), 2, 1, 1, False),
                        StagePlan(g("B", 4), 2, 1, 1, False)],
                       dp=1, microbatches=4)
    assert HP.from_plan(uni).tensor_parallel == 1
    spec = HP.from_plan(uni, execute_tp=True)
    assert spec.tensor_parallel == 2 and spec.num_stages == 2
    assert not spec.grouped
    mixed = ParallelPlan([StagePlan(g("A", 4), 4, 1, 1, False),
                          StagePlan(g("B", 4), 2, 1, 1, False)],
                         dp=1, microbatches=4)
    assert HP.from_plan(mixed).tensor_parallel == 1   # legacy path intact
    gspec = HP.from_plan(mixed, execute_tp=True)
    assert gspec.grouped and gspec.stage_tp == (4, 2)
    assert gspec.tensor_parallel == 1 and gspec.pipe_width == 6
    assert gspec.reshard in (("sr_ag",), ("naive",))
    assert heteroauto.runtime_path(mixed) == "grouped-tp"
    assert heteroauto.runtime_path(uni) == "uniform-tp"


def test_from_plan_refuses_inexpressible_layouts():
    """The clear-error path survives for layouts the group runtime
    cannot express: non-uniform tp under a chunked schedule, and
    execute_dp with dp > 1 on a grouped spec."""
    from repro.core.cost_model import ParallelPlan, StagePlan
    g = lambda n, c: chips.ChipGroup(chips.CHIPS[n], c)
    chunked = ParallelPlan([StagePlan(g("A", 4), 4, 1, 1, False),
                            StagePlan(g("B", 4), 2, 1, 1, False)],
                           dp=1, microbatches=4, schedule="zb_v")
    with pytest.raises(ValueError, match="non-uniform"):
        HP.from_plan(chunked, execute_tp=True)
    assert heteroauto.runtime_path(chunked).startswith("refused")
    mixed_dp = ParallelPlan([StagePlan(g("A", 8), 4, 1, 2, False),
                             StagePlan(g("B", 4), 2, 1, 2, False)],
                            dp=2, microbatches=4)
    with pytest.raises(ValueError, match="non-uniform"):
        HP.from_plan(mixed_dp, execute_tp=True, execute_dp=True)
    # direct grouped-spec construction enforces the same contract
    with pytest.raises(ValueError, match="non-uniform"):
        HP.PipelineSpec(2, (1, 1, 1, 1), microbatches=4, stage_tp=(4, 2),
                        schedule="zb_v", n_chunks=2)
    with pytest.raises(ValueError, match="non-uniform"):
        HP.PipelineSpec(2, (1, 1), microbatches=4, stage_tp=(4, 2),
                        data_parallel=2)
    # a non-dividing model refuses through the same validator as uniform
    cfg = get_smoke_config("granite_8b")           # 2 heads, 2 kv heads
    spec = HP.PipelineSpec(2, (1, 1), microbatches=4, stage_tp=(4, 2))
    with pytest.raises(ValueError, match="num_heads"):
        HP.validate_spec_tp(cfg, spec)


def test_validate_tensor_parallel():
    """The tp runtime is dense-decoder-only and divisibility-checked."""
    dense = get_smoke_config("granite_8b")
    HP.validate_tensor_parallel(dense, 1)
    HP.validate_tensor_parallel(dense, 2)          # 2 heads, 2 kv, ff 512
    with pytest.raises(ValueError, match="num_heads"):
        HP.validate_tensor_parallel(dense, 4)      # 4 ∤ 2 heads
    moe = get_smoke_config("qwen3_moe_30b_a3b")
    HP.validate_tensor_parallel(moe, 1)            # tp=1 always fine
    with pytest.raises(NotImplementedError, match="dense"):
        HP.validate_tensor_parallel(moe, 2)
    ssm = get_smoke_config("mamba2_780m")
    with pytest.raises(NotImplementedError):
        HP.validate_tensor_parallel(ssm, 2)


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs ≥4 devices (CI runs an 8-device job)")
def test_spmd_tp_pipeline_in_process():
    """The 2-D mesh path on the REAL process devices (exercised by the
    8-virtual-device CI job; skipped on a 1-device laptop run)."""
    cfg = dataclasses.replace(get_smoke_config("granite_8b"),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 16), 0,
                                cfg.vocab_size)
    mesh = auto_mesh((2, 2), ("pipe", "tp"))
    spec = HP.PipelineSpec(2, (1, 1), microbatches=2, tensor_parallel=2)
    sp, mask = HP.split_stage_params(params, cfg, spec)
    loss = float(HP.make_spmd_pipeline_loss(cfg, spec, mesh)(
        sp, mask, tokens))
    refs = [float(M.loss_fn(params, cfg, {"tokens": tokens[i]},
                            remat=False)[0]) for i in range(2)]
    ref = float(np.mean(refs))
    assert abs(loss - ref) / max(abs(ref), 1e-9) < 2e-3, (loss, ref)
