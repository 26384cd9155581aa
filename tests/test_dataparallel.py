"""repro.core.dataparallel: the heterogeneous batch-domain partitioner,
bucketed grad-sync byte accounting, the dp modes of heteropp.from_plan /
heteroauto.search / cost_model.evaluate, the measured dgrad/wgrad
profiler split, the launcher's --data-parallel refusal, and the 8-device
(dp × pipe × tp) SPMD e2e helper (DESIGN.md §9)."""
import dataclasses
import os
import subprocess
import sys

import pytest
from hypothesis_compat import given, settings, st

from repro.comm.latency import p2p_latency
from repro.core import chips
from repro.core.cost_model import ParallelPlan, StagePlan, evaluate
from repro.core.dataparallel import (GradBuckets, bucketize,
                                     check_memory_caps, domain_cost,
                                     partition, sync_time,
                                     zero1_scatter_dim)
from repro.launch.mesh import auto_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# batch-domain partitioner
# ---------------------------------------------------------------------------

def test_partition_exact_proportional_split():
    dom = partition(12, [1.0, 2.0, 3.0])
    assert dom.allocations == (2, 4, 6)
    assert dom.uniform is False and dom.total == 12
    assert dom.max_allocation == 6


def test_partition_uniform_and_remainder():
    assert partition(8, [1.0] * 4).allocations == (2, 2, 2, 2)
    dom = partition(6, [1.0] * 4)          # identical replicas, 6 % 4 != 0
    assert sorted(dom.allocations) == [1, 1, 2, 2]
    assert dom.total == 6 and not dom.uniform


def test_partition_quantum_and_floor():
    dom = partition(12, [1.0, 5.0], quantum=2, min_per_replica=2)
    assert dom.total == 12
    assert all(a % 2 == 0 for a in dom.allocations)
    assert min(dom.allocations) >= 2
    with pytest.raises(ValueError):
        partition(3, [1.0, 1.0], quantum=2)      # not a quantum multiple
    with pytest.raises(ValueError):
        partition(2, [1.0, 1.0, 1.0])            # fewer mbs than replicas
    with pytest.raises(ValueError):
        partition(4, [1.0, 0.0])                 # non-positive throughput


def test_partition_refuses_non_multiple_floor():
    """Satellite (ISSUE 8): the old code silently rounded a non-multiple
    min_per_replica UP to whole quanta (floor_q = ceil(min/quantum)),
    over-granting the documented floor and raising "cannot give…" for
    totals the caller's floor would have admitted.  Now it refuses
    loudly; multiples are honored exactly."""
    with pytest.raises(ValueError, match="not a multiple of"):
        partition(12, [1.0, 5.0], quantum=2, min_per_replica=1)
    with pytest.raises(ValueError, match="not a multiple of"):
        partition(12, [1.0, 1.0], quantum=4, min_per_replica=6)
    # the old rounding refused this satisfiable split: floor 2 per
    # replica × 3 replicas = 6 units of quantum 2 fit in 12 exactly
    dom = partition(12, [1.0, 1.0, 1.0], quantum=2, min_per_replica=2)
    assert dom.total == 12 and min(dom.allocations) >= 2


def test_domain_cost_tied_pacing_lowest_index():
    """Satellite (ISSUE 8): equal pacing times resolve deterministically
    to the LOWEST replica index (strict ``>`` argmax, not a
    float-equality ``.index`` lookup)."""
    from repro.core.dataparallel import BatchDomain
    tied = BatchDomain(allocations=(4, 4, 2), throughputs=(1.0, 1.0, 0.5))
    c = domain_cost(tied)          # times (4.0, 4.0, 4.0) — all tied
    assert c["replica_times"] == pytest.approx([4.0, 4.0, 4.0])
    assert c["pacing_replica"] == 0
    assert c["iter_time"] == pytest.approx(4.0)
    # a genuinely larger later replica still wins
    c2 = domain_cost(BatchDomain((2, 6), (1.0, 1.0)))
    assert c2["pacing_replica"] == 1


@settings(max_examples=40)
@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=1, max_value=64),
       st.sampled_from([(1.0,), (1.0, 2.0), (0.5, 1.0, 4.0),
                        (3.0, 2.0, 1.0, 1.0)]))
def test_partition_properties(dp_scale, extra, rates):
    """Sum preserved, floor respected, and the rounding never strays
    more than one microbatch from the exact proportional share."""
    dp = len(rates)
    total = dp * dp_scale + extra
    dom = partition(total, rates)
    assert dom.total == total and dom.dp == dp
    assert min(dom.allocations) >= 1
    tot_rate = sum(rates)
    for a, r in zip(dom.allocations, rates):
        raw = total * r / tot_rate
        assert a >= 1 and abs(a - raw) < 1.0 + 1e-9 or a == 1, \
            (dom.allocations, raw)


@settings(max_examples=30)
@given(st.integers(min_value=1, max_value=16),
       st.sampled_from([1, 2, 4]),
       st.sampled_from([(1.0, 1.0), (1.0, 2.0), (0.5, 1.0, 4.0),
                        (3.0, 2.0, 1.0, 1.0)]))
def test_partition_quantum_properties(units, quantum, rates):
    """Satellite (ISSUE 8) properties: under any quantum the sum is
    preserved exactly, every allocation is a whole number of quanta, and
    the floor (one quantum here) is respected."""
    dp = len(rates)
    total = max(units, dp) * quantum
    dom = partition(total, rates, quantum=quantum,
                    min_per_replica=quantum)
    assert dom.total == total
    assert all(a % quantum == 0 for a in dom.allocations)
    assert min(dom.allocations) >= quantum


@settings(max_examples=30)
@given(st.integers(min_value=0, max_value=10),
       st.sampled_from([(1.0, 2.0), (1.0, 1.0, 3.0),
                        (0.5, 1.0, 2.0, 4.0)]))
def test_partition_monotone_in_throughput(extra, rates):
    """Satellite (ISSUE 8) property: bumping one replica's throughput
    never SHRINKS its allocation (with the others held fixed)."""
    dp = len(rates)
    total = 2 * dp + extra
    base = partition(total, rates)
    for i in range(dp):
        bumped = list(rates)
        bumped[i] *= 2.5
        dom = partition(total, bumped)
        assert dom.allocations[i] >= base.allocations[i], \
            (i, rates, base.allocations, dom.allocations)
        assert dom.total == total


@settings(max_examples=25)
@given(st.sampled_from(["1f1b", "gpipe", "zb_h1"]),
       st.integers(min_value=1, max_value=4),
       st.sampled_from([(5, 3), (2, 1), (4, 2, 1), (1, 6), (3, 3, 1)]))
def test_domain_tick_tables_padding_properties(schedule, S, allocations):
    """Satellite (ISSUE 8) properties of the per-replica tick padding
    (DESIGN.md §13): each replica's un-padded prefix IS the schedule's
    own program for its allocation, the pad region is fully inert
    (active = emit = False), and no ACTIVE op ever consumes a padded
    tick's output — every consumed neighbor/local value was produced by
    an ACTIVE tick, so padded ticks contribute exactly zero to loss and
    grads."""
    import numpy as np
    from repro.core import heteropp as HP
    stacked = HP.domain_tick_tables(schedule, S, allocations)
    pacing = HP.spmd_tick_tables(schedule, S, max(allocations))
    assert stacked.ticks == pacing.ticks          # priced == executed
    assert stacked.mb.shape == (stacked.ticks, len(allocations), S)
    for r, a in enumerate(allocations):
        own = HP.spmd_tick_tables(schedule, S, a)
        assert (stacked.mb[:own.ticks, r] == own.mb).all()
        assert (stacked.active[:own.ticks, r] == own.active).all()
        assert (stacked.emit[:own.ticks, r] == own.emit).all()
        assert not stacked.active[own.ticks:, r].any()   # pad is inert
        assert not stacked.emit[own.ticks:, r].any()
        # every emitting replica covers each of ITS microbatches once
        assert int(stacked.emit[:, r].sum()) == a
        # no active op consumes a padded (inactive) tick's output
        act, src = stacked.active[:, r], stacked.src[:, r]
        for t in range(stacked.ticks):
            for s in range(S):
                if not act[t, s] or src[t, s] == HP.SRC_INJECT:
                    continue
                if src[t, s] == HP.SRC_PREV:
                    prod = (s - 1) % S
                elif src[t, s] == HP.SRC_NEXT:
                    prod = (s + 1) % S
                else:                              # SRC_LOCAL
                    prod = s
                assert t > 0 and act[t - 1, prod], \
                    (schedule, S, allocations, r, t, s)


def test_domain_cost_closed_forms():
    # proportional allocations on 2:1 throughputs -> perfectly balanced
    dom = partition(9, [2.0, 1.0])
    c = domain_cost(dom)
    assert c["iter_time"] == pytest.approx(3.0)      # (6·0.5, 3·1.0)
    assert c["imbalance"] == pytest.approx(0.0)
    # a UNIFORM domain on the same replicas pays the slow replica
    uni = dataclasses.replace(dom, allocations=(4, 5))
    cu = domain_cost(uni)
    assert cu["iter_time"] == pytest.approx(5.0)     # pacing: 5·1.0
    assert cu["pacing_replica"] == 1
    assert cu["imbalance"] == pytest.approx(5.0 / 3.0 - 1.0)


def test_check_memory_caps():
    dom = partition(6, [1.0, 2.0])
    ok = check_memory_caps(dom, act_bytes_per_mb=1.0, cap_bytes=[1.5, 4.0])
    assert ok == [False, True]             # 2 sets > 1.5, 4 sets <= 4
    ok = check_memory_caps(dom, 1.0, [1.5, 4.0], inflight_cap=1)
    assert ok == [True, True]              # schedule stash cap binds first


# ---------------------------------------------------------------------------
# grad-sync bucket accounting
# ---------------------------------------------------------------------------

def test_bucketize_invariants():
    leaves = [("a", 10), ("b", 20), ("c", 5), ("d", 100), ("e", 1)]
    gb = bucketize(leaves, bucket_bytes=30)
    assert gb.total_bytes == 136
    assert [n for b in gb.buckets for n, _ in b] == list("abcde")  # order
    for sz, bucket in zip(gb.sizes, gb.buckets):
        assert sz <= 30 or len(bucket) == 1  # only a lone leaf overflows
    with pytest.raises(ValueError):
        bucketize(leaves, bucket_bytes=0)
    with pytest.raises(ValueError):
        bucketize([("x", -1)], bucket_bytes=8)


@settings(max_examples=30)
@given(st.integers(min_value=1, max_value=200),
       st.sampled_from([(3, 7, 11), (64, 64, 64, 64), (1, 1, 1),
                        (100, 1, 100, 1)]))
def test_bucketize_conserves_bytes(bucket_bytes, sizes):
    leaves = [(f"l{i}", s) for i, s in enumerate(sizes)]
    gb = bucketize(leaves, bucket_bytes=bucket_bytes)
    assert gb.total_bytes == sum(sizes)
    assert sum(len(b) for b in gb.buckets) == len(sizes)


def test_sync_time_matches_closed_forms():
    gb = bucketize([("a", 2 ** 20), ("b", 2 ** 20), ("c", 3 * 2 ** 20)],
                   bucket_bytes=2 * 2 ** 20)
    for dp in (2, 4):
        for transport in ("device_rdma", "cpu_tcp"):
            rs = sync_time(gb, dp, transport, "reduce_scatter")
            want = sum(2 * (dp - 1) * p2p_latency(transport, sz / dp)
                       for sz in gb.sizes)
            assert rs["total"] == pytest.approx(want)
            assert rs["messages"] == 2 * (dp - 1) * gb.num_buckets
            ps = sync_time(gb, dp, transport, "psum")
            assert ps["total"] == pytest.approx(
                2 * (dp - 1) * p2p_latency(transport, gb.total_bytes / dp))
            # same wire bytes, different message structure: flat psum
            # amortizes per-message latency best
            assert ps["wire_bytes"] == pytest.approx(rs["wire_bytes"])
            assert ps["total"] <= rs["total"] + 1e-12
    z = sync_time(gb, 1, "device_rdma", "psum")
    assert z["total"] == 0.0 and z["wire_bytes"] == 0.0
    with pytest.raises(ValueError):
        sync_time(gb, 2, "device_rdma", "allgather")


def test_bucketize_edge_cases():
    """Satellite (ISSUE 5): zero-byte leaves ride along in order, and a
    leaf exactly equal to bucket_bytes closes its bucket without
    spilling into the next."""
    gb = bucketize([("a", 0), ("b", 10), ("c", 0)], bucket_bytes=10)
    assert gb.total_bytes == 10
    assert [n for b in gb.buckets for n, _ in b] == ["a", "b", "c"]
    # exact-fit leaf: closes the bucket at exactly bucket_bytes
    gb = bucketize([("a", 10), ("b", 1)], bucket_bytes=10)
    assert gb.sizes == [10, 1] and gb.num_buckets == 2
    # exact fill by accumulation closes too
    gb = bucketize([("a", 4), ("b", 6), ("c", 1)], bucket_bytes=10)
    assert gb.sizes == [10, 1]
    # all-zero tree: one empty-byte bucket, zero sync time
    gb = bucketize([("a", 0), ("b", 0)], bucket_bytes=10)
    assert gb.num_buckets == 1 and gb.total_bytes == 0


def test_sync_time_edge_cases():
    """Satellite (ISSUE 5): dp=1 short-circuits to zero regardless of
    mode, and psum's bytes-proportional per-bucket attribution sums to
    the fused total."""
    gb = bucketize([("a", 2 ** 20), ("b", 3 * 2 ** 20), ("c", 2 ** 19)],
                   bucket_bytes=2 ** 20)
    for mode in ("psum", "reduce_scatter"):
        z = sync_time(gb, 1, "device_rdma", mode)
        assert z["total"] == 0.0 and z["messages"] == 0
        assert z["per_bucket"] == [0.0] * gb.num_buckets
    ps = sync_time(gb, 4, "cpu_tcp", "psum")
    assert sum(ps["per_bucket"]) == pytest.approx(ps["total"])
    # attribution is bytes-proportional bucket by bucket
    for share, sz in zip(ps["per_bucket"], gb.sizes):
        assert share == pytest.approx(ps["total"] * sz / gb.total_bytes)
    rs = sync_time(gb, 4, "cpu_tcp", "reduce_scatter")
    assert sum(rs["per_bucket"]) == pytest.approx(rs["total"])
    with pytest.raises(ValueError, match="dp"):
        sync_time(gb, 0, "device_rdma", "psum")


def test_replica_grad_norm_rejects_mismatched_specs():
    """Satellite (ISSUE 5): a specs tree with a different leaf count
    used to zip-truncate silently, dropping leaves from the global grad
    norm — it must raise instead."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.dataparallel.grad_sync import replica_grad_norm
    grads = {"a": jnp.ones((2, 2)), "b": jnp.ones((3,)),
             "extra": jnp.full((4,), 7.0)}
    specs = {"a": P(), "b": P()}          # missing the 'extra' leaf
    with pytest.raises(ValueError, match="leaves"):
        replica_grad_norm(grads, specs, {})
    # and the matched tree still computes the plain norm with no axes
    ok = replica_grad_norm({"a": grads["a"], "b": grads["b"]},
                           specs, {})
    want = float(jnp.sqrt(jnp.sum(jnp.square(grads["a"]))
                          + jnp.sum(jnp.square(grads["b"]))))
    assert float(ok) == pytest.approx(want)


def test_zero1_scatter_dim():
    assert zero1_scatter_dim((1, 4, 8), 2) == 1
    assert zero1_scatter_dim((1, 4, 8), 2, taken_dims=(1,)) == 2
    assert zero1_scatter_dim((1, 3, 5), 2) is None
    assert zero1_scatter_dim((6,), 3) == 0


def test_stage_param_buckets_cover_tree():
    """Bucket accounting over a REAL stage-parameter tree: every leaf
    lands in exactly one bucket and the bytes add up."""
    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.core import heteropp as HP
    from repro.core.dataparallel.grad_sync import tree_leaf_bytes

    cfg = get_smoke_config("granite_8b")
    spec = HP.PipelineSpec(2, (1, 1), microbatches=2)
    aps = HP.abstract_stage_params(cfg, spec)
    leaves = tree_leaf_bytes(aps)
    total = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree.leaves(aps))
    gb = bucketize(leaves, bucket_bytes=64 * 1024)
    assert gb.total_bytes == total
    assert sum(len(b) for b in gb.buckets) == len(jax.tree.leaves(aps))


# ---------------------------------------------------------------------------
# plan / cost-model / search integration
# ---------------------------------------------------------------------------

def _plan(dp=2, b=4, domain=None, schedule="1f1b"):
    g = lambda n, c: chips.ChipGroup(chips.CHIPS[n], c)
    return ParallelPlan([StagePlan(g("A", 4), 2, 1, 1, False),
                         StagePlan(g("B", 4), 2, 1, 1, False)],
                        dp=dp, microbatches=b, schedule=schedule,
                        batch_domain=domain)


def test_from_plan_dp_modes():
    """from_plan: dp stays a cost-model dimension by default; with
    execute_dp=True a uniform plan sets spec.data_parallel and a
    non-uniform batch domain threads into per-replica tick programs
    (spec.batch_domain — DESIGN.md §13)."""
    from repro.core import heteropp as HP
    uni = _plan()
    assert HP.from_plan(uni).data_parallel == 1
    spec = HP.from_plan(uni, execute_dp=True)
    assert spec.data_parallel == 2 and spec.microbatches == 4
    spec = HP.from_plan(uni, execute_tp=True, execute_dp=True)
    assert spec.tensor_parallel == 2 and spec.data_parallel == 2
    hetero = _plan(dp=2, b=5, domain=(5, 3))
    assert HP.from_plan(hetero).data_parallel == 1    # legacy path intact
    spec = HP.from_plan(hetero, execute_dp=True)
    assert spec.data_parallel == 2 and spec.batch_domain == (5, 3)
    assert spec.microbatches == 5          # the pacing allocation
    assert spec.total_microbatches == 8
    # an explicit microbatches override cannot rescale the split
    with pytest.raises(ValueError, match="cannot rescale"):
        HP.from_plan(hetero, microbatches=4, execute_dp=True)
    # a uniform EXPLICIT domain is executable (it IS the uniform split)
    spec = HP.from_plan(_plan(domain=(4, 4)), execute_dp=True)
    assert spec.data_parallel == 2 and spec.batch_domain == ()


def test_plan_json_roundtrip_preserves_batch_domain():
    import json
    p = _plan(dp=2, b=5, domain=(5, 3))
    p2 = ParallelPlan.from_dict(json.loads(json.dumps(p.to_dict())))
    assert p2.batch_domain == (5, 3)
    assert p2.batch_seqs == 8 and p.describe() == p2.describe()
    assert ParallelPlan.from_dict(
        json.loads(json.dumps(_plan().to_dict()))).batch_domain is None


def test_evaluate_dp_sync_memory_modes():
    """ZeRO-1 (reduce_scatter) shards optimizer state ×1/dp; the flat
    psum sync replicates it — strictly more memory per stage at dp>1."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("granite_8b")
    plan = _plan(dp=4, b=4)
    rs = evaluate(plan, cfg, 128, 4 * 128 * 4)
    ps = evaluate(plan, cfg, 128, 4 * 128 * 4, dp_sync="psum")
    assert rs.dp_sync == "reduce_scatter" and ps.dp_sync == "psum"
    for m_rs, m_ps in zip(rs.stage_mem_gb, ps.stage_mem_gb):
        assert m_ps > m_rs
    with pytest.raises(ValueError, match="dp_sync"):
        evaluate(plan, cfg, 128, 4 * 128 * 4, dp_sync="allreduce")


def test_search_uneven_dp_carries_batch_domain():
    """With uneven_dp the search may pick a dp that does not divide the
    batch: the plan carries the rounded batch domain and the cost model
    charges the pacing max allocation."""
    from repro.configs import get_smoke_config
    from repro.core import heteroauto
    cfg = get_smoke_config("granite_8b")
    groups = chips.cluster(("A", 4))
    seq = 128
    r = heteroauto.search(groups, cfg, 6 * seq, seq, two_stage=False,
                          dp_candidates=[4], uneven_dp=True)
    assert r.plan is not None and r.plan.dp == 4
    assert r.plan.batch_domain is not None
    assert sorted(r.plan.batch_domain) == [1, 1, 2, 2]
    assert r.plan.microbatches == 2 == max(r.plan.batch_domain)
    assert r.plan.batch_seqs == 6
    # and the runtime EXECUTES the non-uniform domain (DESIGN.md §13)
    from repro.core import heteropp as HP
    spec = HP.from_plan(r.plan, execute_dp=True)
    assert spec.batch_domain == tuple(r.plan.batch_domain)
    assert spec.total_microbatches == 6
    from repro.core.heteroauto import runtime_path
    assert runtime_path(r.plan).endswith("+uneven-dp")


def test_search_divisible_dp_stays_uniform():
    from repro.configs import get_smoke_config
    from repro.core import heteroauto
    cfg = get_smoke_config("granite_8b")
    groups = chips.cluster(("A", 4))
    seq = 128
    r = heteroauto.search(groups, cfg, 8 * seq, seq, two_stage=False,
                          dp_candidates=[4], uneven_dp=True)
    assert r.plan is not None and r.plan.dp == 4
    assert r.plan.batch_domain is None and r.plan.microbatches == 2


# ---------------------------------------------------------------------------
# measured dgrad/wgrad satellite
# ---------------------------------------------------------------------------

def test_measure_layer_profile_times_dgrad_wgrad():
    from repro.configs import get_smoke_config
    from repro.core.profiler import measure_layer_profile
    prof = measure_layer_profile(get_smoke_config("granite_8b"), 64,
                                 iters=1)
    for k in ("t_fwd", "t_bwd", "t_recomp", "t_dgrad", "wgrad_frac"):
        assert k in prof and prof[k] > 0, (k, prof)
    assert prof["t_wgrad"] >= 0.0        # t_bwd − t_dgrad; noise-clamped
    assert 0.0 < prof["wgrad_frac"] < 1.0


def test_plan_to_schedule_inputs_prefers_measured_wgrad():
    from repro.configs import get_smoke_config
    from repro.core.schedule import plan_to_schedule_inputs
    cfg = get_smoke_config("granite_8b")
    plan = _plan()
    *_, wf_analytic = plan_to_schedule_inputs(plan, cfg, 128)
    assert all(0.0 < w < 1.0 for w in wf_analytic)
    measured = {"A": {"wgrad_frac": 0.25, "t_fwd": 1e-3}}
    *_, wf = plan_to_schedule_inputs(plan, cfg, 128, measured=measured)
    assert wf[0] == 0.25                       # chip A: measured wins
    assert wf[1] == wf_analytic[1]             # chip B: analytic kept


def test_measure_layer_profile_per_kernel_backends():
    """The profiler times the kernels the chosen backend executes:
    per-kernel rows + a decode-step time, tagged with the resolved
    backend, and the pallas run is a distinct measurement."""
    from repro.configs import get_smoke_config
    from repro.core.profiler import measure_layer_profile
    cfg = get_smoke_config("granite_8b")
    out = {be: measure_layer_profile(cfg, 64, iters=1, backend=be)
           for be in ("einsum", "pallas")}
    for be, m in out.items():
        assert m["backend"] == be
        for key in ("t_attn", "t_rmsnorm", "t_decode"):
            assert key in m and m[key] > 0, (be, key, m)
    assert out["pallas"] != out["einsum"]


def test_evaluate_and_replay_consume_measured_times():
    """The full measured overlay (not just wgrad_frac) reaches both
    rankers: evaluate() reprices the plan and plan_to_schedule_inputs
    feeds the replay the measured per-stage times."""
    from repro.configs import get_smoke_config
    from repro.core.schedule import plan_to_schedule_inputs, simulate_plan
    cfg = get_smoke_config("granite_8b")
    plan = _plan()
    meas = {"A": {"t_fwd": 5e-3, "t_bwd": 9e-3, "wgrad_frac": 0.25}}

    base = evaluate(plan, cfg, 128, 1e6)
    mod = evaluate(plan, cfg, 128, 1e6, measured=meas)
    assert mod.iter_time > base.iter_time      # measured times dominate

    tf0, *_ = plan_to_schedule_inputs(plan, cfg, 128)
    tf1, tb1, _, _, _, wf1 = plan_to_schedule_inputs(plan, cfg, 128,
                                                     measured=meas)
    lps = plan.stages[0].layers_per_stage
    assert tf1[0] == pytest.approx(lps * 5e-3)   # chip A: measured t_fwd
    assert tb1[0] == pytest.approx(lps * 9e-3)
    assert tf1[-1] == tf0[-1]                    # chip B: analytic kept
    r = simulate_plan(plan, cfg, 128, measured=meas)
    r0 = simulate_plan(plan, cfg, 128)
    assert r.makespan > r0.makespan


# ---------------------------------------------------------------------------
# launcher refusal + SPMD e2e (subprocess; forced virtual devices)
# ---------------------------------------------------------------------------

def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + ":" + \
        env.get("PYTHONPATH", "")
    return env


def test_train_refuses_data_parallel_without_pipeline():
    """--data-parallel without a pipeline path must refuse loudly
    instead of silently ignoring the flag (mirrors the PR 3
    --tensor-parallel refusal)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "qwen1p5_0p5b", "--smoke", "--data-parallel", "2", "--steps", "1"],
        capture_output=True, text=True, timeout=600, env=_env(), cwd=ROOT)
    assert r.returncode != 0
    assert "--data-parallel 2 only applies" in r.stderr, r.stderr[-800:]
    assert "--pipeline-parallel" in r.stderr


@pytest.mark.e2e
def test_spmd_dp_pipeline_subprocess():
    """3-D (dp × pipe × tp) pipeline on 8 virtual devices: dp=2 matches
    the dp=1 pipeline and the monolithic model; both grad-sync modes
    agree; uniform-dp plans execute bit-identically to the direct spec
    (DESIGN.md §9).  Non-uniform domains are covered by
    run_spmd_uneven_dp_pipeline.py / test_uneven_dp_exec.py
    (DESIGN.md §13)."""
    script = os.path.join(ROOT, "tests", "helpers",
                          "run_spmd_dp_pipeline.py")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=600, env=_env(), cwd=ROOT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "DP_OK" in r.stdout


@pytest.mark.skipif(
    len(__import__("jax").devices()) < 8,
    reason="needs ≥8 devices (CI runs an 8-device job)")
def test_spmd_dp_pipeline_in_process():
    """The 3-D mesh path on the REAL process devices (exercised by the
    8-virtual-device CI job; skipped on a 1-device laptop run)."""
    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.core import heteropp as HP
    from repro.models import model as M

    cfg = dataclasses.replace(get_smoke_config("granite_8b"),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 16), 0,
                                cfg.vocab_size)
    mesh = auto_mesh((2, 2, 2), ("dp", "pipe", "tp"))
    spec = HP.PipelineSpec(2, (1, 1), microbatches=2, tensor_parallel=2,
                           data_parallel=2)
    sp, mask = HP.split_stage_params(params, cfg, spec)
    loss = float(HP.make_spmd_pipeline_loss(cfg, spec, mesh)(
        sp, mask, tokens))
    refs = [float(M.loss_fn(params, cfg, {"tokens": tokens[i]},
                            remat=False)[0]) for i in range(4)]
    ref = float(np.mean(refs))
    assert abs(loss - ref) / max(abs(ref), 1e-9) < 2e-3, (loss, ref)
