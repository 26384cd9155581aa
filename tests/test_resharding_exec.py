"""Reshard execution harness (§5 / DESIGN.md §12).

Three layers of pinning for the boundary collective the grouped stage
runtime now executes:

* value equivalence — ``naive`` and ``sr_ag`` are BIT-identical on a
  (pipe × tp) virtual mesh across dtypes, shapes and mesh splits (they
  reorder the same gather, they must not differ in a single ULP);
* HLO byte accounting — the docstring claim in ``resharding.py`` made
  inspectable: which collective carries how many bytes.  naive's
  cross-stage ``collective-permute`` moves the FULL feature dim (tp×
  the shard), sr_ag's moves the 1/tp shard and the tp-group
  ``all-gather`` consumes the permute's OUTPUT (send-then-gather);
* closed-form properties (via ``hypothesis_compat``) — dominance,
  monotonicity and the sr_ag-wins-when-sharded rule that
  ``choose_strategy`` (and through it ``from_plan`` and
  ``cost_model.evaluate``) act on.
"""
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.resharding import (boundary_time, choose_strategy,
                                   naive_cost, reshard, sr_ag_cost)

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs ≥8 devices (CI runs an 8-device job)")


def _mesh(pipe, tp):
    devs = np.array(jax.devices()[:pipe * tp]).reshape(pipe, tp)
    return jax.sharding.Mesh(devs, ("pipe", "tp"))


def _sharded(key, shape, dtype, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jax.random.normal(key, shape).astype(dtype)
    return jax.device_put(x, NamedSharding(mesh, P("pipe", None, "tp")))


# ------------------------- value equivalence -------------------------------

@needs8
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("pipe,tp,shape", [
    (2, 4, (2, 8, 16)),
    (4, 2, (4, 4, 8)),
    (2, 4, (2, 3, 32)),     # odd microbatch dim, wider feature
])
def test_reshard_equivalence_in_process(dtype, pipe, tp, shape):
    """naive and sr_ag reorder the same gather — bit-identical values,
    and every stage s+1 receives exactly stage s's activation."""
    mesh = _mesh(pipe, tp)
    x = _sharded(jax.random.PRNGKey(0), shape, dtype, mesh)
    a = np.asarray(reshard(x, mesh, strategy="naive")).astype(np.float32)
    b = np.asarray(reshard(x, mesh, strategy="sr_ag")).astype(np.float32)
    np.testing.assert_array_equal(a, b)
    xs = np.asarray(x).astype(np.float32)
    for s in range(1, pipe):
        np.testing.assert_array_equal(a[s], xs[s - 1])
    # ppermute has no source for stage 0: it receives zeros
    np.testing.assert_array_equal(a[0], np.zeros_like(a[0]))


@needs8
def test_reshard_grad_flows_through_both_in_process():
    """Both schedules are differentiable (the grouped runtime trains
    through its boundary collective): the cotangent routes back to the
    producing stage with identical values."""
    mesh = _mesh(2, 4)
    x = _sharded(jax.random.PRNGKey(1), (2, 4, 16), jnp.float32, mesh)
    grads = [jax.grad(lambda v: jnp.sum(
        reshard(v, mesh, strategy=s) ** 2))(x) for s in ("naive", "sr_ag")]
    ga, gb = (np.asarray(g) for g in grads)
    np.testing.assert_array_equal(ga, gb)
    # only stage 0's activation is consumed downstream; the last stage's
    # output leaves the (2-stage) pipe, so its cotangent is zero
    assert np.abs(ga[0]).sum() > 0
    np.testing.assert_array_equal(ga[1], np.zeros_like(ga[1]))


# ------------------------- HLO byte accounting -----------------------------
# Asserted on the StableHLO lowering (per-device types, dtype-exact,
# direct use-def chains); the compiled module upcasts bf16 collectives
# on CPU and fuses copies in between, which would blur both claims.

_CP_LINE = re.compile(
    r'"stablehlo\.collective_permute"\((%\w+)\).*'
    r'\(tensor<([0-9x]+)x(?:f32|bf16)>\)')
_AG_LINE = re.compile(r'"stablehlo\.all_gather"\((%\w+)\).*')


def _lowered(mesh, x, strategy):
    f = jax.jit(lambda v: reshard(v, mesh, strategy=strategy))
    return f.lower(x).as_text()


@needs8
@pytest.mark.parametrize("dtype,itemsize", [(jnp.float32, 4),
                                            (jnp.bfloat16, 2)])
def test_reshard_hlo_byte_accounting_in_process(dtype, itemsize):
    """The cross-stage collective_permute carries the docstring's bytes:
    the full activation under naive (tp redundant feature shards wide),
    exactly the 1/tp shard under sr_ag — and sr_ag's tp all_gather
    consumes the permute's OUTPUT (send-then-gather) while naive
    permutes the gather's output (gather-then-send)."""
    pipe, tp, shape = 2, 4, (2, 8, 16)
    mesh = _mesh(pipe, tp)
    x = _sharded(jax.random.PRNGKey(0), shape, dtype, mesh)
    shard_bytes = (shape[0] // pipe) * shape[1] * (shape[2] // tp) * itemsize

    for strategy, want_bytes in (("naive", shard_bytes * tp),
                                 ("sr_ag", shard_bytes)):
        txt = _lowered(mesh, x, strategy)
        (cp,) = _CP_LINE.findall(txt)
        cp_arg, dims = cp
        elems = int(np.prod([int(d) for d in dims.split("x")]))
        assert elems * itemsize == want_bytes, (strategy, dims)
        (ag_arg,) = _AG_LINE.findall(txt)
        cp_result = re.search(
            r"(%\w+) = \"stablehlo\.collective_permute\"", txt).group(1)
        ag_result = re.search(
            r"(%\w+) = \"stablehlo\.all_gather\"", txt).group(1)
        if strategy == "sr_ag":
            assert ag_arg == cp_result, txt   # gather AFTER the hop
        else:
            assert cp_arg == ag_result, txt   # hop AFTER the gather


@needs8
def test_reshard_hlo_gather_axis_in_process():
    """The all_gather runs over the tp groups (devices of ONE pipe row,
    on the feature dim) and the permute crosses pipe rows — the axes
    the byte model assigns to intra- vs cross-island traffic."""
    pipe, tp = 2, 4
    mesh = _mesh(pipe, tp)
    x = _sharded(jax.random.PRNGKey(0), (2, 8, 16), jnp.float32, mesh)
    tp_groups = "dense<[[0, 1, 2, 3], [4, 5, 6, 7]]>"
    pipe_pairs = "dense<[[0, 4], [1, 5], [2, 6], [3, 7]]>"
    for s in ("naive", "sr_ag"):
        txt = _lowered(mesh, x, s)
        (ag,) = re.findall(r'"stablehlo\.all_gather"[^\n]*', txt)
        assert f"replica_groups = {tp_groups}" in ag, (s, ag)
        assert "all_gather_dim = 2" in ag, (s, ag)
        (cp,) = re.findall(r'"stablehlo\.collective_permute"[^\n]*', txt)
        assert f"source_target_pairs = {pipe_pairs}" in cp, (s, cp)


def test_reshard_equivalence_subprocess():
    """tier-1 (single-device) coverage of the same equivalence on forced
    virtual devices, including the bfloat16 + transposed-mesh corner."""
    script = textwrap.dedent("""
        from repro.launch.hostdevices import force_host_device_count
        force_host_device_count(8)
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.resharding import reshard
        from repro.launch.mesh import auto_mesh
        for pipe, tp, dt in ((2, 4, jnp.float32), (4, 2, jnp.bfloat16)):
            mesh = auto_mesh((pipe, tp), ("pipe", "tp"))
            x = jax.random.normal(
                jax.random.PRNGKey(0), (pipe, 4, 16)).astype(dt)
            x = jax.device_put(
                x, NamedSharding(mesh, P("pipe", None, "tp")))
            a = np.asarray(reshard(x, mesh, strategy="naive"))
            b = np.asarray(reshard(x, mesh, strategy="sr_ag"))
            np.testing.assert_array_equal(
                a.astype(np.float32), b.astype(np.float32))
            np.testing.assert_array_equal(
                a[1:].astype(np.float32),
                np.asarray(x)[:-1].astype(np.float32))
        print("RESHARD_EXEC_OK")
    """)
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + ":" + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "RESHARD_EXEC_OK" in r.stdout


# ------------------------- closed-form properties --------------------------

_TPS = st.sampled_from([1, 2, 4, 8])
_NICS = st.sampled_from([12.5e9, 25e9])
_INTRAS = st.sampled_from([100e9, 200e9, 300e9])
_LANES = st.sampled_from([1, 2, 4, 8])


@given(_TPS, _TPS)
@settings(max_examples=16, deadline=None)
def test_cost_dominance(ts, td):
    """sr_ag puts exactly ONE activation copy on the boundary; naive's
    total wire bytes are tp_src redundant copies.  The intra-island
    gather sr_ag pays instead stays strictly below one copy."""
    act = 64 << 20
    n, s = naive_cost(act, ts, td), sr_ag_cost(act, ts, td)
    assert s.cross_bytes == act
    assert n.cross_bytes * n.cross_messages == act * ts
    assert s.cross_bytes <= n.cross_bytes * n.cross_messages
    if ts > 1:
        assert s.cross_bytes < n.cross_bytes * n.cross_messages
    assert 0 <= s.intra_bytes < act
    assert s.cross_messages == max(ts, td)


@given(_TPS, _TPS, _NICS, _INTRAS, _LANES,
       st.sampled_from(["naive", "sr_ag"]))
@settings(max_examples=40, deadline=None)
def test_boundary_time_monotone_in_act_bytes(ts, td, nic, intra, lanes,
                                             strategy):
    kw = dict(nic_bw=nic, intra_bw=intra, nics_per_node=lanes,
              strategy=strategy)
    ts_list = [boundary_time(act, ts, td, **kw)
               for act in (1 << 20, 8 << 20, 64 << 20)]
    assert ts_list == sorted(ts_list)
    assert ts_list[0] < ts_list[-1]


@given(_TPS, _TPS, _NICS, _INTRAS,
       st.sampled_from(["naive", "sr_ag"]))
@settings(max_examples=40, deadline=None)
def test_boundary_time_nonincreasing_in_nics(ts, td, nic, intra, strategy):
    """More NICs can only add parallel lanes for the cross messages."""
    act = 64 << 20
    times = [boundary_time(act, ts, td, nic_bw=nic, intra_bw=intra,
                           nics_per_node=l, strategy=strategy)
             for l in (1, 2, 4, 8)]
    assert times == sorted(times, reverse=True)


@given(_TPS, _TPS, _NICS, _INTRAS, _LANES)
@settings(max_examples=60, deadline=None)
def test_sr_ag_wins_whenever_source_is_sharded(ts, td, nic, intra, lanes):
    """With tp_src > 1 naive sends redundant copies, so under any
    realistic bandwidth split (intra ≫ NIC) sr_ag is never slower —
    and choose_strategy (which from_plan and evaluate both consume)
    agrees."""
    act = 64 << 20
    kw = dict(nic_bw=nic, intra_bw=intra, nics_per_node=lanes)
    t_sr = boundary_time(act, ts, td, strategy="sr_ag", **kw)
    t_nv = boundary_time(act, ts, td, strategy="naive", **kw)
    if ts > 1:
        assert t_sr <= t_nv
        assert choose_strategy(ts, td, **kw) == "sr_ag"
    else:
        # equal-cost layouts tie-break to the paper's default
        assert choose_strategy(ts, td, **kw) in ("sr_ag", "naive")
        assert choose_strategy(ts, td, **kw) == (
            "sr_ag" if t_sr <= t_nv else "naive")


def test_executed_and_priced_strategies_agree():
    """Cross-layer pin: the reshard strategy from_plan bakes into the
    executed spec equals the one cost_model.evaluate prices, boundary by
    boundary — the two consult the same choose_strategy."""
    from repro.core import chips, heteropp as HP
    from repro.core.cost_model import ParallelPlan, StagePlan, evaluate
    g = lambda n, c: chips.ChipGroup(chips.CHIPS[n], c)
    plan = ParallelPlan(
        [StagePlan(g("A", 4), 4, 1, 2, False),
         StagePlan(g("B", 2), 2, 1, 1, False),
         StagePlan(g("C", 1), 1, 1, 1, False)],
        dp=1, microbatches=4, schedule="1f1b")
    spec = HP.from_plan(plan, execute_tp=True)
    from repro.configs import get_config
    cfg = get_config("h2_100b")
    cost = evaluate(plan, cfg, 4096, 4 * 4096, allow_offload=True)
    assert spec.reshard == tuple(cost.reshard)
    assert len(cost.t_reshard) == len(plan.stages)
    assert cost.t_reshard[0] == 0.0
    assert all(t > 0 for t, r in zip(cost.t_reshard[1:], cost.reshard)
               if r != "none")
