"""Host-driven per-tick tracer for the SPMD pipeline (DESIGN.md §14).

The production train step scans the whole tick program inside ONE
``shard_map`` call, so per-tick wall times are invisible to the host.
This module re-drives the SAME device-local tick body the scan runs
(``replica_fn.tick_step`` — the cores attach it exactly so the traced
program cannot drift from the executed one) one host call per tick:
the carry leaves round-trip through a jit'd single-tick ``shard_map``
(compiled once — every row slice has a constant shape), each call
fenced with ``block_until_ready`` so the measured interval is the real
device time of that tick.  A warm-up pass absorbs compilation; the
loss-denominator accumulated by the traced pass is cross-checked
against the closed form (units × Σ microbatches × tokens/microbatch),
which catches carry-threading or routing bugs in the tracer itself.

Opt-in only (``train.py --trace``): the default hot path never imports
this module and its step function is untouched.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .trace import SOURCE_EXECUTED, build_trace

__all__ = ["trace_spmd_pipeline", "device_memory_highwater"]


def device_memory_highwater() -> Optional[int]:
    """Max ``peak_bytes_in_use`` across local devices, or None where the
    backend keeps no memory stats (host CPU platforms return None).  Any
    other failure to read them propagates."""
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and stats.get("peak_bytes_in_use") is not None:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def trace_spmd_pipeline(cfg, spec, mesh, stage_params, mask, tokens, *,
                        remat: bool = True,
                        schedule: Optional[str] = None) -> dict:
    """Execute ``spec``'s tick program one fenced host call at a time
    and return the executed-timeline trace dict (``obs.trace`` schema,
    ``source="executed"``).

    ``stage_params``/``mask``/``tokens`` are exactly the arrays the
    train step consumes (``split_stage_params`` layout; tokens in the
    ``(total_mb, mb_size, seq)`` layout).  The trace carries one span
    per (replica, stage) per ACTIVE tick — every active stage of a tick
    shares the tick's fenced wall time, which is precisely what the
    tick-synchronous runtime executes — plus ``metadata.wall_s``,
    per-tick times, and the denominator cross-check."""
    from ..core.heteropp import (_pipeline_replica_core,
                                 _prepare_domain_tokens)
    from ..core.jax_compat import shard_map
    from ..core.schedules import get_schedule

    replica_fn, in_specs, manual, out_axes = _pipeline_replica_core(
        cfg, spec, mesh, remat=remat, schedule=schedule)
    tables = replica_fn.tick_tables
    xs = replica_fn.tick_xs
    tokens = _prepare_domain_tokens(spec, tokens)
    mb_size, s_seq = int(tokens.shape[1]), int(tokens.shape[2])

    def tick_fn(stage_params, mask, tokens, carry, row):
        local = tuple(c[0] for c in carry)
        out = replica_fn.tick_step(stage_params, mask, tokens, local, row)
        return tuple(o[None] for o in out)

    shapes = replica_fn.carry_shapes(mb_size, s_seq)
    nmem = 1
    for a in out_axes:
        nmem *= mesh.shape[a]
    carry_specs = tuple(P(out_axes) for _ in shapes)
    row_specs = tuple(P() for _ in xs)
    smapped = shard_map(
        tick_fn, mesh=mesh,
        in_specs=in_specs + (carry_specs, row_specs),
        out_specs=carry_specs, manual_axes=manual)
    jitted = jax.jit(smapped)

    def init_carry():
        return tuple(jnp.zeros((nmem,) + tuple(shape), dtype)
                     for shape, dtype in shapes)

    rows = [tuple(x[t] for x in xs) for t in range(tables.ticks)]
    # warm-up: the full program once (single compile — constant shapes),
    # so the timed pass below measures execution, not tracing
    carry = init_carry()
    for row in rows:
        carry = jitted(stage_params, mask, tokens, carry, row)
    jax.block_until_ready(carry)

    carry = init_carry()
    tick_times = []
    for row in rows:
        t0 = time.perf_counter()
        carry = jitted(stage_params, mask, tokens, carry, row)
        jax.block_until_ready(carry)
        tick_times.append(time.perf_counter() - t0)

    # denominator cross-check: the traced pass must have streamed every
    # microbatch through the full program exactly once
    denom = float(np.sum(np.asarray(carry[-1])))
    expected = float(replica_fn.denom_units * spec.total_microbatches
                     * mb_size * (s_seq - 1))
    if abs(denom - expected) > 0.5:
        raise RuntimeError(
            f"traced denominator {denom} != expected {expected}: the "
            f"tracer's tick threading diverged from the program")

    sched = get_schedule(schedule or spec.schedule)
    S = spec.num_stages
    active = np.asarray(tables.active)
    mb_tab = np.asarray(tables.mb)
    ck_tab = np.asarray(tables.chunk)
    dp = spec.data_parallel
    spans = []
    start = 0.0
    for t, dt in enumerate(tick_times):
        end = start + dt
        for s in range(S):
            for r in range(dp):
                cell = (t, r, s) if active.ndim == 3 else (t, s)
                if not active[cell]:
                    continue
                ck = int(ck_tab[cell])
                spans.append({
                    "replica": r, "stage": s, "chunk": ck, "kind": "F",
                    "mb": int(mb_tab[cell]),
                    "g": sched.global_stage(s, ck, S),
                    "start_s": start, "end_s": end, "tick": t,
                })
        start = end
    mem = device_memory_highwater()
    return build_trace(
        spans, source=SOURCE_EXECUTED, schedule=sched.name,
        num_stages=S, n_chunks=spec.n_chunks, dp=dp, ticks=tables.ticks,
        extra_meta={"wall_s": sum(tick_times),
                    "tick_times_s": tick_times,
                    "denom_check": {"measured": denom,
                                    "expected": expected},
                    "peak_bytes_in_use": mem})
