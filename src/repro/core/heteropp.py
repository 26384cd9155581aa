"""HeteroPP runtime — heterogeneous pipeline parallelism in JAX.

Two execution paths (DESIGN.md §2 explains the SPMD constraint, §7 the
schedule/runtime contract):

* ``simulate_*``   — sequential per-stage execution on the local device(s),
  bit-identical to the monolithic model: the numerics oracle for tests and
  the tick-level schedule studies.

* ``spmd`` path    — ``jax.shard_map`` manual over the ``pipe`` axis (and,
  when ``PipelineSpec.tensor_parallel > 1``, a second manual ``tp`` axis;
  when ``PipelineSpec.data_parallel > 1``, a third manual ``dp`` axis: up
  to a 3-D ``(dp, pipe, tp)`` mesh — DESIGN.md §8–§9): every device runs
  the same program; per-stage *data* (padded stacked layer weights) differs.
  Each pipe ROW holds ONE physical stage — ``n_chunks`` (v) chunk
  slots of layers for virtual-stage schedules, stacked ``(S, v, Lcmax,
  ...)``; single-chunk specs keep the flat ``(S, Lmax, ...)`` layout.
  Within a pipe row the tp axis shards each layer Megatron-style
  (``sharding/rules.py``: QKV/MLP-up column-parallel, the two ``wo``
  row-parallel) and ``_stage_forward`` closes each sub-block with a
  ``psum`` over tp, so activations re-enter the pipe stream replicated
  and the tick-synchronous ppermute keeps moving along pipe rows only.
  The dp axis replicates the whole (pipe × tp) pipeline: each dp member
  runs its own microbatches — a UNIFORM allocation b, or a non-uniform
  ``batch_domain`` where replica r runs the schedule's tick program for
  its own ``allocations[r]``, padded with bit-inert no-op ticks to the
  pacing replica's length (``domain_tick_tables``, DESIGN.md §13;
  ``repro.core.dataparallel``) — no collective touches dp during
  the tick scan, and gradients close with ONE bucketed dp sync
  (``grad_sync``: flat psum, or ZeRO-1 reduce-scatter + all-gather with
  dp-sharded optimizer state) before the optimizer step.
  Microbatches stream through a tick-synchronous scan whose static
  tick→(microbatch, chunk, route) program is derived from the plan's
  ``repro.core.schedules`` Schedule by :func:`spmd_tick_tables`:
  gpipe/1f1b/zb_h1 are the single-chunk diagonal stream, ``interleaved``
  streams chunk-major with a circular wrap S−1 → 0, ``zb_v`` zig-zags
  the V placement with a device-local turn.  Stage-to-stage activation
  transfer is ``jax.lax.ppermute`` (the DiComm device-direct analogue),
  one hop each way per tick.  Backward is derived by autodiff through
  the scan + ppermute — a GPipe-memory schedule with per-layer remat;
  1F1B/ZB-H1/ZB-V bubble *timing* is modeled by the cost model's α
  closed forms (gpipe/1f1b 1, zb_h1 2/3, interleaved 1/v, zb_v 1/6) and
  the generic schedule simulator, and the schedules' in-flight memory
  profiles (gpipe b, 1f1b/zb_h1 min(b, S−k), interleaved warmup/v, zb_v
  min(b, S)) drive the cost model's feasibility check.

Non-uniform layer counts: global chunk-stages are padded to the max
layer count and masked (idle compute on short stages is the price of
SPMD; HeteroAuto's cost model accounts the true per-stage time).

Non-uniform per-stage tp (``PipelineSpec.stage_tp`` — DESIGN.md §12):
the GROUPED runtime lays the pipeline out on a FLAT 1-D pipe mesh of
Σ tp_s devices where stage s owns a contiguous group of tp_s of them.
Each device runs one program on its zero-padded Megatron shard; the
stage-interior psum and the stage-boundary transfer are both one fused
``all_gather`` over the flat axis plus a per-device masked contraction
(:func:`group_layout` / :func:`_boundary_tables`), with the boundary
rows realizing the per-boundary ``reshard`` strategy (``sr_ag`` vs
``naive`` — ``core/resharding.py``) at the value level.  Single-chunk
schedules and dp == 1 only; ``from_plan(execute_tp=True)`` builds these
specs from plans whose stages disagree on tp.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import attention, layers, model as M, transformer as tfm
from ..models.config import ModelConfig
from ..obs import scopes
from ..optim import adamw

PyTree = Any


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """Runtime pipeline layout.

    ``num_stages`` is the PHYSICAL pipe-axis size S.  ``layers_per_stage``
    is indexed by GLOBAL chunk-stage g in ascending model-layer order
    (length S·n_chunks; for single-chunk schedules g == physical stage).
    The schedule's chunk placement decides which physical stage hosts
    which global chunk-stage (``Schedule.global_stage`` — chunk-major for
    interleaved, V-shaped for zb_v).  ``recompute`` stays per PHYSICAL
    stage.  ``tensor_parallel`` is the UNIFORM tp degree realized inside
    each pipe row on the 2-D ``(pipe, tp)`` mesh (DESIGN.md §8); 1 keeps
    the 1-D pipe mesh.  ``data_parallel`` replicates the whole
    (pipe × tp) pipeline over a leading ``dp`` mesh axis (DESIGN.md §9):
    ``microbatches`` is the PACING replica's allocation b.  A uniform
    batch domain (empty ``batch_domain``) gives every replica b
    microbatches (global batch dp·b); a NON-UNIFORM ``batch_domain``
    gives replica r its own ``batch_domain[r]`` (global batch
    Σ allocations), executed as per-replica tick programs padded to the
    pacing replica's length (DESIGN.md §13)."""
    num_stages: int
    layers_per_stage: Tuple[int, ...]     # per global chunk-stage
    microbatches: int
    recompute: Tuple[bool, ...] = ()      # per physical stage
    pipe_axis: str = "pipe"
    schedule: str = "1f1b"                # repro.core.schedules name
    n_chunks: int = 1                     # virtual stages per device (v)
    tensor_parallel: int = 1              # uniform tp inside each pipe row
    tp_axis: str = "tp"
    data_parallel: int = 1                # pipeline replicas over dp
    dp_axis: str = "dp"
    # NON-UNIFORM batch domain (DESIGN.md §13): ``batch_domain[r]`` is dp
    # replica r's microbatch allocation (throughput-proportional splits
    # from ``repro.core.dataparallel.batch_domain``).  Empty means
    # uniform — every replica runs ``microbatches``.  When non-empty the
    # pacing (max) allocation must equal ``microbatches`` and each
    # replica runs the schedule's tick program for ITS OWN allocation,
    # padded with bit-inert no-op ticks to the pacing replica's length
    # (``domain_tick_tables``).  Uniform non-empty domains normalize to
    # () so the legacy bit-exact path is taken.
    batch_domain: Tuple[int, ...] = ()
    # dp grad-sync bucket budget (DESIGN.md §10): with bucket_bytes > 0
    # the psum sync mode coalesces gradient leaves into fused per-bucket
    # all-reduces issued in wgrad-completion order (later chunk slots
    # first — the order the §10 overlap model assumes); 0 keeps the
    # legacy one-collective-per-leaf program.  ``from_plan`` threads a
    # searched plan's bucket_bytes here.
    bucket_bytes: int = 0
    # NON-UNIFORM per-stage tp — the grouped stage runtime (DESIGN.md
    # §12).  When non-empty, ``stage_tp[s]`` is physical stage s's tp
    # degree and the pipeline runs on a FLAT 1-D ``pipe_axis`` mesh of
    # sum(stage_tp) devices, stage s owning a contiguous group of
    # stage_tp[s] of them, instead of the rectangular (pipe, tp) mesh.
    # Requires tensor_parallel == 1 (the uniform field is unused),
    # n_chunks == 1 (single-chunk schedules only: the grouped boundary
    # collective streams forward along adjacent groups) and
    # data_parallel == 1.  ``reshard`` names the boundary collective per
    # stage boundary (len S−1): "none" / "naive" / "sr_ag"
    # (core/resharding.py); auto-filled when left empty ("none" at
    # equal-tp boundaries, "sr_ag" elsewhere — from_plan overrides with
    # the per-boundary ``resharding.choose_strategy`` argmin).
    stage_tp: Tuple[int, ...] = ()
    reshard: Tuple[str, ...] = ()

    def __post_init__(self):
        assert len(self.layers_per_stage) == self.num_stages * self.n_chunks
        assert self.tensor_parallel >= 1, self.tensor_parallel
        assert self.data_parallel >= 1, self.data_parallel
        assert self.bucket_bytes >= 0, self.bucket_bytes
        if not self.recompute:
            object.__setattr__(self, "recompute",
                               (True,) * self.num_stages)
        assert len(self.recompute) == self.num_stages
        if self.batch_domain:
            object.__setattr__(self, "batch_domain",
                               tuple(int(a) for a in self.batch_domain))
            # real raises, not asserts: domains arrive from hand-editable
            # plan JSON via from_plan
            if len(self.batch_domain) != self.data_parallel:
                raise ValueError(
                    f"batch_domain has {len(self.batch_domain)} "
                    f"allocations but data_parallel="
                    f"{self.data_parallel}")
            if any(a < 1 for a in self.batch_domain):
                raise ValueError(f"batch_domain allocations must be "
                                 f">= 1: {self.batch_domain}")
            if max(self.batch_domain) != self.microbatches:
                raise ValueError(
                    f"batch_domain pacing allocation "
                    f"{max(self.batch_domain)} must equal microbatches="
                    f"{self.microbatches} — ``microbatches`` is the "
                    f"pacing replica's tick-table length (DESIGN.md §13)")
            if len(set(self.batch_domain)) == 1:
                # uniform domains take the legacy bit-exact path
                object.__setattr__(self, "batch_domain", ())
        if self.stage_tp:
            object.__setattr__(self, "stage_tp",
                               tuple(int(t) for t in self.stage_tp))
            # real raises, not asserts: grouped specs arrive from
            # hand-editable plan JSON via from_plan
            if len(self.stage_tp) != self.num_stages:
                raise ValueError(
                    f"stage_tp has {len(self.stage_tp)} entries but the "
                    f"spec has {self.num_stages} physical stages")
            if any(t < 1 for t in self.stage_tp):
                raise ValueError(f"stage_tp degrees must be >= 1: "
                                 f"{self.stage_tp}")
            if self.tensor_parallel != 1:
                raise ValueError(
                    f"non-uniform per-stage tp (stage_tp={self.stage_tp}) "
                    f"replaces the uniform tensor_parallel="
                    f"{self.tensor_parallel}; set tensor_parallel=1")
            if self.n_chunks != 1:
                raise ValueError(
                    f"non-uniform per-stage tp (stage_tp={self.stage_tp}) "
                    f"executes single-chunk schedules only; n_chunks="
                    f"{self.n_chunks} chunked schedules keep asymmetric "
                    f"tp a cost-model dimension (DESIGN.md §12)")
            if self.data_parallel != 1:
                raise ValueError(
                    f"non-uniform per-stage tp (stage_tp={self.stage_tp}) "
                    f"does not compose with data_parallel="
                    f"{self.data_parallel} yet; dp replicas of grouped "
                    f"pipelines stay a cost-model dimension "
                    f"(DESIGN.md §12)")
            if not self.reshard:
                object.__setattr__(self, "reshard", tuple(
                    "none" if a == b else "sr_ag"
                    for a, b in zip(self.stage_tp, self.stage_tp[1:])))
            if len(self.reshard) != self.num_stages - 1:
                raise ValueError(
                    f"reshard names {len(self.reshard)} boundary "
                    f"strategies but the spec has "
                    f"{self.num_stages - 1} stage boundaries")
            bad = [r for r in self.reshard
                   if r not in ("none", "naive", "sr_ag")]
            if bad:
                raise ValueError(f"unknown reshard strategies {bad}; "
                                 f"pick from 'none' | 'naive' | 'sr_ag'")
        elif self.reshard:
            raise ValueError("reshard strategies need stage_tp (the "
                             "grouped runtime); uniform specs have no "
                             "per-boundary collective to choose")

    @property
    def total_layers(self) -> int:
        return sum(self.layers_per_stage)

    @property
    def max_layers(self) -> int:
        return max(self.layers_per_stage)

    @property
    def grouped(self) -> bool:
        """True when the spec uses the grouped (non-uniform per-stage tp)
        runtime — a flat pipe mesh of :attr:`pipe_width` devices."""
        return bool(self.stage_tp)

    @property
    def stage_tps(self) -> Tuple[int, ...]:
        """Effective per-physical-stage tp degrees (uniform or grouped)."""
        return self.stage_tp if self.stage_tp \
            else (self.tensor_parallel,) * self.num_stages

    @property
    def pipe_width(self) -> int:
        """Devices on the flat pipe axis of the grouped runtime."""
        return sum(self.stage_tp) if self.stage_tp else self.num_stages

    @property
    def batch_allocations(self) -> Tuple[int, ...]:
        """Effective per-dp-replica microbatch allocations (uniform or
        non-uniform — DESIGN.md §13)."""
        return self.batch_domain if self.batch_domain \
            else (self.microbatches,) * self.data_parallel

    @property
    def total_microbatches(self) -> int:
        """Global-batch microbatch count Σ_r allocations[r]."""
        return sum(self.batch_allocations)


def from_plan(plan, microbatches: Optional[int] = None, *,
              execute_tp: bool = False,
              execute_dp: bool = False,
              verify: bool = True) -> PipelineSpec:
    """Build a runtime PipelineSpec from a HeteroAuto ParallelPlan.

    For chunked schedules (``interleaved``, ``zb_v``) each physical
    stage's layer allotment is split across its v chunk slots (earlier
    slots take the remainder) and laid out in ascending global-stage
    order, so the model's layer order follows the schedule's chunk
    placement and the searched non-uniform split survives intact.

    ``execute_tp=True`` consumes the plan's per-stage tp degree.  A plan
    whose stages AGREE on tp keeps the legacy rectangular
    ``(pipe, tp)`` mesh (bit-exact with the historical path); stages
    that DISAGREE produce a grouped spec (``stage_tp`` non-empty,
    DESIGN.md §12): the pipeline runs on a flat pipe mesh where each
    stage owns tp_k devices, and each tp-changing stage boundary gets
    the reshard collective ``resharding.choose_strategy`` picks from the
    adjacent chips' NIC / intra-node bandwidths (``sr_ag`` vs
    ``naive``, priced by ``boundary_time``).  Genuinely inexpressible
    layouts are still refused with a clear error: non-uniform tp under a
    CHUNKED schedule (interleaved / zb_v / wave's multi-chunk cousins)
    or combined with ``execute_dp`` on a dp > 1 plan.

    ``execute_dp=True`` consumes the plan's dp degree and realizes it as
    pipeline replicas over the 3-D mesh's leading ``dp`` axis.  A plan
    carrying a NON-UNIFORM ``batch_domain`` (throughput-proportional
    allocations from ``repro.core.dataparallel.batch_domain``) threads
    the allocations into ``PipelineSpec.batch_domain``: each replica
    runs the schedule's tick program for its own allocation, padded to
    the pacing replica's length (DESIGN.md §13).  An explicit
    ``microbatches`` override that disagrees with the domain's pacing
    allocation is refused — the override cannot rescale a per-replica
    split.

    The defaults keep the historical behaviour: tp and dp remain
    cost-model dimensions and the runtime executes the layer split
    alone.

    ``verify=True`` (the default) runs the cfg-free static verifier
    (``repro.analysis``, DESIGN.md §15) over the plan after the spec is
    built and raises ``PlanVerificationError`` (a ValueError) if any
    H2Exxx diagnostic fires — divergent per-replica collective
    sequences, underivable tick programs, inconsistent grouped layouts
    — so a plan that would deadlock a real mesh is refused at load
    time rather than at trace time.  Callers that already ran the full
    analyzer (``launch/train.py``) pass ``verify=False``."""
    from .schedules import get_schedule
    sched = get_schedule(plan.schedule)
    v = sched.n_chunks
    tp = 1
    stage_tp: Tuple[int, ...] = ()
    reshard: Tuple[str, ...] = ()
    if execute_tp:
        tps = sorted({s.tp for s in plan.stages})
        if len(tps) == 1:
            tp = tps[0]
        else:
            if v > 1:
                raise ValueError(
                    f"plan assigns non-uniform per-stage tp {tps} under "
                    f"the chunked {plan.schedule!r} schedule "
                    f"({plan.describe()}); the grouped stage runtime "
                    f"streams single-chunk schedules only, so this "
                    f"combination stays a cost-model artifact "
                    f"(DESIGN.md §12) — re-search with a single-chunk "
                    f"schedule or uniform tp")
            if execute_dp and plan.dp > 1:
                raise ValueError(
                    f"plan assigns non-uniform per-stage tp {tps} AND "
                    f"dp={plan.dp} ({plan.describe()}); dp replicas of "
                    f"grouped pipelines stay a cost-model dimension "
                    f"(DESIGN.md §12) — call from_plan with "
                    f"execute_dp=False or re-search with uniform tp")
            from . import resharding as RS
            per_tp, per_chip = [], []
            for s in plan.stages:
                per_tp.extend([s.tp] * s.pp)
                per_chip.extend([s.group.spec] * s.pp)
            stage_tp = tuple(per_tp)
            reshard = tuple(
                "none" if per_tp[i] == per_tp[i + 1] else
                RS.choose_strategy(per_tp[i], per_tp[i + 1],
                                   nic_bw=per_chip[i].nic_bw,
                                   intra_bw=per_chip[i + 1].intra_node_bw)
                for i in range(len(per_tp) - 1))
    dp = 1
    batch_domain: Tuple[int, ...] = ()
    if execute_dp:
        domain = getattr(plan, "batch_domain", None)
        if domain is not None and len(set(domain)) > 1:
            if microbatches is not None and microbatches != max(domain):
                raise ValueError(
                    f"microbatches={microbatches} override conflicts "
                    f"with the plan's non-uniform batch domain "
                    f"{list(domain)} ({plan.describe()}): the override "
                    f"cannot rescale a per-replica split — rebuild the "
                    f"plan's domain instead (DESIGN.md §13)")
            batch_domain = tuple(int(a) for a in domain)
        dp = plan.dp
    phys, rec = [], []
    for s in plan.stages:
        per = s.layers_per_stage
        left = s.layers
        for _ in range(s.pp):
            take = min(per, left)
            phys.append(take)
            rec.append(s.recompute)
            left -= take
    # the bucket budget only shapes the psum sync program (ZeRO-1 keeps
    # one message per leaf), so thread it only when it will be consulted
    bucket = getattr(plan, "bucket_bytes", 0) \
        if dp > 1 and getattr(plan, "dp_sync", "") == "psum" else 0
    spec = PipelineSpec(len(phys), chunk_layer_counts(phys, sched),
                        microbatches or plan.microbatches,
                        tuple(rec), schedule=plan.schedule, n_chunks=v,
                        tensor_parallel=tp, data_parallel=dp,
                        bucket_bytes=bucket, batch_domain=batch_domain,
                        stage_tp=stage_tp, reshard=reshard)
    if verify:
        # lazy: analysis never imports heteropp, but keeping the gate
        # import out of module scope keeps this module's import cheap
        from ..analysis import verify_plan
        verify_plan(plan, microbatches=microbatches,
                    execute_tp=execute_tp, execute_dp=execute_dp)
    return spec


# ---------------------------------------------------------------------------
# static programs (jax-free — extracted to core/tickprogram.py so the
# plan verifier can walk them without jax; re-exported here for the
# runtime callers and the historical import paths)
# ---------------------------------------------------------------------------

from .tickprogram import (  # noqa: E402  (re-exports)
    SRC_INJECT, SRC_PREV, SRC_NEXT, SRC_LOCAL, GroupLayout, TickTables,
    boundary_tables as _boundary_tables, chunk_layer_counts,
    domain_tick_tables, group_layout, schedule_injection_order,
    spmd_tick_tables)


# ---------------------------------------------------------------------------
# stage parameter construction
# ---------------------------------------------------------------------------

def _spec_schedule(spec: PipelineSpec):
    from .schedules import get_schedule
    sched = get_schedule(spec.schedule)
    assert sched.n_chunks == spec.n_chunks, \
        (sched.name, sched.n_chunks, spec.n_chunks)
    return sched


def split_stage_params(params: PyTree, cfg: ModelConfig, spec: PipelineSpec
                       ) -> Tuple[PyTree, jnp.ndarray]:
    """Split stacked block params (L, ...) into the padded per-stage layout
    plus a validity mask: ``(S, Lmax, ...)`` / mask ``(S, Lmax)`` for
    single-chunk specs, ``(S, v, Lcmax, ...)`` / mask ``(S, v, Lcmax)``
    for chunked ones — slot k of stage s holds the layers of global
    chunk-stage ``schedule.global_stage(s, k, S)``.  Embedding/final-norm
    params are replicated to every stage (injection ops use embed, the
    last global stage unembeds).

    Grouped specs (``spec.stage_tp`` non-empty) lay out PER DEVICE of the
    flat pipe mesh instead: leaf ``(N, Lmax, ...)`` / mask ``(N, Lmax)``
    where device i holds its stage's layers sliced to its Megatron tp
    shard (``rules.tp_local_slice``) and zero-padded to the widest local
    width (a tp_min-way shard) — the phantom rows/columns are exact
    zeros and stay zero through training (DESIGN.md §12)."""
    L = cfg.num_layers
    S, v, Lmax = spec.num_stages, spec.n_chunks, spec.max_layers
    assert spec.total_layers == L, (spec.layers_per_stage, L)
    counts = spec.layers_per_stage
    bounds = np.cumsum([0] + list(counts))

    def pad_part(leaf, g):
        part = leaf[bounds[g]:bounds[g + 1]]
        pad = Lmax - part.shape[0]
        if pad:
            part = jnp.pad(part, [(0, pad)] + [(0, 0)] * (leaf.ndim - 1))
        return part

    if spec.stage_tp:
        from ..sharding import rules
        layout = group_layout(spec.stage_tp)
        N, tp_min = layout.num_devices, layout.tp_min
        mask = np.zeros((N, Lmax), np.bool_)
        for i in range(N):
            mask[i, : counts[int(layout.stage_of[i])]] = True

        def split_grouped(kp, leaf):
            path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in kp)
            return jnp.stack([
                rules.tp_local_slice(
                    path, pad_part(leaf, int(layout.stage_of[i])),
                    int(layout.rank_of[i]), int(layout.tp_of[i]), tp_min)
                for i in range(N)])                  # (N, Lmax, ...)

        stage_params = {
            "blocks": jax.tree_util.tree_map_with_path(
                split_grouped, params["blocks"]),
            "embed": params["embed"],
            "final_norm": params["final_norm"],
        }
        return stage_params, jnp.asarray(mask)

    if v == 1:
        mask = np.zeros((S, Lmax), np.bool_)
        for s in range(S):
            mask[s, : counts[s]] = True

        def split(leaf):
            return jnp.stack([pad_part(leaf, s) for s in range(S)])
    else:
        sched = _spec_schedule(spec)
        gmap = [[sched.global_stage(s, k, S) for k in range(v)]
                for s in range(S)]
        mask = np.zeros((S, v, Lmax), np.bool_)
        for s in range(S):
            for k in range(v):
                mask[s, k, : counts[gmap[s][k]]] = True

        def split(leaf):
            return jnp.stack([
                jnp.stack([pad_part(leaf, gmap[s][k]) for k in range(v)])
                for s in range(S)])                  # (S, v, Lcmax, ...)

    stage_params = {
        "blocks": jax.tree.map(split, params["blocks"]),
        "embed": params["embed"],
        "final_norm": params["final_norm"],
    }
    return stage_params, jnp.asarray(mask)


def abstract_stage_params(cfg: ModelConfig, spec: PipelineSpec) -> PyTree:
    params = M.abstract_params(cfg)
    return jax.eval_shape(
        lambda p: split_stage_params(p, cfg, spec)[0], params)


# ---------------------------------------------------------------------------
# stage compute
# ---------------------------------------------------------------------------

def validate_tensor_parallel(cfg: ModelConfig, tp: int) -> None:
    """Check that the runtime can realize tp-degree ``tp`` for ``cfg``.

    The manual tp path shards attention heads and MLP ff Megatron-style
    (DESIGN.md §8), so it is limited to dense decoder blocks whose head /
    kv-head / ff counts divide tp; MoE / SSM / hybrid blocks keep tp as a
    cost-model dimension until their expert/state sharding is realized."""
    if tp == 1:
        return
    kind = M._block_kind(cfg)
    if kind != "dense" or cfg.hybrid_attn_every or cfg.is_encoder_decoder:
        raise NotImplementedError(
            f"tensor_parallel={tp}: the 2-D (pipe, tp) runtime shards "
            f"dense decoder blocks only; {cfg.name} has block kind "
            f"{kind!r} (family {cfg.family!r}) — tp stays a cost-model "
            f"dimension for it (DESIGN.md §8)")
    for what, n in (("num_heads", cfg.num_heads),
                    ("num_kv_heads", cfg.num_kv_heads),
                    ("d_ff", cfg.d_ff)):
        if n % tp:
            raise ValueError(
                f"tensor_parallel={tp} does not divide {cfg.name}.{what}"
                f"={n}; pick a tp that divides heads, kv heads and d_ff")


def validate_spec_tp(cfg: ModelConfig, spec: PipelineSpec) -> None:
    """Validate every tp degree a spec realizes — the uniform
    ``tensor_parallel`` or each distinct grouped ``stage_tp`` entry:
    the model's head / kv-head / ff counts must divide every degree
    (including the smallest, which sizes the grouped padding)."""
    for t in sorted(set(spec.stage_tps)):
        validate_tensor_parallel(cfg, t)


def _tp_local_cfg(cfg: ModelConfig, tp: int) -> ModelConfig:
    """The per-member view of the model: each tp member owns 1/tp of the
    heads, kv heads and ff width; everything else (d_model, head_dim,
    rope, norms) is unchanged."""
    if tp == 1:
        return cfg
    return dataclasses.replace(cfg, num_heads=cfg.num_heads // tp,
                               num_kv_heads=cfg.num_kv_heads // tp,
                               d_ff=cfg.d_ff // tp)


def _tp_block_forward(p, cfg: ModelConfig, lcfg: ModelConfig, x,
                      tp_axis: Optional[str], psum=None):
    """One dense block with manual Megatron tensor parallelism: the
    params are the LOCAL tp shards (column-parallel wq/wk/wv/wi/wg, row-
    parallel wo — ``sharding/rules.py`` placement), so attention runs on
    the member's heads and the MLP on its ff slice; each sub-block's
    row-parallel output projection yields a PARTIAL sum that a psum over
    the tp axis completes BEFORE the residual add, keeping activations
    (and the norms that consume them) replicated across tp.  ``psum``
    overrides the collective — the grouped runtime passes its stage-group
    psum (all-gather + membership-masked contraction, DESIGN.md §12)
    because its tp groups are sub-spans of the flat pipe axis, not a
    mesh axis of their own."""
    if psum is None:
        psum = lambda v: jax.lax.psum(v, tp_axis)
    h = layers.apply_norm(p["ln1"], x, cfg.norm)
    a = attention.self_attention(p["attn"], lcfg, h)
    x = x + psum(a)
    h = layers.apply_norm(p["ln2"], x, cfg.norm)
    y = layers.apply_mlp(p["mlp"], h, cfg.mlp)
    return x + psum(y), {}


def _stage_forward(blocks, mask_row, cfg, x, kind: str, remat: bool,
                   *, tp_axis: Optional[str] = None,
                   lcfg: Optional[ModelConfig] = None, psum=None):
    """Run Lmax (padded) layers; masked layers are identity.  With
    ``tp_axis`` (or an explicit ``psum`` collective) set, each layer is
    the manual tensor-parallel dense block (every member runs the same
    psums, padded layers included, so the program stays SPMD-uniform)."""

    def one(x, inp):
        p, valid = inp
        if tp_axis is None and psum is None:
            y, m = tfm.block_forward(p, cfg, x, kind)
        else:
            y, m = _tp_block_forward(p, cfg, lcfg, x, tp_axis, psum)
        aux = m.get("moe_aux_loss", 0.0) + m.get("moe_z_loss", 0.0)
        x = jnp.where(valid, y, x)
        # rank-1, not scalar: rank-0 float consts become implicit
        # shard_map inputs whose cotangents the legacy transpose rejects
        aux1 = jnp.asarray(aux, jnp.float32).reshape(1)
        return x, jnp.where(valid, aux1, 0.0)

    body = jax.checkpoint(one) if remat else one
    with jax.named_scope(scopes.LAYERS):
        x, auxs = jax.lax.scan(body, x, (blocks, mask_row))
    return x, jnp.sum(auxs)


# ---------------------------------------------------------------------------
# SPMD pipeline (shard_map over the pipe axis)
# ---------------------------------------------------------------------------

def _grouped_replica_core(cfg: ModelConfig, spec: PipelineSpec, mesh: Mesh,
                          *, remat: bool = True,
                          schedule: Optional[str] = None):
    """The grouped (non-uniform per-stage tp) pipeline core — the
    DESIGN.md §12 stage-group runtime contract.

    One shard_map program manual over a FLAT pipe axis of
    N = Σ stage_tp devices.  Stage s owns the contiguous device span
    ``[offset[s], offset[s] + stage_tp[s])`` (:func:`group_layout`); each
    device runs the SAME tick program on its zero-padded Megatron shard
    (``split_stage_params``; the phantom heads / ff slices compute exact
    zeros, so the padded program is value-identical to the unpadded
    one).  Collectives:

    * stage-interior psum — JAX cannot form unequal-size
      ``axis_index_groups``, so the group psum is one ``all_gather``
      over the flat axis + a per-device membership-row contraction
      (its transpose is a psum-scatter, so autodiff through it is the
      standard Megatron backward);
    * stage-boundary transfer — one fused ``all_gather`` of the
      send-masked outputs + a per-device receive-row contraction
      (:func:`_boundary_tables`), realizing the per-boundary ``reshard``
      strategy at the value level: ``sr_ag`` sources keep only their
      feature shard (one activation copy crosses the boundary, the
      recv row's group sum IS the destination all-gather), ``naive`` /
      ``none`` sources send the full copy to their matched rank.

    The loss gates on ``rank == 0`` so each group counts its emitted
    microbatches exactly once, then psums over the flat axis.  Returns
    the same ``(replica_fn, in_specs, manual, out_axes)`` contract as
    :func:`_pipeline_replica_core` (dp is always 1 here)."""
    kind = M._block_kind(cfg)
    axis = spec.pipe_axis
    nstages = spec.num_stages
    b = spec.microbatches
    layout = group_layout(spec.stage_tp)
    N = layout.num_devices
    tmax = max(spec.stage_tp)
    validate_spec_tp(cfg, spec)
    if axis not in mesh.axis_names or mesh.shape[axis] != N:
        raise ValueError(
            f"grouped spec stage_tp={spec.stage_tp} needs a flat "
            f"{axis!r} mesh axis of {N} devices (= sum of the stage "
            f"groups); got mesh {dict(mesh.shape)}")
    from .schedules import get_schedule
    sched = get_schedule(schedule or spec.schedule)
    if sched.n_chunks != 1:
        raise ValueError(
            f"schedule {sched.name!r} is chunked (v={sched.n_chunks}); "
            f"non-uniform per-stage tp executes single-chunk schedules "
            f"only (DESIGN.md §12)")
    tables = spmd_tick_tables(sched, nstages, b)
    used = set(np.unique(tables.src[tables.active]))
    # single-chunk streams are strictly INJECT/PREV (v == 1 means every
    # hop g−1 → g lands on the previous physical stage, and stage 0 only
    # injects), so the grouped runtime needs exactly one fused transfer
    assert used <= {SRC_INJECT, SRC_PREV}, (sched.name, used)
    xs = (jnp.asarray(tables.mb), jnp.asarray(tables.src),
          jnp.asarray(tables.active), jnp.asarray(tables.emit))

    lcfg = _tp_local_cfg(cfg, layout.tp_min)
    send_np, recv_np = _boundary_tables(layout, spec.reshard, cfg.d_model)
    stage_of_t = jnp.asarray(layout.stage_of)
    rank_of_t = jnp.asarray(layout.rank_of)
    member_t = jnp.asarray(layout.member, jnp.float32)
    send_t = jnp.asarray(send_np)
    recv_t = jnp.asarray(recv_np)

    d = cfg.d_model
    dtype = layers.dtype_of(cfg)

    @jax.named_scope(scopes.PIPE_TICK)
    def tick_step(stage_params, mask, tokens, carry, row):
        # One tick of the grouped SPMD program, device-local (inside
        # shard_map): shared by the lax.scan below and the host-driven
        # per-tick tracer (repro.obs.runtime — DESIGN.md §14).
        # Leading device dim is local (size 1) -> squeeze.
        blocks = jax.tree.map(lambda x: x[0], stage_params["blocks"])
        mask_dev = mask[0]                        # (Lmax,)
        embed = stage_params["embed"]
        fnorm = stage_params["final_norm"]
        dev = jax.lax.axis_index(axis)
        sid = jnp.take(stage_of_t, dev)
        rank0 = jnp.take(rank_of_t, dev) == 0
        mrow = jnp.take(member_t, dev, axis=0)    # (N,) group membership
        srow = jnp.take(send_t, dev, axis=0)      # (d,) boundary send mask
        rrow = jnp.take(recv_t, dev, axis=0)      # (N,) boundary recv row

        def gpsum(v):
            g = jax.lax.all_gather(v, axis)       # (N, ...)
            return jnp.tensordot(mrow.astype(v.dtype), g, axes=(0, 0))

        psum_cb = gpsum if tmax > 1 else None

        x_prev, loss_acc, aux_acc, denom = carry
        mb_row, src_row, act_row, emit_row = row
        mb_idx = jnp.take(mb_row, sid)
        src = jnp.take(src_row, sid)
        active = jnp.take(act_row, sid)
        take = active & jnp.take(emit_row, sid) & rank0
        toks = jax.lax.dynamic_index_in_dim(tokens, mb_idx, 0,
                                            keepdims=False)
        x0 = layers.embed_tokens(embed, toks).astype(dtype)
        x = jnp.where(src == SRC_INJECT, x0, x_prev)
        y, aux = _stage_forward(blocks, mask_dev, cfg, x, kind, remat,
                                lcfg=lcfg, psum=psum_cb)
        # the group output y is replicated across the stage's tp
        # members (each sub-block closes with the group psum), so
        # ONLY rank 0 counts its emitted microbatch's CE / tokens
        h = layers.apply_norm(fnorm, y, cfg.norm)
        targets = jnp.concatenate(
            [toks[:, 1:], jnp.zeros_like(toks[:, :1])], axis=1)
        lmask = jnp.ones_like(toks, jnp.float32).at[:, -1].set(0.0)
        ce = M.chunked_ce(embed, h, targets, lmask)
        loss_acc = loss_acc + jnp.where(take, ce, 0.0)
        denom = denom + jnp.where(take, jnp.sum(lmask), 0.0)
        aux_acc = aux_acc + jnp.where(active & rank0, aux, 0.0)
        # boundary transfer: one fused gather of the send-masked
        # outputs, then each device mixes its sources' contributions
        # (disjoint sr_ag shards sum to the full activation; naive
        # rows pick their matched source) — the next tick's x_prev
        with jax.named_scope(scopes.PIPE_SEND):
            g = jax.lax.all_gather(y * srow.astype(y.dtype), axis)
            x_prev2 = jnp.tensordot(rrow.astype(y.dtype), g, axes=(0, 0))
        return (x_prev2, loss_acc, aux_acc, denom)

    def replica_fn(stage_params, mask, tokens):
        mb_size, S_seq = tokens.shape[1], tokens.shape[2]
        x_init = jnp.zeros((mb_size, S_seq, d), dtype)
        zero = jnp.zeros((1,), jnp.float32)
        (_, loss_sum, aux_sum, denom), _ = jax.lax.scan(
            lambda c, r: (tick_step(stage_params, mask, tokens, c, r),
                          None),
            (x_init, zero, zero, zero), xs)
        loss_sum = jax.lax.psum(loss_sum, axis)
        denom = jax.lax.psum(denom, axis)
        aux_sum = jax.lax.psum(aux_sum, axis) / nstages
        return loss_sum, denom, aux_sum

    # hooks for the host-driven per-tick tracer (repro.obs.runtime)
    replica_fn.tick_step = tick_step
    replica_fn.tick_tables = tables
    replica_fn.tick_xs = xs
    replica_fn.carry_shapes = lambda mb_size, S_seq: (
        (((mb_size, S_seq, d), dtype),)
        + ((((1,), jnp.float32),) * 3))
    replica_fn.denom_units = 1

    aps = abstract_stage_params(cfg, spec)
    from ..sharding import rules
    blk_specs = rules.stage_block_specs(
        aps["blocks"], pipe_axis=axis, tp_axis=None, stacked_prefix=2)
    in_specs = (
        {
            "blocks": blk_specs,
            "embed": jax.tree.map(lambda _: P(), aps["embed"]),
            "final_norm": jax.tree.map(lambda _: P(), aps["final_norm"]),
        },
        P(axis),
        P(),
    )
    return replica_fn, in_specs, {axis}, (axis,)


def _pipeline_replica_core(cfg: ModelConfig, spec: PipelineSpec, mesh: Mesh,
                           *, remat: bool = True,
                           schedule: Optional[str] = None):
    """Shared builder for the SPMD pipeline: validates the spec against
    the mesh and returns ``(replica_fn, in_specs, manual, out_axes)``.

    ``replica_fn(stage_params, mask, tokens)`` runs INSIDE shard_map and
    returns the replica's un-normalized ``(loss_sum, denom, aux_sum)``
    — each shape (1,), psum'd over the pipe axis so every member of one
    (pipe × tp) replica holds the same values; nothing touches the dp
    axis, so dp replicas stay independent until the caller closes them
    (the loss path psums them, the train step syncs gradients —
    DESIGN.md §9).  Grouped specs (non-uniform per-stage tp) dispatch to
    :func:`_grouped_replica_core`, which honors the same contract on the
    flat stage-group mesh (DESIGN.md §12)."""
    if spec.stage_tp:
        return _grouped_replica_core(cfg, spec, mesh, remat=remat,
                                     schedule=schedule)
    kind = M._block_kind(cfg)
    axis = spec.pipe_axis
    nstages = spec.num_stages
    v = spec.n_chunks
    b = spec.microbatches
    tp = spec.tensor_parallel
    tp_axis = spec.tp_axis if tp > 1 else None
    validate_tensor_parallel(cfg, tp)
    if mesh.shape[axis] != nstages:
        raise ValueError(
            f"mesh axis {axis!r} has size {mesh.shape[axis]} but the "
            f"PipelineSpec has {nstages} physical stages")
    if tp > 1 and spec.tp_axis not in mesh.axis_names:
        raise ValueError(
            f"spec.tensor_parallel={tp} needs a {spec.tp_axis!r} mesh "
            f"axis; got axes {mesh.axis_names}")
    if spec.tp_axis in mesh.axis_names and mesh.shape[spec.tp_axis] != tp:
        raise ValueError(
            f"mesh axis {spec.tp_axis!r} has size "
            f"{mesh.shape[spec.tp_axis]} but spec.tensor_parallel={tp}")
    dp = spec.data_parallel
    if dp > 1 and spec.dp_axis not in mesh.axis_names:
        raise ValueError(
            f"spec.data_parallel={dp} needs a {spec.dp_axis!r} mesh "
            f"axis; got axes {mesh.axis_names}")
    if spec.dp_axis in mesh.axis_names and mesh.shape[spec.dp_axis] != dp:
        raise ValueError(
            f"mesh axis {spec.dp_axis!r} has size "
            f"{mesh.shape[spec.dp_axis]} but spec.data_parallel={dp}")
    lcfg = _tp_local_cfg(cfg, tp)
    from .schedules import get_schedule
    sched = get_schedule(schedule or spec.schedule)
    if sched.n_chunks != v:
        raise ValueError(
            f"schedule {sched.name!r} has n_chunks={sched.n_chunks} but the "
            f"PipelineSpec was laid out with n_chunks={v}; rebuild the spec "
            f"for this schedule (from_plan does this automatically)")
    if v > 1 and sched.name != spec.schedule:
        ref = _spec_schedule(spec)
        for s in range(nstages):
            for k in range(v):
                if sched.global_stage(s, k, nstages) != \
                        ref.global_stage(s, k, nstages):
                    raise ValueError(
                        f"schedule {sched.name!r} places chunks differently "
                        f"from the spec's {spec.schedule!r}; the parameter "
                        f"layout is placement-specific")
    # table rows are (ticks, S) for uniform domains, (ticks, dp, S) for
    # non-uniform ones (per-replica programs padded to the pacing
    # replica's length — DESIGN.md §13); the ellipsis indexing below
    # covers both layouts
    if spec.batch_domain:
        tables = domain_tick_tables(sched, nstages, spec.batch_domain)
    else:
        tables = spmd_tick_tables(sched, nstages, b)
    # static routing facts: skip permutes/branches/wrap edges no tick
    # ever uses (single-chunk schedules keep the old one-permute,
    # no-wrap program)
    used = set(np.unique(tables.src[tables.active])) \
        if tables.active.any() else set()
    needs_prev = SRC_PREV in used
    needs_next = SRC_NEXT in used
    needs_local = SRC_LOCAL in used
    wraps_prev = bool(np.any(tables.active[..., 0]
                             & (tables.src[..., 0] == SRC_PREV)))
    wraps_next = bool(np.any(tables.active[..., -1]
                             & (tables.src[..., -1] == SRC_NEXT)))
    xs = (jnp.asarray(tables.mb), jnp.asarray(tables.chunk),
          jnp.asarray(tables.src), jnp.asarray(tables.active),
          jnp.asarray(tables.emit))

    d = cfg.d_model
    dtype = layers.dtype_of(cfg)

    @jax.named_scope(scopes.PIPE_TICK)
    def tick_step(stage_params, mask, tokens, carry, row):
        # One tick of the SPMD program, device-local (inside shard_map):
        # shared by the lax.scan below and the host-driven per-tick
        # tracer (repro.obs.runtime.trace_spmd_pipeline — DESIGN.md §14)
        blocks = jax.tree.map(lambda x: x[0], stage_params["blocks"])
        mask_dev = mask[0]           # (Lmax,) or (v, Lcmax)
        embed = stage_params["embed"]
        fnorm = stage_params["final_norm"]
        sid = jax.lax.axis_index(axis)
        x_prev, x_next, y_loc, loss_acc, aux_acc, denom = carry
        if spec.batch_domain:
            # non-uniform domains stack per-replica programs on a middle
            # dp dim; each replica selects ITS OWN row (DESIGN.md §13)
            ridx = jax.lax.axis_index(spec.dp_axis)
            row = tuple(jnp.take(a, ridx, axis=0) for a in row)
        mb_row, ck_row, src_row, act_row, emit_row = row
        mb_idx = jnp.take(mb_row, sid)
        src = jnp.take(src_row, sid)
        active = jnp.take(act_row, sid)
        take = active & jnp.take(emit_row, sid)
        toks = jax.lax.dynamic_index_in_dim(tokens, mb_idx, 0,
                                            keepdims=False)
        # route the input: fresh embedding for injection ops, else the
        # neighbor (or own, for the zb_v turn) output of tick t-1
        x0 = layers.embed_tokens(embed, toks).astype(dtype)
        x = jnp.where(src == SRC_INJECT, x0, x_prev)
        if needs_next:
            x = jnp.where(src == SRC_NEXT, x_next, x)
        if needs_local:
            x = jnp.where(src == SRC_LOCAL, y_loc, x)
        if v > 1:
            ck = jnp.take(ck_row, sid)
            blk = jax.tree.map(
                lambda p: jax.lax.dynamic_index_in_dim(
                    p, ck, 0, keepdims=False), blocks)
            mrow = jax.lax.dynamic_index_in_dim(mask_dev, ck, 0,
                                                keepdims=False)
        else:
            blk, mrow = blocks, mask_dev
        y, aux = _stage_forward(blk, mrow, cfg, x, kind, remat,
                                tp_axis=tp_axis, lcfg=lcfg)
        # the member hosting the last global stage computes the LM
        # loss for its finished microbatch
        h = layers.apply_norm(fnorm, y, cfg.norm)
        targets = jnp.concatenate(
            [toks[:, 1:], jnp.zeros_like(toks[:, :1])], axis=1)
        lmask = jnp.ones_like(toks, jnp.float32).at[:, -1].set(0.0)
        ce = M.chunked_ce(embed, h, targets, lmask)
        loss_acc = loss_acc + jnp.where(take, ce, 0.0)
        denom = denom + jnp.where(take, jnp.sum(lmask), 0.0)
        aux_acc = aux_acc + jnp.where(active, aux, 0.0)
        # shift activations one hop each way for the next tick
        x_prev2, x_next2 = x_prev, x_next
        with jax.named_scope(scopes.PIPE_SEND):
            if needs_prev:
                perm_f = [(i, (i + 1) % nstages)
                          for i in range(nstages if wraps_prev
                                         else nstages - 1)]
                x_prev2 = jax.lax.ppermute(y, axis, perm_f)
            if needs_next:
                perm_b = [(i, i - 1) for i in range(1, nstages)]
                if wraps_next:
                    perm_b.append((0, nstages - 1))
                x_next2 = jax.lax.ppermute(y, axis, perm_b)
        y_loc2 = y if needs_local else y_loc
        return (x_prev2, x_next2, y_loc2, loss_acc, aux_acc, denom)

    def replica_fn(stage_params, mask, tokens):
        mb_size, S_seq = tokens.shape[1], tokens.shape[2]
        # accumulators are rank-1 (see _stage_forward): the zero inits are
        # closed-over constants that shard_map lifts to implicit
        # pipe-named inputs, and rank-0 ones cannot be transposed
        x_init = jnp.zeros((mb_size, S_seq, d), dtype)
        zero = jnp.zeros((1,), jnp.float32)
        carry = (x_init, x_init, x_init, zero, zero, zero)
        (_, _, _, loss_sum, aux_sum, denom), _ = jax.lax.scan(
            lambda c, r: (tick_step(stage_params, mask, tokens, c, r),
                          None),
            carry, xs)
        # broadcast the emitting member's loss to every pipe member; emit
        # one (identical, shape-(1,)) copy per member — a replicated
        # scalar out_spec does not transpose under the legacy shard_map
        loss_sum = jax.lax.psum(loss_sum, axis)
        denom = jax.lax.psum(denom, axis)
        aux_sum = jax.lax.psum(aux_sum, axis) / nstages
        return loss_sum, denom, aux_sum

    # hooks for the host-driven per-tick tracer (repro.obs.runtime):
    # the SAME tick body the scan runs, plus the static program and the
    # carry layout it needs to drive ticks one host call at a time
    replica_fn.tick_step = tick_step
    replica_fn.tick_tables = tables
    replica_fn.tick_xs = xs
    replica_fn.carry_shapes = lambda mb_size, S_seq: (
        (((mb_size, S_seq, d), dtype),) * 3
        + ((((1,), jnp.float32),) * 3))
    replica_fn.denom_units = tp

    aps = abstract_stage_params(cfg, spec)
    from ..sharding import rules
    blk_specs = rules.stage_block_specs(
        aps["blocks"], pipe_axis=axis, tp_axis=tp_axis,
        stacked_prefix=1 + (1 if v == 1 else 2))
    in_specs = (
        {
            "blocks": blk_specs,
            "embed": jax.tree.map(lambda _: P(), aps["embed"]),
            "final_norm": jax.tree.map(lambda _: P(), aps["final_norm"]),
        },
        P(axis),
        P(spec.dp_axis) if dp > 1 else P(),
    )
    # manual over the pipe (and, when present, dp/tp) axes; any other
    # mesh axes stay GSPMD-automatic
    manual = {axis} | ({spec.tp_axis, spec.dp_axis} & set(mesh.axis_names))
    out_axes = tuple(a for a in (spec.dp_axis, axis, spec.tp_axis)
                     if a in mesh.axis_names)
    return replica_fn, in_specs, manual, out_axes


def _prepare_domain_tokens(spec: PipelineSpec, tokens):
    """Validate/normalize the leading microbatch dim of ``tokens`` for
    the dp runtime (runs OUTSIDE shard_map).

    Uniform domains require exactly ``dp · b`` microbatches.  Non-uniform
    domains accept either layout (unambiguous: Σ allocations < dp · bmax
    strictly when allocations differ):

    * TIGHT replica-major — ``Σ allocations`` microbatches, replica r's
      ``allocations[r]`` consecutive; packed onto the padded per-replica
      slots via :func:`~repro.core.dataparallel.pad_index_map` (pad slots
      repeat the replica's last real microbatch — never read, the
      replica's tick program only names microbatches < allocations[r]);
    * PADDED — ``dp · bmax`` microbatches, already laid out per replica;
      passed through as-is (what the tight path produces)."""
    dp, b = spec.data_parallel, spec.microbatches
    n = tokens.shape[0]
    if not spec.batch_domain:
        if dp > 1 and n != dp * b:
            raise ValueError(
                f"tokens carry {n} microbatches but data_parallel={dp} "
                f"× microbatches={b} needs {dp * b} (uniform batch "
                f"domain — DESIGN.md §9)")
        return tokens
    from .dataparallel import pad_index_map
    total = spec.total_microbatches
    if n == total:
        return jnp.take(tokens,
                        jnp.asarray(pad_index_map(spec.batch_domain)),
                        axis=0)
    if n == dp * b:
        return tokens
    raise ValueError(
        f"tokens carry {n} microbatches but the batch domain "
        f"{list(spec.batch_domain)} needs {total} (tight replica-major) "
        f"or {dp * b} (padded per-replica — DESIGN.md §13)")


def make_spmd_pipeline_loss(cfg: ModelConfig, spec: PipelineSpec, mesh: Mesh,
                            *, remat: bool = True,
                            schedule: Optional[str] = None):
    """Returns loss_fn(stage_params, mask, tokens) -> scalar loss, where
    inside ``shard_map`` each pipe-axis ROW holds ONE physical stage
    (v chunk slots of layers for chunked schedules).  With
    ``spec.tensor_parallel > 1`` the mesh grows a manual ``tp`` axis (the
    tp members of a row share the stage Megatron-style — DESIGN.md §8);
    with ``spec.data_parallel > 1`` a manual ``dp`` axis replicates the
    whole pipeline and shards the microbatch dim of ``tokens``
    (DESIGN.md §9).

    tokens: (dp·b, mb_size, S_seq) — b microbatches per dp replica (for
    a non-uniform ``spec.batch_domain``, either the tight Σ-allocations
    replica-major layout or the padded dp·bmax layout —
    :func:`_prepare_domain_tokens`), streamed through the schedule's
    static tick program
    (:func:`spmd_tick_tables`): per tick each member runs one
    chunk-forward on the microbatch the tables name, reading its input
    from a fresh embedding, a ±1 pipe neighbor, or its own previous
    output (the zb_v turn).  The loss is the GLOBAL batch mean: CE sums
    and token counts are psum'd over dp before the division.
    """
    replica_fn, in_specs, manual, out_axes = _pipeline_replica_core(
        cfg, spec, mesh, remat=remat, schedule=schedule)
    dp, dpax, b = spec.data_parallel, spec.dp_axis, spec.microbatches
    total_mb = spec.total_microbatches

    def stage_loss(stage_params, mask, tokens):
        loss_sum, denom, aux_sum = replica_fn(stage_params, mask, tokens)
        if dp > 1:
            loss_sum = jax.lax.psum(loss_sum, dpax)
            denom = jax.lax.psum(denom, dpax)
            aux_sum = jax.lax.psum(aux_sum, dpax)
            # aux is a per-microbatch mean over the GLOBAL batch: uniform
            # domains factor the count as /dp then /b (bit-exact with the
            # historical path); non-uniform domains divide once by
            # Σ allocations (DESIGN.md §13)
            aux = aux_sum / total_mb if spec.batch_domain \
                else aux_sum / dp / max(b, 1)
        else:
            aux = aux_sum / max(b, 1)
        return loss_sum / jnp.maximum(denom, 1.0) + aux

    from .jax_compat import shard_map
    smapped = shard_map(stage_loss, mesh=mesh, in_specs=in_specs,
                        out_specs=P(out_axes), manual_axes=manual)

    def loss_fn(stage_params, mask, tokens):
        # (dp·S·tp,) identical per-member copies -> scalar (mean keeps
        # the cotangent uniform across members; each carries 1/n of it)
        tokens = _prepare_domain_tokens(spec, tokens)
        return jnp.mean(smapped(stage_params, mask, tokens))

    return loss_fn


def make_spmd_pipeline_train_step(cfg: ModelConfig, spec: PipelineSpec,
                                  mesh: Mesh, opt_cfg=None, *, remat=True,
                                  schedule: Optional[str] = None,
                                  grad_sync: str = "reduce_scatter"):
    """Training step for the SPMD pipeline.

    With ``spec.data_parallel == 1`` this is autodiff through the
    pipeline loss plus a replicated AdamW update (``grad_sync`` is
    irrelevant — there is no dp axis to sync over).  With dp > 1 the
    WHOLE step runs inside one shard_map manual over (dp, pipe, tp):
    per-replica gradients close with an explicit bucketed dp sync
    (``repro.core.dataparallel.grad_sync``) before the optimizer —
    ``grad_sync="psum"`` keeps optimizer state dp-replicated,
    ``"reduce_scatter"`` (the default, matching
    ``cost_model.evaluate``'s ``dp_sync`` memory model and the paper's
    ZeRO-1-by-default setup) shards it over dp (DESIGN.md §9).  With
    ``spec.bucket_bytes > 0`` the psum mode issues fused per-bucket
    all-reduces in wgrad-completion order instead of one collective per
    leaf — the program the §10 overlap model prices, bit-identical
    numerics (DESIGN.md §10).
    """
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    from .dataparallel.grad_sync import GRAD_SYNC_MODES
    if grad_sync not in GRAD_SYNC_MODES:
        raise ValueError(f"grad_sync {grad_sync!r} not in "
                         f"{GRAD_SYNC_MODES}")
    if spec.data_parallel > 1:
        return _make_dp_train_step(cfg, spec, mesh, opt_cfg, remat=remat,
                                   schedule=schedule, grad_sync=grad_sync)
    loss_fn = make_spmd_pipeline_loss(cfg, spec, mesh, remat=remat,
                                      schedule=schedule)

    def train_step(state, mask, batch):
        params, opt_state, step = state
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, mask, batch["tokens"]))(params)
        new_params, new_opt, om = adamw.apply_update(
            opt_cfg, opt_state, grads, step, params)
        return (new_params, new_opt, step + 1), {"loss": loss, **om}

    return train_step


def _bucketed_dp_psum(grads: PyTree, dp_axis: str, n_chunks: int,
                      bucket_bytes: int) -> PyTree:
    """Fused per-bucket dp all-reduces in wgrad-completion order
    (DESIGN.md §10).

    The gradient stream is ordered the way backward finalizes it: later
    chunk slots first (a device's higher slot hosts a later global
    stage, whose backward completes earlier), block leaves in reverse
    flatten order within a slot, and the pipe-replicated embed/final
    norm last (their cotangents accumulate across the whole backward).
    The coalescing itself is ``dataparallel.grad_sync.bucketize`` — the
    SAME rule the §10 accounting (``exposed_sync_time`` /
    ``plan_sync_events``) prices, applied per dtype run (a fused psum
    needs one dtype) — so the executed message structure and the model
    cannot drift apart.  Element-wise sums are unchanged by the
    concatenation, so the result is bit-identical to the per-leaf psum
    program — validated in ``tests/helpers/run_spmd_dp_pipeline.py``."""
    import jax.numpy as jnp
    from .dataparallel.grad_sync import bucketize
    flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
    nleaves = len(flat)
    # (completion-order key, leaf idx, chunk slot or None, array)
    entries = []
    for i, (kp, leaf) in enumerate(flat):
        top = getattr(kp[0], "key", str(kp[0])) if kp else ""
        if top == "blocks" and n_chunks > 1:
            for k in range(n_chunks):
                entries.append(((0, n_chunks - 1 - k, nleaves - i),
                                i, k, leaf[:, k]))
        elif top == "blocks":
            entries.append(((0, 0, nleaves - i), i, None, leaf))
        else:
            entries.append(((1, 0, nleaves - i), i, None, leaf))
    entries.sort(key=lambda e: e[0])

    buckets: List[List[tuple]] = []
    run: List[tuple] = []          # maximal same-dtype run of the stream

    def flush_run():
        if not run:
            return
        gb = bucketize([(str(j), a.size * a.dtype.itemsize)
                        for j, (_, _, a) in enumerate(run)], bucket_bytes)
        for bucket in gb.buckets:
            buckets.append([run[int(name)] for name, _ in bucket])
        run.clear()

    for _, i, k, arr in entries:
        if run and arr.dtype != run[0][2].dtype:
            flush_run()
        run.append((i, k, arr))
    flush_run()

    out: List[Optional[Any]] = [None] * nleaves
    chunk_parts: Dict[int, List[Optional[Any]]] = {}
    for bucket in buckets:
        if len(bucket) == 1:
            i, k, arr = bucket[0]
            pieces = [(i, k, jax.lax.psum(arr, dp_axis))]
        else:
            fused = jax.lax.psum(
                jnp.concatenate([a.reshape(-1) for _, _, a in bucket]),
                dp_axis)
            sizes = np.cumsum([a.size for _, _, a in bucket][:-1])
            pieces = [(i, k, part.reshape(a.shape))
                      for (i, k, a), part in
                      zip(bucket, jnp.split(fused, sizes))]
        for i, k, arr in pieces:
            if k is None:
                out[i] = arr
            else:
                chunk_parts.setdefault(i, [None] * n_chunks)[k] = arr
    for i, parts in chunk_parts.items():
        assert all(p is not None for p in parts), (i, parts)
        out[i] = jnp.stack(parts, axis=1)
    assert all(o is not None for o in out)
    return jax.tree_util.tree_unflatten(treedef, out)


def _make_dp_train_step(cfg: ModelConfig, spec: PipelineSpec, mesh: Mesh,
                        opt_cfg, *, remat: bool, schedule: Optional[str],
                        grad_sync: str):
    """The dp > 1 train step: ONE shard_map manual over (dp, pipe, tp)
    wrapping loss, backward, dp gradient sync, and the optimizer
    (DESIGN.md §9).

    Inside the body every value is device-local, so
    ``jax.value_and_grad`` of the replica loss yields per-member
    cotangents.  Two corrections rebuild the true global gradient:

    * the replica loss is divided by the replica's member count S·tp
      before grad — each member seeds a cotangent of 1 into ITS copy of
      the (psum-broadcast) loss, and those seeds all flow back through
      the same psum, so the raw per-member gradient is S·tp× the true
      one (this is the in-body mirror of the dp=1 path's outer
      ``jnp.mean`` over member copies);
    * leaves REPLICATED over a replica axis (tp-replicated norm scales,
      the pipe-replicated embed/final norm) get their gradients psum'd
      over the missing axes afterwards — each copy only accumulated the
      cotangent of its own partial use, and summing the copies is
      exactly what shard_map's replication transpose does at the
      boundary in the dp=1 path.

    The loss is the GLOBAL batch mean (CE sums and token counts psum
    over dp BEFORE the division — the same objective as the loss path),
    so each member's raw gradient is its replica's PARTIAL of the global
    gradient and the dp sync that closes it is a plain sum: ``psum``
    mode is one psum per leaf (optimizer state dp-replicated),
    ``reduce_scatter`` mode is a per-leaf ``psum_scatter`` on a
    :func:`~repro.core.dataparallel.grad_sync.zero1_scatter_dim`, a
    shard-local AdamW update on dp-SHARDED (master, m, v), and one
    ``all_gather`` to rebuild the bf16 params — ZeRO-1 with ×1/dp
    optimizer memory.  Both modes perform identical sums, so they agree
    to reduction tolerance (validated in
    ``tests/helpers/run_spmd_dp_pipeline.py``)."""
    from .dataparallel import grad_sync as GS
    replica_fn, in_specs, manual, out_axes = _pipeline_replica_core(
        cfg, spec, mesh, remat=remat, schedule=schedule)
    param_specs, mask_spec, tok_spec = in_specs
    dp, dpax = spec.data_parallel, spec.dp_axis
    S, tp, b = spec.num_stages, spec.tensor_parallel, spec.microbatches
    nmem = S * tp
    axis_sizes = {spec.pipe_axis: S}
    if tp > 1:
        axis_sizes[spec.tp_axis] = tp
    axis_sizes_dp = dict(axis_sizes, **{dpax: dp})

    aps = abstract_stage_params(cfg, spec)
    msizes = dict(mesh.shape)

    def _local_shape(leaf, pspec):
        shape = list(leaf.shape)
        for i, ax in enumerate(pspec):
            if ax is None:
                continue
            for a in ((ax,) if isinstance(ax, str) else tuple(ax)):
                shape[i] //= msizes.get(a, 1)
        return tuple(shape)

    if grad_sync == "reduce_scatter":
        def _sdim(leaf, pspec):
            taken = [i for i, ax in enumerate(pspec) if ax is not None]
            return GS.zero1_scatter_dim(_local_shape(leaf, pspec), dp,
                                        taken)
        scatter_dims = jax.tree.map(_sdim, aps, param_specs)
    else:
        scatter_dims = jax.tree.map(lambda _: None, aps)

    def _with_dp(leaf, pspec, d):
        parts = list(pspec) + [None] * (leaf.ndim - len(pspec))
        if d is not None:
            assert parts[d] is None, (pspec, d)
            parts[d] = dpax
        return P(*parts)

    opt_specs = jax.tree.map(_with_dp, aps, param_specs, scatter_dims)

    def step_body(stage_params, opt_state, step, mask, tokens):
        def scaled_loss(p):
            # the GLOBAL batch mean: CE sums and token counts cross dp
            # BEFORE the division (same objective as the loss path — a
            # per-replica division would silently diverge from it the
            # moment denom became data-dependent).  Non-uniform domains
            # need no extra weighting here: replica r's sums cover its
            # own allocations[r] microbatches, so the psum IS the
            # allocation-weighted global total (DESIGN.md §13)
            loss_sum, denom, aux_sum = replica_fn(p, mask, tokens)
            loss_sum = jax.lax.psum(loss_sum, dpax)
            denom = jax.lax.psum(denom, dpax)
            aux_sum = jax.lax.psum(aux_sum, dpax)
            aux = aux_sum / spec.total_microbatches if spec.batch_domain \
                else aux_sum / dp / max(b, 1)
            gl = loss_sum / jnp.maximum(denom, 1.0) + aux
            return jnp.sum(gl) / (nmem * dp)

        val, grads = jax.value_and_grad(scaled_loss)(stage_params)

        def _fix(g, pspec):
            missing = tuple(a for a in axis_sizes
                            if a not in GS.spec_axes(pspec))
            return jax.lax.psum(g, missing) if missing else g

        grads = jax.tree.map(_fix, grads, param_specs)

        # dp sync: each member holds its replica's PARTIAL of the global
        # gradient (the loss psums over dp divided every seed by dp), so
        # the sync is a plain psum — bucketed fused all-reduces in
        # wgrad-completion order when spec.bucket_bytes > 0 (the §10
        # program the overlap model prices), per-leaf psums otherwise,
        # or per-leaf scatters into ZeRO-1 shards (each leaf stays its
        # own message there: the scatter dim is leaf-specific)
        if grad_sync == "psum" and spec.bucket_bytes > 0:
            grads = _bucketed_dp_psum(grads, dpax, spec.n_chunks,
                                      spec.bucket_bytes)
        else:
            def _sync(g, d):
                if d is None:
                    return jax.lax.psum(g, dpax)
                return jax.lax.psum_scatter(
                    g, dpax, scatter_dimension=d, tiled=True)

            grads = jax.tree.map(_sync, grads, scatter_dims)
        gnorm = GS.replica_grad_norm(grads, opt_specs, axis_sizes_dp)
        new_params, new_opt, om = adamw.apply_update(
            opt_cfg, opt_state, grads, step, stage_params,
            grad_norm=gnorm)
        if grad_sync == "reduce_scatter":
            def _gather(p_new, d):
                if d is None:
                    return p_new
                return jax.lax.all_gather(p_new, dpax, axis=d, tiled=True)
            new_params = jax.tree.map(_gather, new_params, scatter_dims)
        mets = {"loss": jnp.reshape(val * (nmem * dp), (1,)),
                "grad_norm": jnp.reshape(om["grad_norm"], (1,)),
                "lr": jnp.reshape(om["lr"], (1,))}
        return new_params, new_opt, mets

    from .jax_compat import shard_map
    opt_tree_specs = {"master": opt_specs, "m": opt_specs, "v": opt_specs}
    met_specs = {"loss": P(out_axes), "grad_norm": P(out_axes),
                 "lr": P(out_axes)}
    smapped = shard_map(
        step_body, mesh=mesh,
        in_specs=(param_specs, opt_tree_specs, P(), mask_spec, tok_spec),
        out_specs=(param_specs, opt_tree_specs, met_specs),
        manual_axes=manual)

    def train_step(state, mask, batch):
        params, opt_state, step = state
        tokens = _prepare_domain_tokens(spec, batch["tokens"])
        new_p, new_opt, mets = smapped(params, opt_state, step, mask,
                                       tokens)
        return ((new_p, new_opt, step + 1),
                {k: jnp.mean(v) for k, v in mets.items()})

    return train_step


# ---------------------------------------------------------------------------
# simulate path (numerics oracle; supports per-stage recompute trivially)
# ---------------------------------------------------------------------------

def simulate_pipeline_forward(params: PyTree, cfg: ModelConfig,
                              spec: PipelineSpec, batch: Dict[str, jnp.ndarray]
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Run the pipeline global-stage-by-global-stage on the local device
    (following the schedule's chunk placement for chunked specs); must
    equal the monolithic ``M.forward`` exactly (tested)."""
    if spec.stage_tp:
        raise NotImplementedError(
            "simulate_pipeline_forward is the uniform-layout oracle; "
            "grouped specs (non-uniform per-stage tp) hold tp-sharded "
            "per-device params — validate them against the monolithic "
            "forward directly (DESIGN.md §12)")
    stage_params, mask = split_stage_params(params, cfg, spec)
    kind = M._block_kind(cfg)
    tokens = batch["tokens"]
    x = layers.embed_tokens(params["embed"], tokens)
    aux_total = jnp.float32(0)
    S, v = spec.num_stages, spec.n_chunks
    sched = _spec_schedule(spec) if v > 1 else None
    for g in range(S * v):
        if v == 1:
            s, sel, mrow = g, (g,), mask[g]
        else:
            s = sched.device_of(g, S)
            k = next(k for k in range(v)
                     if sched.global_stage(s, k, S) == g)
            sel, mrow = (s, k), mask[s, k]
        blocks = jax.tree.map(lambda t: t[sel], stage_params["blocks"])
        x, aux = _stage_forward(blocks, mrow, cfg, x, kind,
                                remat=spec.recompute[s])
        aux_total = aux_total + aux
    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    logits = layers.unembed(params["embed"], x)
    return logits, aux_total
