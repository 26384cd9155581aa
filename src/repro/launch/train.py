"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1p5_0p5b \
        --steps 100 --batch 8 --seq 256 [--model-parallel 1] [--accum 1] \
        [--pipeline-parallel 4 --tensor-parallel 2 --data-parallel 2 \
         --schedule 1f1b --microbatches 4 --grad-sync reduce_scatter] \
        [--plan plan.json | --search A:2,B:2] \
        [--ckpt-dir ckpts --ckpt-every 50] [--smoke] \
        [--backend auto|einsum|pallas]

Uses whatever devices exist (CPU/TPU); on a real TPU fleet the same flags
drive the production mesh.  ``--smoke`` selects the reduced config family.
``--pipeline-parallel N`` switches to the shard_map HeteroPP pipeline over
N devices; ``--schedule`` picks the pipeline schedule (see
``repro.core.schedules``) — chunked schedules (``interleaved``,
``interleaved3``, ``zb_v``) run with v chunk slots per device via the
schedule-derived tick tables.  ``--tensor-parallel N`` adds a manual tp
mesh axis: each stage is sharded Megatron-style over N tp members
(DESIGN.md §8).  ``--data-parallel N`` adds a leading manual dp axis:
N pipeline replicas each stream their own microbatches and close
gradients with the ``--grad-sync`` mode (flat psum, or ZeRO-1
reduce-scatter + all-gather with dp-sharded optimizer state —
DESIGN.md §9) on the up-to-3-D ``(dp, pipe, tp)`` mesh.  ``--plan
plan.json`` executes a saved HeteroAuto ``ParallelPlan`` (see
``examples/hetero_search.py --save-plan``) through ``heteropp.from_plan``
— schedule, non-uniform layer split AND the plan's tp and dp included.
Plans whose stages DISAGREE on tp execute too, via the grouped stage
runtime (DESIGN.md §12): a flat pipe mesh where stage k owns tp_k
devices, with the §5 reshard collective (sr_ag vs naive, picked per
boundary by ``resharding.boundary_time``) at every tp-differing stage
boundary.  Plans carrying a non-uniform ``batch_domain`` execute too:
each dp replica runs the schedule's tick program for its own
allocation, padded to the pacing replica's length (DESIGN.md §13).
``--search A:2,B:2`` runs the HeteroAuto search on the given chip
cluster first and executes the winner the same way (``--search-dp``
widens the dp candidate set, ``--search-uneven-dp`` admits dp degrees
that do not divide the batch; dp·pp·tp — or Σ tp_k for grouped plans —
must fit the available devices; only genuinely inexpressible layouts
are refused: non-uniform tp under a chunked schedule, grouped tp ×
dp > 1).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpointing.io import load_checkpoint, save_checkpoint
from ..configs import canonical, get_config, get_smoke_config, list_configs
from ..core.schedules import available_schedules
from ..data.pipeline import DataConfig, make_loader
from ..optim.adamw import AdamWConfig
from ..sharding import ctx, rules
from ..training.train_step import (abstract_train_state, make_train_state,
                                   make_train_step)
from .compile_cache import enable_compile_cache
from .mesh import make_local_mesh


@dataclasses.dataclass
class TrainRun:
    """What a training run hands back to an in-process caller: the loss
    and the mean wall time per step at every logged step, and the final
    state.  The GSPMD path also gives its compile time and the compiled
    step (whose HLO shows which kernels it runs)."""
    steps: List[int] = dataclasses.field(default_factory=list)
    losses: List[float] = dataclasses.field(default_factory=list)
    step_times_s: List[float] = dataclasses.field(default_factory=list)
    compile_s: Optional[float] = None
    compiled: Any = None
    mesh: Any = None
    state: Any = None

    def log(self, step: int, loss: float, step_time_s: float) -> None:
        self.steps.append(step)
        self.losses.append(loss)
        self.step_times_s.append(step_time_s)


def _pipeline_spec(args, cfg):
    """Resolve the PipelineSpec plus the dp grad-sync mode: from a saved
    plan (--plan), a fresh HeteroAuto search (--search), or the uniform
    CLI split.  Plans carry their searched sync config (dp_sync +
    bucket_bytes — DESIGN.md §10), so the plan paths refuse an explicit
    --grad-sync exactly like the other plan-owned flags.  Returns
    ``(spec, grad_sync, plan-or-None)`` — the plan rides along so the
    observability layer can price its expectations (DESIGN.md §14)."""
    from ..core import heteropp as HP

    mb = args.microbatches
    if args.plan and args.search:
        raise SystemExit("--plan and --search are mutually exclusive")
    if (args.search_dp or args.search_uneven_dp) and not args.search:
        flag = "--search-dp" if args.search_dp else "--search-uneven-dp"
        raise SystemExit(f"{flag} only shapes the HeteroAuto search; "
                         f"add --search CHIP:N,...")
    if args.plan or args.search:
        # the plan carries schedule, stage count, tp, dp AND the grad-
        # sync config; conflicting explicit flags would be silently
        # ignored — refuse instead
        src = "--plan" if args.plan else "--search"
        if args.schedule is not None:
            raise SystemExit(f"{src} uses the plan's schedule; drop "
                             f"--schedule {args.schedule}")
        if args.grad_sync is not None:
            raise SystemExit(f"{src} sets the grad-sync mode from the "
                             f"plan (searched over sync mode × bucket "
                             f"size — DESIGN.md §10); drop --grad-sync "
                             f"{args.grad_sync}")
        if args.pipeline_parallel > 1:
            raise SystemExit(f"{src} sets the stage count from the plan; "
                             f"drop --pipeline-parallel")
        if args.tensor_parallel:
            raise SystemExit(f"{src} sets tp from the plan (uniform plans "
                             f"execute on the (pipe, tp) mesh, non-uniform "
                             f"ones via the grouped stage runtime); drop "
                             f"--tensor-parallel {args.tensor_parallel}")
        if args.data_parallel:
            raise SystemExit(f"{src} sets dp from the plan (uniform batch "
                             f"domains execute on the (dp, pipe, tp) "
                             f"mesh); drop --data-parallel "
                             f"{args.data_parallel}")
        if args.bucket_bytes:
            raise SystemExit(f"{src} sets the grad-sync bucket size from "
                             f"the plan (searched over bucket size × sync "
                             f"mode — DESIGN.md §10); drop --bucket-bytes "
                             f"{args.bucket_bytes}")

    def _from_plan(plan):
        if not args.no_verify_plan:
            # static verification gate (DESIGN.md §15): cfg-full — the
            # plan-shape / schedule-safety / collective-divergence
            # passes plus memory bounds and kernel lint.  Errors refuse
            # the plan before anything compiles; warnings print.
            from ..analysis import analyze_plan, format_report, split
            diags = analyze_plan(plan, cfg, seq_len=args.seq,
                                 gbs_tokens=args.batch * args.seq,
                                 microbatches=mb or None)
            errs, warns = split(diags)
            for d in warns:
                print(f"plan verifier: WARNING {d.format()}")
            if errs:
                raise SystemExit(
                    "plan fails static verification (DESIGN.md §15; "
                    "--no-verify-plan to bypass):\n"
                    + format_report(errs))
        try:
            # verify=False: the gate above already ran (or the user
            # bypassed it explicitly)
            spec = HP.from_plan(plan, microbatches=mb or None,
                                execute_tp=True, execute_dp=True,
                                verify=False)
            HP.validate_spec_tp(cfg, spec)
            # the plan's searched sync mode executes too (its
            # bucket_bytes already rode in through from_plan)
            return spec, plan.dp_sync, plan
        except (ValueError, NotImplementedError) as e:
            raise SystemExit(str(e)) from None

    if args.plan:
        import json
        from ..core.cost_model import ParallelPlan
        with open(args.plan) as f:
            try:
                plan = ParallelPlan.from_dict(json.load(f))
            except (KeyError, ValueError) as e:
                raise SystemExit(f"--plan {args.plan}: {e}") from None
        print(f"plan [{args.plan}]: {plan.describe()}")
        return _from_plan(plan)
    if args.search:
        from ..core import chips, heteroauto
        groups = []
        for part in args.search.split(","):
            name, count = part.split(":")
            groups.append(chips.ChipGroup(chips.CHIPS[name], int(count)))
        dp_cands = [int(d) for d in args.search_dp.split(",")] \
            if args.search_dp else [1]
        r = heteroauto.search(groups, cfg, args.batch * args.seq, args.seq,
                              two_stage=False, dp_candidates=dp_cands,
                              uneven_dp=args.search_uneven_dp)
        if r.plan is None:
            raise SystemExit(f"--search {args.search}: no feasible plan for "
                             f"{cfg.name}")
        print(f"searched plan ({r.evaluated} configs, {r.search_time_s:.2f}s): "
              f"{r.plan.describe()} [{r.runtime}]")
        return _from_plan(r.plan)
    from ..core.schedules import get_schedule
    pp = args.pipeline_parallel
    tp = args.tensor_parallel or 1
    dp = args.data_parallel or 1
    try:
        HP.validate_tensor_parallel(cfg, tp)
    except (ValueError, NotImplementedError) as e:
        raise SystemExit(str(e)) from None
    grad_sync = args.grad_sync or "reduce_scatter"
    # flags the step would never consult must refuse, not silently drop
    # (same rule as the other conflicting flags)
    if args.grad_sync is not None and dp <= 1:
        raise SystemExit(
            f"--grad-sync {args.grad_sync} needs --data-parallel > 1: "
            f"there is no dp gradient sync without dp replicas")
    if args.bucket_bytes:
        if args.bucket_bytes < 0:
            raise SystemExit(
                f"--bucket-bytes must be positive: {args.bucket_bytes}")
        if dp <= 1:
            raise SystemExit(
                f"--bucket-bytes {args.bucket_bytes} needs "
                f"--data-parallel > 1: there is no dp grad sync to "
                f"bucket")
        if grad_sync != "psum":
            raise SystemExit(
                f"--bucket-bytes {args.bucket_bytes} only shapes the "
                f"psum sync mode (ZeRO-1 reduce_scatter keeps one "
                f"message per leaf — DESIGN.md §10); add "
                f"--grad-sync psum or drop the flag")
    sched = get_schedule(args.schedule or "1f1b")
    base, rem = divmod(cfg.num_layers, pp)
    phys = [base + (1 if i < rem else 0) for i in range(pp)]
    spec = HP.PipelineSpec(pp, HP.chunk_layer_counts(phys, sched),
                           microbatches=mb or pp, schedule=sched.name,
                           n_chunks=sched.n_chunks, tensor_parallel=tp,
                           data_parallel=dp,
                           bucket_bytes=args.bucket_bytes)
    return spec, grad_sync, None


def _run_dir(args, cfg) -> str:
    return args.run_dir or os.path.join("runs", cfg.name)


def _export_obs(args, cfg, spec, mesh, plan, stage_params, mask, toks,
                run_dir: str) -> None:
    """--trace epilogue (DESIGN.md §14): predicted timeline from the
    event simulator, executed timeline from the fenced per-tick
    re-drive, alignment report + straggler sections, all written next
    to ``metrics.jsonl``."""
    from ..obs import align_traces, write_trace
    from ..obs.align import per_replica_seconds, per_stage_seconds
    from ..obs.runtime import trace_spmd_pipeline
    from ..obs.straggler import replica_stragglers, stage_stragglers
    from ..obs.trace import (predicted_trace_for_plan,
                             predicted_trace_for_spec)
    if plan is not None:
        predicted, _ = predicted_trace_for_plan(
            plan, cfg, args.seq, grad_sync=plan.dp > 1)
    else:
        predicted, _ = predicted_trace_for_spec(spec)
    executed = trace_spmd_pipeline(cfg, spec, mesh, stage_params, mask,
                                   toks)
    report = align_traces(predicted, executed)
    stragglers = {}
    if plan is not None:
        from ..core.cost_model import evaluate
        cost = evaluate(plan, cfg, args.seq, args.batch * args.seq)
        measured = per_stage_seconds(executed)
        stages = sorted(measured)
        stragglers["stage"] = stage_stragglers(
            plan, cost, [measured[s] for s in stages],
            factor=args.straggler_factor)
    if spec.data_parallel > 1:
        # expected ∝ allocations (uniform per-microbatch time): the
        # median normalization makes the unit irrelevant
        per_rep = per_replica_seconds(executed)
        reps = sorted(per_rep)
        stragglers["replica"] = replica_stragglers(
            spec.batch_allocations, 1.0, [per_rep[r] for r in reps],
            factor=args.straggler_factor)
    report["stragglers"] = stragglers
    write_trace(os.path.join(run_dir, "trace_predicted.json"), predicted)
    write_trace(os.path.join(run_dir, "trace_executed.json"), executed)
    import json
    if plan is not None:
        # persist the executed plan so repro.obs.validate can fold the
        # static plan lint into the run-dir check (DESIGN.md §15)
        with open(os.path.join(run_dir, "plan.json"), "w",
                  encoding="utf-8") as f:
            json.dump(plan.to_dict(), f, indent=2)
    with open(os.path.join(run_dir, "align.json"), "w",
              encoding="utf-8") as f:
        json.dump(report, f, indent=2)
    err = report["max_abs_rel_err"]
    print(f"trace: {run_dir}/trace_executed.json "
          f"ticks={report['executed_ticks']} "
          f"(priced {report['priced_ticks']}, "
          f"match={report['ticks_match']}) "
          f"wall={executed['metadata']['wall_s']:.3f}s "
          f"max_share_err={err if err is None else round(err, 4)}",
          flush=True)


def run_pipeline(args, cfg) -> TrainRun:
    """shard_map pipeline training: one physical stage (v chunk slots of
    layers for chunked schedules) per pipe-axis member; dp replicates
    the whole pipeline over a leading mesh axis (DESIGN.md §9)."""
    from jax.sharding import Mesh
    from ..core import heteropp as HP
    from ..optim import adamw

    devices = jax.devices()
    spec, grad_sync, plan = _pipeline_spec(args, cfg)
    pp, tp, dp = spec.num_stages, spec.tensor_parallel, spec.data_parallel
    if spec.grouped:
        # non-uniform per-stage tp: flat 1-D pipe mesh of Σ tp_k devices,
        # stage k owning tp_k of them (DESIGN.md §12)
        need = spec.pipe_width
        if len(devices) < need:
            raise SystemExit(
                f"grouped pipeline needs ≥Σtp={need} devices "
                f"(stage_tp={spec.stage_tp}, have {len(devices)})")
        mesh = Mesh(np.array(devices[:need]), ("pipe",))
    else:
        need = dp * pp * tp
        if len(devices) < need:
            raise SystemExit(f"pipeline needs ≥{dp}·{pp}·{tp}={need} "
                             f"devices (have {len(devices)})")
        sizes = [("dp", dp), ("pipe", pp), ("tp", tp)]
        sizes = [(a, n) for a, n in sizes if n > 1 or a == "pipe"]
        mesh = Mesh(np.array(devices[:need]).reshape([n for _, n in sizes]),
                    tuple(a for a, _ in sizes))

    mb = spec.microbatches
    # global batch in microbatches: Σ per-replica allocations (= dp·mb
    # for uniform domains); non-uniform domains feed the runtime the
    # TIGHT replica-major layout, which packs it onto the padded
    # per-replica slots itself (DESIGN.md §13)
    total_mb = spec.total_microbatches
    if args.batch % total_mb:
        raise SystemExit(f"--batch {args.batch} not divisible by the "
                         f"global microbatch count "
                         f"Σ allocations = {total_mb} "
                         f"(allocations {list(spec.batch_allocations)})")
    if spec.total_layers != cfg.num_layers:
        raise SystemExit(f"plan covers {spec.total_layers} layers but "
                         f"{cfg.name} has {cfg.num_layers}")
    print(f"pipeline: stages={pp} "
          + (f"stage_tp={spec.stage_tp} reshard={spec.reshard} "
             if spec.grouped else f"tp={tp} dp={dp} ")
          + f"v={spec.n_chunks} "
          f"layers/global-stage={spec.layers_per_stage} microbatches={mb} "
          + (f"batch_domain={list(spec.batch_domain)} "
             if spec.batch_domain else "")
          + f"schedule={spec.schedule}"
          + (f" grad_sync={grad_sync}" if dp > 1 else "")
          + (f" bucket_bytes={spec.bucket_bytes}"
             if dp > 1 and grad_sync == "psum" and spec.bucket_bytes
             else ""))

    from ..models import model as M
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    stage_params, mask = HP.split_stage_params(params, cfg, spec)
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(args.steps // 20, 5))
    # the state is donated: the step's output takes its buffers
    step_fn = jax.jit(HP.make_spmd_pipeline_train_step(
        cfg, spec, mesh, opt, grad_sync=grad_sync), donate_argnums=(0,))
    state = (stage_params, adamw.init_opt_state(stage_params),
             jnp.int32(0))

    from ..obs import MetricsLogger
    from ..obs.runtime import device_memory_highwater
    run_dir = _run_dir(args, cfg)
    meta = {"arch": cfg.name, "family": cfg.family, "mode": "pipeline",
            "devices": need, "stages": pp, "tp": tp, "dp": dp,
            "schedule": spec.schedule, "microbatches": mb,
            "batch": args.batch, "seq": args.seq}
    if plan is not None:
        # the plan's priced expectations ride in the meta row so the
        # drift/straggler reports are reproducible from the JSONL alone
        from ..core.cost_model import evaluate
        cost = evaluate(plan, cfg, args.seq, args.batch * args.seq)
        meta.update(priced_iter_time_s=cost.iter_time,
                    priced_tgs=cost.tgs,
                    priced_exposed_sync_s=sum(cost.exposed_sync),
                    priced_reshard_s=sum(cost.t_reshard))
    metrics = MetricsLogger(run_dir, meta=meta)
    run = TrainRun(mesh=mesh)

    dcfg = DataConfig(batch_size=args.batch, seq_len=args.seq,
                      seed=1234 + args.seed)
    loader = make_loader(cfg, dcfg)
    tokens_per_step = args.batch * args.seq
    toks = None
    t0 = time.perf_counter()
    t_last, i_last = t0, 0
    for i in range(args.steps):
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            batch = next(loader)
            toks = batch["tokens"].reshape(total_mb, args.batch // total_mb,
                                           args.seq)
            state, m = step_fn(state, mask, {"tokens": toks})
        if (i + 1) % args.log_every == 0 or i == 0:
            row = {k: float(v) for k, v in m.items()}
            now = time.perf_counter()
            dt = now - t0
            tgs = tokens_per_step * (i + 1) / dt / need
            step_time = (now - t_last) / (i + 1 - i_last)
            run.log(i + 1, row["loss"], step_time)
            metrics.log(step=i + 1,
                        tokens_per_s=tokens_per_step * (i + 1) / dt,
                        tgs=tgs, step_time_s=step_time,
                        peak_bytes_in_use=device_memory_highwater(),
                        **row)
            t_last, i_last = now, i + 1
            print(f"step {i + 1:5d} loss={row['loss']:.4f} "
                  f"TGS={tgs:.0f}", flush=True)
    loader.close()
    if args.trace:
        _export_obs(args, cfg, spec, mesh, plan, state[0], mask, toks,
                    run_dir)
    metrics.close()
    run.state = state
    return run


def main(argv: Optional[List[str]] = None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_configs() + ["all"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--pipeline-parallel", type=int, default=1,
                    help="run the shard_map pipeline over N stages")
    ap.add_argument("--tensor-parallel", type=int, default=0,
                    help="with --pipeline-parallel: shard every stage "
                         "over N tp members on a 2-D (pipe, tp) mesh "
                         "(default 1; saved/searched plans carry their "
                         "own tp and refuse this flag)")
    ap.add_argument("--data-parallel", type=int, default=0,
                    help="with --pipeline-parallel: run N pipeline "
                         "replicas over a leading dp mesh axis, each "
                         "streaming its share of the microbatches "
                         "(default 1; saved/searched plans carry their "
                         "own dp and refuse this flag)")
    ap.add_argument("--grad-sync", default=None,
                    choices=["psum", "reduce_scatter"],
                    help="with --data-parallel: dp gradient sync mode — "
                         "flat psum (replicated optimizer state) or "
                         "ZeRO-1 reduce-scatter + all-gather "
                         "(dp-sharded optimizer state; default "
                         "reduce_scatter; saved/searched plans carry "
                         "their own sync config and refuse this flag)")
    ap.add_argument("--bucket-bytes", type=int, default=0,
                    help="with --data-parallel --grad-sync psum: coalesce "
                         "gradient leaves into fused per-bucket "
                         "all-reduces of at most this many bytes, issued "
                         "in wgrad-completion order (DESIGN.md §10); 0 = "
                         "one collective per leaf (saved/searched plans "
                         "carry their own bucket size and refuse this "
                         "flag)")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "einsum", "pallas"],
                    help="kernel path for the model math: auto (Pallas "
                         "kernels on TPU, jnp einsum/chunked elsewhere), "
                         "einsum (force jnp), pallas (force the kernels; "
                         "interpret mode off-TPU — correctness tool, not "
                         "a fast path). Applies to the GSPMD data-"
                         "parallel path; the shard_map pipeline resolves "
                         "backend='auto' per device.")
    ap.add_argument("--schedule", default=None,
                    choices=available_schedules(),
                    help="pipeline schedule (with --pipeline-parallel; "
                         "default 1f1b; saved/searched plans carry their "
                         "own)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="pipeline microbatches (default: = stages)")
    ap.add_argument("--plan", default=None,
                    help="run a saved HeteroAuto plan JSON through "
                         "heteropp.from_plan (schedule + non-uniform "
                         "layer split; see hetero_search.py --save-plan)")
    ap.add_argument("--search", default=None, metavar="CHIP:N,...",
                    help="HeteroAuto-search the given chip cluster and "
                         "run the winning plan (e.g. A:2,B:2)")
    ap.add_argument("--no-verify-plan", action="store_true",
                    help="skip the static plan verifier (repro.analysis, "
                         "DESIGN.md §15) that refuses --plan/--search "
                         "plans with H2Exxx errors before compiling")
    ap.add_argument("--search-dp", default=None, metavar="N,...",
                    help="with --search: dp candidate degrees (comma "
                         "list, default 1; the winner's dp executes on "
                         "the (dp, pipe, tp) mesh)")
    ap.add_argument("--search-uneven-dp", action="store_true",
                    help="with --search: also consider dp degrees that "
                         "do NOT divide the batch — the winner carries "
                         "a throughput-proportional batch_domain and "
                         "executes via per-replica tick programs "
                         "(DESIGN.md §13)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10,
                    help="cadence of BOTH the human step line and the "
                         "metrics.jsonl row")
    ap.add_argument("--run-dir", default=None,
                    help="observability output directory (metrics.jsonl "
                         "and, with --trace, the trace/alignment files; "
                         "default runs/<arch>)")
    ap.add_argument("--trace", action="store_true",
                    help="after training, re-drive the pipeline's tick "
                         "program host-fenced and write "
                         "trace_predicted.json / trace_executed.json / "
                         "align.json to --run-dir (DESIGN.md §14; "
                         "pipeline runs only)")
    ap.add_argument("--straggler-factor", type=float, default=1.5,
                    help="with --trace: flag a stage/replica whose "
                         "measured/priced ratio exceeds this factor × "
                         "the cohort median")
    args = ap.parse_args(argv)

    enable_compile_cache()
    name = canonical(args.arch)
    cfg = get_smoke_config(name) if args.smoke else get_config(name)
    print(f"arch={cfg.name} family={cfg.family} "
          f"params~{cfg.param_count() / 1e6:.1f}M devices={len(jax.devices())}")

    if args.pipeline_parallel > 1 or args.plan or args.search:
        return run_pipeline(args, cfg)
    if args.trace:
        # the trace is a pipeline artifact (per-tick program re-drive);
        # the GSPMD path has no tick program to trace — refuse rather
        # than silently write nothing
        raise SystemExit(
            "--trace re-drives the shard_map pipeline's tick program; "
            "add --pipeline-parallel N (or --plan/--search)")
    if args.tensor_parallel:
        # the GSPMD path below would silently ignore it — refuse instead
        raise SystemExit(
            f"--tensor-parallel {args.tensor_parallel} only applies to the "
            f"shard_map pipeline; add --pipeline-parallel N (or use "
            f"--model-parallel for GSPMD tensor parallelism)")
    if args.data_parallel:
        # likewise: the GSPMD path shards the batch on its own rules and
        # would silently ignore an explicit dp degree — refuse instead
        raise SystemExit(
            f"--data-parallel {args.data_parallel} only applies to the "
            f"shard_map pipeline; add --pipeline-parallel N (the GSPMD "
            f"path data-parallelizes over the mesh's data axes by "
            f"itself)")

    return run_gspmd(args, cfg)


def run_gspmd(args, cfg) -> TrainRun:
    """GSPMD data/model-parallel training over the local mesh.  The step
    is compiled ahead of the loop, so its compile time is reported apart
    from the step times."""
    mesh = make_local_mesh(model=args.model_parallel)
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(args.steps // 20, 5))

    with ctx.use_mesh(mesh):
        state_sh = rules.train_state_shardings(
            abstract_train_state(cfg), mesh, hybrid=cfg.family == "hybrid")
        state = jax.device_put(
            make_train_state(cfg, jax.random.PRNGKey(args.seed)), state_sh)
        if args.ckpt_dir:
            from ..checkpointing.io import checkpoint_step
            if checkpoint_step(args.ckpt_dir) is not None:
                state = load_checkpoint(args.ckpt_dir,
                                        jax.eval_shape(lambda: state),
                                        state_sh)
                print(f"resumed from {args.ckpt_dir} at step {int(state.step)}")

        dcfg = DataConfig(batch_size=args.batch, seq_len=args.seq,
                          seed=1234 + args.seed)
        loader = make_loader(cfg, dcfg)
        batch = next(loader)
        batch_sh = rules.batch_shardings(batch, mesh)
        # the state is donated: the step's output takes its buffers, so
        # HBM holds one copy of params + AdamW state, not two
        step_fn = jax.jit(make_train_step(cfg, opt, accum_steps=args.accum,
                                          backend=args.backend),
                          in_shardings=(state_sh, batch_sh),
                          out_shardings=(state_sh, None),
                          donate_argnums=(0,))
        t_c = time.perf_counter()
        compiled = step_fn.lower(state, batch).compile()
        compile_s = time.perf_counter() - t_c
        print(f"compile: {compile_s:.1f}s", flush=True)

        from ..obs import MetricsLogger
        from ..obs.runtime import device_memory_highwater
        metrics = MetricsLogger(
            _run_dir(args, cfg),
            meta={"arch": cfg.name, "family": cfg.family, "mode": "gspmd",
                  "devices": len(jax.devices()), "batch": args.batch,
                  "seq": args.seq, "compile_s": compile_s})
        run = TrainRun(compile_s=compile_s, compiled=compiled, mesh=mesh)
        tokens_per_step = args.batch * args.seq
        t0 = time.perf_counter()
        t_last, i_last = t0, 0
        for i in range(args.steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                if i:
                    batch = next(loader)
                state, m = compiled(state, jax.device_put(batch, batch_sh))
            if (i + 1) % args.log_every == 0 or i == 0:
                loss = float(m["loss"])
                now = time.perf_counter()
                dt = now - t0
                tgs = tokens_per_step * (i + 1) / dt / len(jax.devices())
                step_time = (now - t_last) / (i + 1 - i_last)
                run.log(i + 1, loss, step_time)
                metrics.log(step=i + 1,
                            tokens_per_s=tokens_per_step * (i + 1) / dt,
                            tgs=tgs, step_time_s=step_time,
                            peak_bytes_in_use=device_memory_highwater(),
                            **{k: float(v) for k, v in m.items()})
                t_last, i_last = now, i + 1
                print(f"step {i + 1:5d} loss={loss:.4f} "
                      f"lr={float(m['lr']):.2e} gnorm={float(m['grad_norm']):.2f} "
                      f"TGS={tgs:.0f}", flush=True)
            if args.ckpt_dir and args.ckpt_every and \
                    (i + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, state, step=i + 1)
        loader.close()
        metrics.close()
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, state, step=args.steps)
            print(f"checkpoint saved to {args.ckpt_dir}")
    run.state = state
    return run


if __name__ == "__main__":
    main()
