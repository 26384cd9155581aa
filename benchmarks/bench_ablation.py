"""Paper Table 9 (+ Fig 12) — ablations on the Exp-C-1 configuration:
relative iteration time of DDR vs TCP transport, HeteroPP vs uniform layer
split, SR&AG resharding on/off, fine-grained overlap on/off, pipeline
SCHEDULE (GPipe / 1F1B / interleaved / ZB-H1 / ZB-V, the §5 wgrad-overlap
ablation; backward-split rows use the profiler's analytic per-stage
dgrad/wgrad fractions), a tp ablation (uniform executable tp — the
shape the 2-D (pipe, tp) runtime can run, DESIGN.md §8 — vs the searched
per-stage tp), and a dp ablation (DESIGN.md §9: flat-psum vs bucketed
ZeRO-1 reduce-scatter gradient sync over the comm/latency transports,
plus uniform vs throughput-proportional batch domains across
heterogeneous replica sets) — replayed through the generic event-driven
schedule simulator and the dataparallel closed forms.

    PYTHONPATH=src python -m benchmarks.bench_ablation [--schedule 1f1b]

``--schedule`` sets the reference schedule for the transport/resharding/
overlap rows; the schedule ablation section always sweeps all of them.
"""
import argparse
import dataclasses
import sys

from .common import emit

PAPER = {
    "full": 100.0, "tcp": 110.1, "uniform": 126.4,
    "no_srag": 104.8, "no_overlap": 101.8,
}


def main(argv=None):
    from repro.configs import get_config
    from repro.core import chips, heteroauto, schedule as SCH
    from repro.core.cost_model import ParallelPlan, StagePlan
    from repro.core.schedules import available_schedules, get_schedule

    ap = argparse.ArgumentParser()
    ap.add_argument("--schedule", default="1f1b",
                    choices=available_schedules(),
                    help="reference schedule for the Table 9 rows")
    args = ap.parse_args(argv if argv is not None else [])

    cfg = get_config("h2_100b")
    groups = chips.cluster(("A", 384), ("B", 1024))   # Exp-C-1
    r = heteroauto.search(groups, cfg, 4 * 2 ** 20, 4096, two_stage=True,
                          schedule=args.schedule)
    plan = r.plan
    assert plan is not None

    def run(transport="device_rdma", resharding="sr_ag", overlap=True,
            the_plan=None, schedule=None):
        return SCH.simulate_plan(the_plan or plan, cfg, 4096,
                                 schedule=schedule or args.schedule,
                                 transport=transport, resharding=resharding,
                                 overlap=overlap).makespan

    full = run()
    emit("table9.full", "100.0%",
         f"makespan={full:.2f}s (reference, schedule={args.schedule})")
    emit("table9.tcp", f"{run(transport='cpu_tcp') / full:.1%}",
         f"paper: {PAPER['tcp']}%")
    emit("table9.no_srag", f"{run(resharding='naive') / full:.1%}",
         f"paper: {PAPER['no_srag']}%")
    emit("table9.no_overlap", f"{run(overlap=False) / full:.1%}",
         f"paper: {PAPER['no_overlap']}%")

    # schedule ablation (§5 backward-split / wgrad-overlap): same plan,
    # every schedule that supports its (S, b)
    S, b = plan.total_pp, plan.microbatches
    for name in available_schedules():
        if not get_schedule(name).supports(S, b):
            emit(f"table9.schedule.{name}", "n/a",
                 f"unsupported for S={S} b={b}")
            continue
        emit(f"table9.schedule.{name}", f"{run(schedule=name) / full:.1%}",
             f"relative makespan vs {args.schedule} reference")

    # grad-sync overlap ablation (DESIGN.md §10): replay the plan with
    # explicit per-bucket dp sync events — the exposed tail is the part
    # of the sync the schedule cannot hide under its wgrad wave; the
    # legacy column is the pre-§10 constant-overlap heuristic.  These
    # rows land in BENCH_ablation.json via benchmarks/run.py.
    ov_plan = plan if plan.dp > 1 else dataclasses.replace(plan, dp=4)
    ov_whatif = "" if plan.dp > 1 else f" (what-if dp={ov_plan.dp})"
    for name in ("1f1b", "zb_h1", "zb_v", "wave"):
        if not get_schedule(name).supports(ov_plan.total_pp,
                                           ov_plan.microbatches):
            emit(f"table_overlap.{name}", "n/a",
                 f"unsupported for S={ov_plan.total_pp} "
                 f"b={ov_plan.microbatches}")
            continue
        ov = SCH.simulate_plan(ov_plan, cfg, 4096, schedule=name,
                               grad_sync=True)
        legacy = SCH.simulate_plan(ov_plan, cfg, 4096, schedule=name)
        emit(f"table_overlap.{name}",
             f"{max(ov.exposed_sync) * 1e3:.1f}ms",
             f"exposed dp-sync tail; overlap-aware makespan "
             f"{ov.makespan:.2f}s vs legacy-heuristic {legacy.makespan:.2f}s"
             f"{ov_whatif}")
    for mode in ("psum", "reduce_scatter"):
        ov = SCH.simulate_plan(ov_plan, cfg, 4096, grad_sync=True,
                               sync_mode=mode)
        emit(f"table_overlap.mode.{mode}",
             f"{max(ov.exposed_sync) * 1e3:.1f}ms",
             f"exposed tail under {mode} bucket structure, "
             f"schedule={ov_plan.schedule}{ov_whatif}")

    # uniform 1F1B: what a homogeneous-style framework would do on the same
    # chips — ONE tp everywhere, equal layers per stage, uniform recompute
    dp = plan.dp
    tp = 4
    uni_stages = []
    total_pp = sum(g.count // (tp * dp) for g in groups)
    acc = 0
    for i, g in enumerate(groups):
        pp = g.count // (tp * dp)
        layers = (cfg.num_layers * pp // total_pp) if i < len(groups) - 1 \
            else cfg.num_layers - acc
        acc += layers
        uni_stages.append(StagePlan(g, tp, pp, layers, recompute=True))
    uni = ParallelPlan(uni_stages, dp, plan.microbatches)
    emit("table9.uniform_1f1b", f"{run(the_plan=uni) / full:.1%}",
         f"paper: {PAPER['uniform']}% (tp=4 everywhere, equal layers/stage)")

    # tp ablation: force ONE tp degree across every stage — what a
    # uniform framework would run — vs the searched per-stage tp, which
    # the grouped stage runtime now executes for real (DESIGN.md §12).
    # Keeping pp and the layer split fixed changes the chip budget, so
    # these are WHAT-IF rows (the chip counts are in the detail column),
    # not feasible same-cluster alternatives.
    tps = sorted({s.tp for s in plan.stages})
    for tp_f in sorted({1, max(tps)}):
        forced = ParallelPlan(
            [dataclasses.replace(s, tp=tp_f) for s in plan.stages],
            plan.dp, plan.microbatches, plan.schedule)
        emit(f"table9.tp_whatif{tp_f}",
             f"{run(the_plan=forced) / full:.1%}",
             f"what-if uniform tp={tp_f} vs searched per-stage tp={tps}, "
             f"same pp/layer split — uses {forced.total_chips} chips vs "
             f"the plan's {plan.total_chips}")

    # §5 boundary resharding: the collective the grouped runtime now
    # executes at every tp-differing stage boundary (DESIGN.md §12) —
    # naive vs sr_ag wall time per boundary of the Exp-C-1 replay plan,
    # and the HLO-measured cross-stage payload vs the analytic byte
    # model the choice rests on.
    from repro.core import resharding as RS
    act = 4096 * cfg.d_model * 2              # one microbatch row, bf16
    bounds = [(i, plan.stages[i], plan.stages[i + 1])
              for i in range(len(plan.stages) - 1)
              if plan.stages[i].tp != plan.stages[i + 1].tp]
    rtag = ""
    if not bounds:
        # the searched plan came back tp-uniform: replay the tp-whatif
        # asymmetry as a boundary between the two chip islands instead
        s0, s1 = plan.stages[0], plan.stages[-1]
        bounds = [(0, dataclasses.replace(s0, tp=max(tps + [4])),
                   dataclasses.replace(s1, tp=1))]
        rtag = " (what-if: searched plan is tp-uniform)"
    for i, src, dst in bounds:
        kw = dict(nic_bw=src.group.spec.nic_bw,
                  intra_bw=dst.group.spec.intra_node_bw)
        t_nv = RS.boundary_time(act, src.tp, dst.tp, strategy="naive", **kw)
        t_sr = RS.boundary_time(act, src.tp, dst.tp, strategy="sr_ag", **kw)
        chosen = RS.choose_strategy(src.tp, dst.tp, **kw)
        emit(f"table_resharding.boundary{i}.naive", f"{t_nv * 1e3:.3f}ms",
             f"tp {src.tp}->{dst.tp} "
             f"({src.group.spec.name}->{dst.group.spec.name}), "
             f"act={act / 2 ** 20:.1f}MiB/microbatch{rtag}")
        emit(f"table_resharding.boundary{i}.sr_ag", f"{t_sr * 1e3:.3f}ms",
             f"speedup {t_nv / t_sr:.2f}x; chosen={chosen} — the strategy "
             f"from_plan bakes into the executed spec{rtag}")
    # measured vs analytic bytes: lower both reshard schedules on
    # virtual devices (subprocess, so the forced device count never
    # leaks) and read the cross-stage collective_permute payload out of
    # the StableHLO — the byte model the strategy choice rests on,
    # asserted against what the compiler actually moves
    # (cf. tests/test_resharding_exec.py).
    import os
    import re
    import subprocess
    import textwrap

    from repro.core.resharding import naive_cost, sr_ag_cost

    pipe, tp, rows, feat = 2, 4, 8, 512
    script = textwrap.dedent(f"""
        from repro.launch.hostdevices import force_host_device_count
        force_host_device_count({pipe * tp})
        import re
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.resharding import reshard
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh(({pipe}, {tp}), ("pipe", "tp"))
        x = jax.random.normal(jax.random.PRNGKey(0),
                              ({pipe}, {rows}, {feat}))
        x = jax.device_put(x, NamedSharding(mesh, P("pipe", None, "tp")))
        for strat in ("naive", "sr_ag"):
            txt = jax.jit(lambda v: reshard(v, mesh, strategy=strat)
                          ).lower(x).as_text()
            (dims,) = re.findall(
                r'collective_permute"[^\\n]*?tensor<([0-9x]+)xf32>',
                txt)
            elems = 1
            for d in dims.split("x"):
                elems *= int(d)
            print(f"BYTES {{strat}} {{elems * 4}}")
    """)
    # a CPU lowering by design: the child must never reach for the chip,
    # which this (JAX-initialized) parent may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + ":" + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"virtual-device reshard lowering failed:\n"
                           f"{r.stderr[-2000:]}")
    measured = dict(
        (m.group(1), int(m.group(2)))
        for m in re.finditer(r"BYTES (\w+) (\d+)", r.stdout))
    # per-rank payloads: naive sends the FULL per-stage activation
    # from every source rank; sr_ag sends each rank's 1/tp shard
    # (one activation copy total, = the closed form's cross_bytes)
    act_f32 = rows * feat * 4            # one stage's activation
    analytic = {"naive": naive_cost(act_f32, tp, tp).cross_bytes,
                "sr_ag": sr_ag_cost(act_f32, tp, tp).cross_bytes // tp}
    for strat in ("naive", "sr_ag"):
        ok = measured[strat] == analytic[strat]
        emit(f"table_resharding.measured_bytes.{strat}",
             f"{measured[strat]}B",
             f"per-rank cross-stage payload from StableHLO vs "
             f"analytic {analytic[strat]}B — "
             f"{'MATCH' if ok else 'MISMATCH'} "
             f"(pipe={pipe} tp={tp} act={act_f32}B f32)")

    # dp ablation (DESIGN.md §9).  (a) Gradient-sync mode: per-bucket
    # byte accounting of the pacing stage's gradient volume under the
    # DiComm transports — flat psum (one fused all-reduce, replicated
    # optimizer state) vs bucketed ZeRO-1 reduce-scatter + all-gather
    # (dp-sharded optimizer state); the memory rows show what the mode
    # buys on small chips.
    from repro.core.cost_model import evaluate
    from repro.core.dataparallel import (bucketize, domain_cost, partition,
                                         sync_time)
    from repro.core.profiler import layer_param_count
    dp_eff = plan.dp if plan.dp > 1 else 4
    whatif = "" if plan.dp > 1 else f" (what-if dp={dp_eff}; plan has dp=1)"
    pace_stage = max(plan.stages,
                     key=lambda s: s.layers_per_stage *
                     layer_param_count(cfg) * 2 / s.tp)
    per_layer = int(layer_param_count(cfg) * 2 / pace_stage.tp)
    pace = pace_stage.layers_per_stage * per_layer
    buckets = bucketize([(f"layer{i}", per_layer)
                         for i in range(pace_stage.layers_per_stage)],
                        bucket_bytes=25 * 2 ** 20)
    for transport in ("device_rdma", "cpu_tcp"):
        ps = sync_time(buckets, dp_eff, transport, "psum")
        rs = sync_time(buckets, dp_eff, transport, "reduce_scatter")
        emit(f"table_dp.sync.psum.{transport}", f"{ps['total'] * 1e3:.2f}ms",
             f"{ps['messages']} msgs, pacing stage "
             f"{pace / 2 ** 20:.0f}MiB grads{whatif}")
        emit(f"table_dp.sync.rs_ag.{transport}", f"{rs['total'] * 1e3:.2f}ms",
             f"{rs['messages']} msgs over {buckets.num_buckets} buckets"
             f"{whatif}")
    dp_plan = dataclasses.replace(plan, dp=dp_eff) if plan.dp == 1 else plan
    mem_rs = evaluate(dp_plan, cfg, 4096, 4 * 2 ** 20)
    mem_ps = evaluate(dp_plan, cfg, 4096, 4 * 2 ** 20, dp_sync="psum")
    emit("table_dp.mem.rs_ag",
         f"{max(mem_rs.stage_mem_gb):.1f}GB",
         f"worst-stage memory, ZeRO-1 opt state /dp={dp_plan.dp}{whatif}")
    emit("table_dp.mem.psum",
         f"{max(mem_ps.stage_mem_gb):.1f}GB",
         f"worst-stage memory, replicated opt state"
         f" (feasible={mem_ps.feasible} vs rs {mem_rs.feasible}){whatif}")

    # (b) Batch domains: run the Exp-C-1 chip groups as SEPARATE
    # homogeneous replica sets (one A-pipeline + one B-pipeline replica)
    # and split the global batch uniformly vs proportionally to each
    # replica's modeled throughput — the paper's inter-replica load
    # balancing (§4, Table 7).
    batch_seqs = 4 * 2 ** 20 // 4096
    homo = []
    for g in groups:
        t6 = chips.TABLE6.get(g.spec.name)
        hb = heteroauto.homogeneous_baseline(
            g, cfg, 2 * 2 ** 20, 4096, allow_offload=True,
            fixed={"dp": t6["dp"], "tp": t6["tp"],
                   "recompute": t6["recompute"]} if t6 else None)
        homo.append((g, hb))
    if all(hb.plan is not None for _, hb in homo):
        t_mb = [hb.cost.iter_time / hb.plan.microbatches for _, hb in homo]
        rates = [1.0 / t for t in t_mb]
        dom_h = partition(batch_seqs, rates)
        base = batch_seqs // len(homo)
        alloc_u = [base] * len(homo)
        alloc_u[-1] += batch_seqs - base * len(homo)
        dom_u = dataclasses.replace(dom_h, allocations=tuple(alloc_u))
        ch, cu = domain_cost(dom_h, t_mb), domain_cost(dom_u, t_mb)
        emit("table_dp.domain.uniform", f"{cu['iter_time']:.2f}s",
             f"even batch split over {len(homo)} hetero replica sets, "
             f"imbalance={cu['imbalance']:.1%}")
        emit("table_dp.domain.hetero", f"{ch['iter_time']:.2f}s",
             f"throughput-proportional domain {list(dom_h.allocations)}, "
             f"imbalance={ch['imbalance']:.1%} "
             f"(speedup {cu['iter_time'] / ch['iter_time']:.2f}x)")

        # executed vs priced pacing (ISSUE 8 / DESIGN.md §13): the
        # runtime's stacked per-replica program must run exactly the
        # tick count of the pacing (max-allocation) replica — the b the
        # §4.3.2 max-based cost model charges
        from repro.core import heteropp as HP
        for name, alloc in (("acceptance", (5, 3)),
                            ("exp_c1", tuple(dom_h.allocations))):
            S = 2
            stacked = HP.domain_tick_tables("1f1b", S, alloc)
            priced = HP.spmd_tick_tables("1f1b", S, max(alloc))
            ok = stacked.ticks == priced.ticks
            emit(f"table_batch_domain.{name}.executed_ticks",
                 stacked.ticks,
                 f"stacked per-replica program, domain {list(alloc)}, "
                 f"S={S} 1f1b")
            emit(f"table_batch_domain.{name}.priced_ticks", priced.ticks,
                 f"pacing b={max(alloc)} tick count "
                 f"({'MATCH' if ok else 'MISMATCH'})")

    # static plan verifier (ISSUE 10 / DESIGN.md §15): the load-time
    # gate must be cheap enough to run on EVERY from_plan — stamp its
    # wall time on the searched Exp-C-1 plan (full analyzer: collective
    # divergence + schedule safety + resources + kernel lint)
    import time
    from repro.analysis import analyze_plan, split
    # execute_dp=False: a searched Exp-C-1 plan has non-uniform tp AND
    # dp > 1, which the §12 grouped runtime only executes with dp as a
    # cost-model dimension — analyze the surface from_plan can run
    t0 = time.perf_counter()
    diags = analyze_plan(plan, cfg, seq_len=4096, execute_dp=False)
    dt = time.perf_counter() - t0
    a_errs, a_warns = split(diags)
    assert dt < 1.0, f"analyzer took {dt:.3f}s on the Exp-C-1 plan"
    assert not a_errs, [d.format() for d in a_errs]
    emit("table_analysis.wall_time", f"{dt * 1e3:.1f}ms",
         f"full analyze_plan on the searched Exp-C-1 plan "
         f"(S={plan.total_pp} b={plan.microbatches} dp={plan.dp}), "
         f"gate budget <1s")
    emit("table_analysis.diagnostics",
         f"{len(a_errs)}E/{len(a_warns)}W",
         "errors/warnings on the searched plan (a clean search must "
         "produce a clean executable surface)")

    # Fig 12: small-scale e2e DDR vs TCP (8-layer model, TP4 PP2 DP2)
    small = dataclasses.replace(cfg, num_layers=8)
    g2 = [chips.ChipGroup(chips.CHIPS["A"], 8), chips.ChipGroup(chips.CHIPS["C"], 8)]
    st = [StagePlan(g2[0], 4, 1, 4, False), StagePlan(g2[1], 4, 1, 4, False)]
    p2 = ParallelPlan(st, 2, 8)
    ddr = SCH.simulate_plan(p2, small, 4096, schedule=args.schedule).makespan
    tcp = SCH.simulate_plan(p2, small, 4096, schedule=args.schedule,
                            transport="cpu_tcp").makespan
    emit("fig12.small_scale_ddr_speedup", f"{tcp / ddr:.3f}x",
         "DDR vs CPU-mediated TCP, 8-layer model, TP4 PP2 DP2")


if __name__ == "__main__":
    main(sys.argv[1:])
