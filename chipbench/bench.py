"""The harness: one run of one cell.

Set-up builds the cell's step (``modes/<mode>.py``) once, makes the
weights on the device from the seed, compiles the step ahead, and drives
that same compiled step, through the window's own call and feed, for the
first ``CHECK_STEPS`` steps, keeping what the check compares.  The window
then runs steps for ``--seconds`` (``--trace 0``) or ``trace_steps``
steps under the profiler (``--trace 1``).  Once it has closed, the
device's peak memory is read, the program's state is freed, and the
plain reference (``reftrain``) trains the same weights on the same
batches; ``check.compare`` decides ``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import shutil
import statistics
import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from chipbench import check, registry, reftrain, tracing, weights

CHECK_STEPS = 3
TRACE_DIR = os.path.join("runs", "chipbench", "trace")


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    model: Dict
    workload: Dict
    traffic: Dict
    peaks: Dict
    chips: int
    tokens_per_step: int
    kernel_batch: int
    seq: int
    flops_per_token: float
    root: Optional[str] = None
    setup_s: float = float("nan")
    steps: int = 0
    window_s: float = float("nan")
    data_waits: List[float] = dataclasses.field(default_factory=list)
    trace: Optional[Dict] = None
    memory: Optional[Dict] = None
    values: Dict[str, float] = dataclasses.field(default_factory=dict)

    def flops(self, name: str):
        return registry.load_module("flops", name, self.root)

    def metric(self, name: str) -> Optional[float]:
        return self.values.get(name)


class CompileCounter:
    """Counts JAX's tracing, lowering and compiling events while open."""

    def __init__(self):
        self.events: List[str] = []

    def _on(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/") or \
                event.startswith("/jax/compilation_cache/cache_retrieval"):
            self.events.append(event)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on)


def _norms(tree) -> Dict[str, jnp.ndarray]:
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in weights.flatten(tree).items()}


def check_layout(specs: Dict, abstract_params) -> None:
    """The reference's parameter list has to be the program's tree, leaf
    for leaf, in shape and dtype."""
    prog = {p: (tuple(x.shape), str(x.dtype))
            for p, x in weights.flatten(abstract_params).items()}
    ref = {p: (tuple(s[0]), str(jnp.dtype(s[1]))) for p, s in specs.items()}
    if prog != ref:
        raise ValueError(f"the reference's parameters differ from the "
                         f"program's: only in the program "
                         f"{sorted(set(prog.items()) - set(ref.items()))}; "
                         f"only in the reference "
                         f"{sorted(set(ref.items()) - set(prog.items()))}")


class Bench:
    """One cell's compiled step and reference, reusable across seeds."""

    def __init__(self, cell_name: str, devices, root: Optional[str] = None):
        from repro.models import model as M
        from repro.models.config import ModelConfig
        c = registry.cell(cell_name, root)
        self.name, self.root = cell_name, root
        self.workload, self.config, self.traffic = (
            c["workload"], c["config"], c["traffic"])
        self.model = registry.local_model(self.config)
        self.cfg = ModelConfig(**self.model)
        self.ref = registry.load_module("references",
                                        self.config["reference"], root)
        self.specs = self.ref.param_specs(self.model)
        check_layout(self.specs, M.abstract_params(self.cfg))
        self.devices = list(devices)
        self.opt = self.workload["optimizer"]
        mode = registry.load_module("modes", self.workload["mode"], root)
        self.mode = mode.StepMode(self.cfg, c, self.specs, self.devices,
                                  self.opt)
        self._norms = jax.jit(_norms)
        self._change = jax.jit(lambda master, key: _norms(jax.tree.map(
            lambda a, b: a - b.astype(jnp.float32), master,
            self.mode.initial_params(key))))

    @property
    def stages(self) -> int:
        return self.workload.get("pipeline", {}).get("stages", 1)

    # -- set-up -----------------------------------------------------------
    def start(self, seed: int):
        """Weights from the seed, the step compiled (once per Bench), the
        loader, and the first CHECK_STEPS steps through the window's own
        call and feed.  Returns (state, loader, program readings)."""
        key = weights.seed_key(seed)
        t0 = time.perf_counter()
        state = self.mode.init(key)
        jax.block_until_ready(state)
        self.phases = {"init": time.perf_counter() - t0}
        if self.mode.compiled is None:
            t0 = time.perf_counter()
            self.mode.compile(state)
            self.phases["compile"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        loader = self.mode.loader(self.traffic, self.model["vocab_size"],
                                    seed)
        losses, grad, gnorm = [], None, None
        for t in range(CHECK_STEPS):
            state, met = self.mode.step(state, next(loader))
            losses.append(met["loss"])
            if t == 0:
                grad = self._norms(self.mode.opt_state(state)["m"])
                gnorm = met["grad_norm"]
        change = self._change(self.mode.opt_state(state)["master"], key)
        self.phases["check_steps"] = time.perf_counter() - t0
        # the first moment after one step is (1 - b1) x the clipped
        # gradient; the clip scale follows from the global norm that the
        # optimizer reports, as it computed it
        gnorm = float(gnorm)
        clip = self.opt["grad_clip"]
        scale = min(1.0, clip / (gnorm + 1e-9)) if clip > 0 else 1.0
        unclip = (1 - self.opt["b1"]) * scale
        readings = {"losses": [float(x) for x in losses], "gnorm": gnorm,
                    "grad": {p: float(v) / unclip for p, v in grad.items()},
                    "change": {p: float(v) for p, v in change.items()}}
        return state, loader, readings

    # -- the window -------------------------------------------------------
    def window(self, state, loader, seconds: float, trace_steps: int = 0):
        """Steps for ``seconds`` (or ``trace_steps`` steps), at most one
        step queued behind the one running, ended by block_until_ready.
        Returns (state, steps, elapsed s, data waits, losses, host
        seconds between successive step completions)."""
        span = jax.profiler.TraceAnnotation if trace_steps \
            else (lambda name: contextlib.nullcontext())
        waits, losses, done, prev = [], [], [], None
        with CompileCounter() as cc, span("window"):
            t0 = time.perf_counter()
            while True:
                with span("data"):
                    tw = time.perf_counter()
                    batch = next(loader)
                    waits.append(time.perf_counter() - tw)
                with span("dispatch"):
                    state, met = self.mode.step(state, batch)
                losses.append(met["loss"])
                if prev is not None:
                    with span("wait"):
                        prev.block_until_ready()
                    done.append(time.perf_counter())
                prev = met["loss"]
                if trace_steps:
                    if len(losses) >= trace_steps:
                        break
                elif time.perf_counter() - t0 >= seconds:
                    break
            with span("wait"):
                jax.block_until_ready((state, met))
            elapsed = time.perf_counter() - t0
        if cc.events:
            raise RuntimeError(f"{len(cc.events)} compilations inside the "
                               f"window: {sorted(set(cc.events))}")
        gaps = [b - a for a, b in zip(done, done[1:])]
        return state, len(losses), elapsed, waits, losses, gaps

    def memory(self) -> Dict[str, int]:
        ma = self.mode.compiled.memory_analysis()
        return {"argument": ma.argument_size_in_bytes,
                "output": ma.output_size_in_bytes,
                "temp": ma.temp_size_in_bytes,
                "alias": ma.alias_size_in_bytes}

    def reference(self, seed: int, quant: str = "",
                  fault: Optional[str] = None) -> Dict:
        with jax.default_matmul_precision("highest"):
            return reftrain.run(self.ref, self.model, self.devices,
                                self.traffic, seed, self.opt, CHECK_STEPS,
                                quant=quant, stages=self.stages, fault=fault)

    def context(self, peaks: Dict) -> Context:
        t = self.traffic
        kb = self.mode.tokens_shape[-2]
        return Context(
            model=self.model, workload=self.workload, traffic=t,
            peaks=peaks, chips=len(self.devices),
            tokens_per_step=self.mode.tokens_per_step, kernel_batch=kb,
            seq=t["seq"], root=self.root,
            flops_per_token=registry.flops(self.model, self.root).per_token(
                self.model, t["seq"]))


def free(tree) -> None:
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()


def selected(benchmark: Dict, key: str, cell: str) -> List[Dict]:
    return [m for m in benchmark[key]
            if "workloads" not in m or cell in m["workloads"]]


def load_benchmark(root: Optional[str] = None) -> Dict:
    path = os.path.join(os.path.dirname(root or registry.ROOT),
                        "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def run(cell: str, seed: int, seconds: float, trace: bool, devices, *,
        t_start: float, root: Optional[str] = None,
        benchmark: Optional[Dict] = None, log=None) -> Dict:
    """One run; returns the result object (see run.py)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    benchmark = benchmark or load_benchmark(root)
    dev0 = devices[0]
    peaks = registry.peaks(dev0.device_kind, root)
    t_build = time.perf_counter()
    b = Bench(cell, devices, root)
    ctx = b.context(peaks)
    t_build = time.perf_counter() - t_build
    state, loader, prog = b.start(seed)
    jax.block_until_ready(state)
    ctx.setup_s = time.perf_counter() - t_start
    phases = {"build": t_build, **b.phases}
    phases["process"] = ctx.setup_s - sum(phases.values())
    log(f"setup {ctx.setup_s:.3f} s (" + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items()) + f"); check steps' "
        f"losses {prog['losses']}")

    trace_steps = b.workload["trace_steps"] if trace else 0
    trace_dir = os.path.abspath(TRACE_DIR)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    try:
        (state, ctx.steps, ctx.window_s, ctx.data_waits, losses,
         gaps) = b.window(state, loader, seconds, trace_steps)
    finally:
        if trace:
            jax.profiler.stop_trace()
    loader.close()
    failed = sum(not math.isfinite(float(x)) for x in losses)
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    ctx.memory = b.memory()
    if gaps:
        log(f"host s between step completions: min {min(gaps):.4f} "
            f"median {statistics.median(gaps):.4f} max {max(gaps):.4f}")
    log(f"window {ctx.steps} steps in {ctx.window_s:.4f} s; "
        f"peak_bytes_in_use {peak_bytes} (allocator) vs "
        f"memory_analysis {ctx.memory}")
    free(state)

    result: Dict = {}
    metrics: Dict[str, Dict] = {}
    if trace:
        t_red = time.perf_counter()
        ctx.trace = tracing.reduce_xplane(tracing.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t_red:.1f} s")
        specs = selected(benchmark, "per_layer", cell)
    else:
        specs = selected(benchmark, "end_to_end", cell)
    for spec in specs:
        mod = registry.load_module("metrics", spec["name"], root)
        v = mod.read(ctx)
        if v is not None:
            ctx.values[spec["name"]] = v
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    if trace:
        lo, hi = tracing.window(ctx.trace)
        busy = [tracing.busy(ctx.trace, d) for d in sorted(ctx.trace["devices"])]
        device["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
        device["window_s"] = (hi - lo) / 1e9
        first = sorted(ctx.trace["devices"])[0] if busy else None
        result["breakdown"] = {
            "device_ops": [list(x) for x in tracing.top_ops(ctx.trace)],
            "idle_gaps": [list(x) for x in
                          tracing.idle_gaps(ctx.trace, first)[:10]]
            if first is not None else []}

    t_ref = time.perf_counter()
    ref = b.reference(seed)
    log(f"reference {time.perf_counter() - t_ref:.1f} s; losses "
        f"{ref['losses']}")
    numbers = check.compare(prog, ref, b.workload["limits"])
    correct = failed == 0 and check.passed(numbers)
    out = {"correct": correct, "attempted": ctx.steps, "failed": failed,
           "metrics": metrics, "device": device, **result,
           "check": numbers}
    for name, n in numbers.items():
        log(f"{name} {n['value']!r} limit {n['limit']!r}")
    return out
