#!/usr/bin/env python3
"""Where one cell's step spends its device time, by the program's layers.

    python chipbench/scope_report.py --workload <cell> --seed <n> \
        [--seconds 10] [--hlo <file>] [--hlo-only] [--out <file>]

Builds the cell as ``bench.py`` does and, after its check steps, runs
the untraced window (``--seconds``, as ``--trace 0`` does) and the
traced one (``trace_steps`` steps, as ``--trace 1`` does) on the same
state.  The trace is reduced by ``tracing.reduce_xplane`` and joined to
the compiled step's ``op_name`` metadata by ``scopes.op_scopes``.  The
last line of standard output is one JSON object:

* ``step_s``: host seconds per step of each window (the cost of the
  profiler is their ratio);
* ``scope_ms``: device ms per step per chip of each (scope, phase);
* ``layer_ms``: the readings a per-layer metric of each layer would
  give (``attention_bwd_ms``, ``ssd_bwd_ms``, ``optimizer_ms``,
  ``loss_head_ms``, ``data_queue_wait_ms``);
* ``coverage``: the shares of busy device time on instructions of the
  compiled step and under a known scope;
* ``top_ops``: the costliest device ops with their scope and op_name;
* ``loader``: the loader's counters over the traced window, and the
  program's host spans (``data.*``) summed in it.

``--hlo`` writes the compiled step's text without its metadata (two
commits' files are the same program where they are equal);
``--hlo-only`` stops there.  Needs a TPU, as run.py does (exit 2 off
one).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join("runs", "chipbench", "scope_trace")
TOP = 20


def layer_readings(ms, loader_ms):
    from chipbench import scopes
    return {
        "attention_bwd_ms": scopes.layer_ms(ms, "attention_core", ["bwd"]),
        "ssd_bwd_ms": scopes.layer_ms(ms, "ssd_core", ["bwd"]),
        "optimizer_ms": scopes.layer_ms(ms, "optimizer"),
        "loss_head_ms": scopes.layer_ms(ms, "loss_head"),
        "data_queue_wait_ms": loader_ms,
    }


def _counters(loader):
    get = getattr(loader, "counters", None)   # a loader that counts
    return get() if get else None


def report(b, state, loader, seconds: float, hlo_text: str) -> dict:
    """The untraced window, then the traced one, on ``b``'s compiled
    step (``b`` a started ``bench.Bench``)."""
    import jax
    from chipbench import scopes, tracing
    state, n0, s0, _, _, _ = b.window(state, loader, seconds)
    trace_dir = os.path.abspath(TRACE_DIR)
    shutil.rmtree(trace_dir, ignore_errors=True)
    before = _counters(loader)
    jax.profiler.start_trace(trace_dir)
    try:
        state, n1, s1, _, _, _ = b.window(state, loader, 0,
                                          b.workload["trace_steps"])
    finally:
        jax.profiler.stop_trace()
    after = _counters(loader)
    path = tracing.find_xplane(trace_dir)
    trace = tracing.reduce_xplane(path)
    spans = scopes.program_spans(path)
    shutil.rmtree(trace_dir, ignore_errors=True)

    op = scopes.op_scopes(hlo_text)
    ms = scopes.scope_ms(trace, op, n1)
    lo, hi = tracing.window(trace)
    in_window = [s for s in spans if lo <= s[1] and s[1] + s[2] <= hi]
    span_ms = {}
    for name, _, dur in in_window:
        span_ms[name] = span_ms.get(name, 0.0) + dur / 1e6 / n1
    loader_out, wait_ms = None, None
    if before and after:
        loader_out = {k: after[k] - before[k] for k in after}
        wait_ms = 1e3 * loader_out["queue_wait_s"] / n1
    busy = [tracing.busy(trace, d) for d in sorted(trace["devices"])]
    top = []
    for name, sec in tracing.top_ops(trace, TOP):
        inst = name.split(" ")[0]
        top.append([name, sec / n1 * 1e3, *op.get(inst, [None, "?", "?"])])
    return {
        "steps": {"untraced": n0, "traced": n1},
        "step_s": {"untraced": s0 / n0, "traced": s1 / n1},
        "busy_ms": sum(busy) / len(busy) / 1e6 / n1 if busy else None,
        "scope_ms": sorted(([s, p, v] for (s, p), v in ms.items()),
                           key=lambda x: -x[2]),
        "layer_ms": layer_readings(ms, wait_ms),
        "coverage": scopes.coverage(trace, op),
        "top_ops": top,
        "loader": {"counters": loader_out, "span_ms": span_ms,
                   "spans_in_window": len(in_window),
                   "spans": len(spans)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--hlo", help="write the compiled step's text, "
                    "metadata stripped, here")
    ap.add_argument("--hlo-only", action="store_true")
    ap.add_argument("--out", help="also write the report here")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from chipbench import bench, registry, scopes
    want = registry.load_json("workloads", args.workload)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"scope_report: {args.workload} needs {want} TPU chips, "
              f"found {devices}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    b = bench.Bench(args.workload, devices[:want])
    state, loader, _ = b.start(args.seed)
    text = b.mode.compiled.as_text()
    if args.hlo:
        os.makedirs(os.path.dirname(os.path.abspath(args.hlo)),
                    exist_ok=True)
        with open(args.hlo, "w", encoding="utf-8") as f:
            f.write(scopes.strip_metadata(text))
    if args.hlo_only:
        loader.close()
        print(json.dumps({"workload": args.workload, "hlo": args.hlo}))
        return 0
    out = report(b, state, loader, args.seconds, text)
    loader.close()
    out = {"workload": args.workload, "seed": args.seed,
           "device": devices[0].device_kind,
           "setup_s": b.phases, **out}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
