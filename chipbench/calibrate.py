#!/usr/bin/env python3
"""Readings from which a cell's check limits are set (run on the chip).

    python chipbench/calibrate.py --workload <cell> --seeds 1-12 \
        --control-seeds 1-3 [--no-faults] --out <file.jsonl>

For each seed, in one process and with the step compiled once: the
program's readings (its first steps through the window's own call and
feed, against the float32 reference) give the lower reading of each
number.  For each of ``--control-seeds``: the control, the
reference in float8 put in the program's place (e4m3 forward, e5m2
backward, ``references/_common.py``), and, unless ``--no-faults``, the
faults the cell can have, planted in the reference put in the program's
place (half of the batch left out; in a pipeline cell, the exchange
between stages left out).  A state left unchanged reads 1 by the measure of
``grad_gap`` and ``change_gap`` and needs no run.  One JSON line per
reading; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeds_arg(text: str):
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b) + 1) if b else [int(a)])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, default=[],
                    help="seeds of the program's readings")
    ap.add_argument("--control-seeds", type=seeds_arg, default=[],
                    help="seeds of the control's and the faults' readings")
    ap.add_argument("--faults", action=argparse.BooleanOptionalAction,
                    default=True, help="read the faults beside the control")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from chipbench import bench, check, registry
    from repro.launch.compile_cache import enable_compile_cache
    want = registry.load_json("workloads", args.workload)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"calibrate: needs {want} TPU chips, found {devices}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    b = bench.Bench(args.workload, devices[:want])
    faults = (["half_batch"] + (["no_exchange"] if b.stages > 1 else [])
              if args.faults else [])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a", encoding="utf-8") as f:
        def emit(seed, kind, r, extra=None):
            row = {"cell": args.workload, "seed": seed, "kind": kind,
                   "readings": r, **(extra or {})}
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)

        for seed in args.seeds:
            t = time.perf_counter()
            state, loader, prog = b.start(seed)
            loader.close()
            bench.free(state)
            ref = b.reference(seed)
            emit(seed, "program", check.readings(prog, ref),
                 {"program_losses": prog["losses"],
                  "reference_losses": ref["losses"],
                  "gnorms": [prog["gnorm"], ref["gnorm"]],
                  "grad_leaves": check.leaf_gaps(prog, ref, "grad"),
                  "change_leaves": check.leaf_gaps(prog, ref, "change"),
                  "seconds": time.perf_counter() - t})
        for seed in args.control_seeds:
            ref = b.reference(seed)
            ctrl = b.reference(seed, quant="fp8")
            emit(seed, "control_fp8", check.readings(ctrl, ref),
                 {"control_losses": ctrl["losses"],
                  "reference_losses": ref["losses"]})
            for fault in faults:
                emit(seed, f"fault_{fault}",
                     check.readings(b.reference(seed, fault=fault), ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
