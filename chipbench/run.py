#!/usr/bin/env python3
"""On-chip benchmark of the trainer: one run of one cell.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is ``chipbench/workloads/<cell>.json``.  ``--trace 0`` prints
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from
a profiler trace of ``trace_steps`` steps (BENCHMARK.json says which
metric belongs to which cell).  Both compare the first steps with the
plain reference (``correct``).  The last line of standard output is one
JSON object; the numbers compared, each beside its limit, are the last
lines of standard error.  Off a TPU, or on fewer chips than the cell
asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import registry
    want = registry.load_json("workloads", args.workload)["chips"]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, but JAX found platform "
              f"{devices[0].platform!r} ({devices[0].device_kind})",
              file=sys.stderr)
        return 2
    if len(devices) < want:
        print(f"chipbench: {args.workload} needs {want} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of a cell, however quick to compile, goes to the
    # persistent cache, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import bench
    out = bench.run(args.workload, args.seed, args.seconds,
                    bool(args.trace), devices[:want], t_start=T_START)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
