"""Runs the tiny pipeline cell whole, sound and with each fault that it
can have planted, on four CPU devices, and its control (the reference
in float8 in the program's place); prints {case: correct} as JSON.
(Started in a process of its own by test_chipbench_faults_pipeline.py:
it needs ``--xla_force_host_platform_device_count=4`` before JAX
starts.)"""
import json
import sys
import tempfile

import chipbench_testlib as lib


def control(root, seed=2**31 + 5):
    import jax
    from chipbench import bench, check
    b = bench.Bench("tiny_dense4.pipe", jax.devices()[:4], root)
    state, loader, _ = b.start(seed)
    loader.close()
    bench.free(state)
    ref = b.reference(seed)
    numbers = check.compare(b.reference(seed, quant="fp8"), ref,
                            b.workload["limits"])
    return {"correct": check.passed(numbers), "check": numbers}


def main():
    with tempfile.TemporaryDirectory() as tmp:
        root = lib.make_root(tmp)
        out = {}
        for fault in (None, "state_unchanged", "half_batch", "no_exchange"):
            r = lib.run_cell(root, "tiny_dense4.pipe", fault)
            out[str(fault)] = {"correct": r["correct"], "check": r["check"]}
        out["control"] = control(root)
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
