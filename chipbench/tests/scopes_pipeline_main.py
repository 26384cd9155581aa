"""Compiles a tiny two-stage pipeline cell on two CPU devices and writes
the compiled step's text to the path given as the one argument.

test_chipbench_scopes.py runs it in a process of its own, because it
needs ``--xla_force_host_platform_device_count=2`` before JAX starts."""
import json
import os
import sys
import tempfile

import jax

import chipbench_testlib as lib
from chipbench import bench, weights


def main(out: str) -> int:
    root = lib.make_root(tempfile.mkdtemp())
    src = os.path.join(root, "workloads", "tiny_dense4.pipe.json")
    with open(src, encoding="utf-8") as f:
        w = json.load(f)
    w["pipeline"]["stages"] = 2
    with open(os.path.join(root, "workloads", "tiny_dense2.pipe.json"), "w",
              encoding="utf-8") as f:
        json.dump(w, f)
    b = bench.Bench("tiny_dense2.pipe", jax.devices()[:2], root)
    b.mode.compile(b.mode.init(weights.seed_key(0)))
    with open(out, "w", encoding="utf-8") as f:
        f.write(b.mode.compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
