"""A whole run of a tiny four-stage pipeline cell (limits of its
own) on four CPU devices comes out correct, and not correct with the
state left unchanged, half of the batch left out, or the exchange
between stages left out, nor with its control (the reference in
float8 in the program's place)."""
import json
import os
import subprocess
import sys

import chipbench_testlib as lib


def test_pipeline_cell_check():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [here, lib.REPO, os.path.join(lib.REPO, "src")]))
    p = subprocess.run([sys.executable,
                        os.path.join(here, "pipeline_faults_main.py")],
                       capture_output=True, text=True, env=env,
                       cwd=lib.REPO, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    got = {k: v["correct"] for k, v in out.items()}
    assert got == {"None": True, "state_unchanged": False,
                   "half_batch": False, "no_exchange": False,
                   "control": False}, out
