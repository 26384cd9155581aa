#!/usr/bin/env python3
"""Records the scoped trace that test_chipbench_scopes.py reduces.

    python chipbench/testdata/record_scoped_trace.py <out_dir>

Run on a host with a TPU chip: two steps of the gradient of a small
program with two of the program's named layers (a matmul under
``mlp``, a sum of squares under ``loss_head``), fed by the program's
``data.pipeline`` loader (its ``data.*`` spans, one of them from the
loader's thread), inside the harness's host spans, traced with
``jax.profiler``.  Writes ``<out_dir>/scoped.xplane.pb`` and the
compiled program's text, without its stack-frame tables, to
``<out_dir>/scoped.hlo.txt``.
"""
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import scopes, tracing  # noqa: E402
from repro.data.pipeline import DataLoader  # noqa: E402
from repro.obs import scopes as names  # noqa: E402

N = 1024


class Rows:
    """``next_batch()`` source of constant rows."""

    def next_batch(self):
        return {"x": np.full((N, N), 0.01, np.float32)}


def loss(w, x):
    with jax.named_scope(names.MLP):
        h = jnp.tanh(x.astype(jnp.bfloat16) @ w)
    with jax.named_scope(names.LOSS_HEAD):
        return jnp.sum(jnp.square(h.astype(jnp.float32)))


def main(out_dir: str) -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record_scoped_trace: needs a TPU chip, found {dev}",
              file=sys.stderr)
        return 2
    # source files in the op metadata by their base names: the recorded
    # files name no directory of the host that made them
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    w = jnp.full((N, N), 0.02, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((N, N), jnp.float32)
    step = jax.jit(jax.grad(loss)).lower(w, x).compile()
    step(w, jnp.zeros((N, N), jnp.float32)).block_until_ready()
    span = jax.profiler.TraceAnnotation
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with span("window"):
            loader = DataLoader(Rows(), jax.sharding.SingleDeviceSharding(
                dev))
            for _ in range(2):
                with span("data"):
                    batch = next(loader)
                with span("dispatch"):
                    g = step(w, batch["x"])
                with span("wait"):
                    g.block_until_ready()
            loader.close()
            time.sleep(0.01)
        jax.profiler.stop_trace()
        os.makedirs(out_dir, exist_ok=True)
        shutil.copy(tracing.find_xplane(tmp),
                    os.path.join(out_dir, "scoped.xplane.pb"))
    with open(os.path.join(out_dir, "scoped.hlo.txt"), "w",
              encoding="utf-8") as f:
        f.write(scopes.strip_tables(step.as_text()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
