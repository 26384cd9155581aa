#!/usr/bin/env python3
"""Records the small trace that test_chipbench_trace.py reduces.

    python chipbench/testdata/record_trace.py <out_dir>

Run on a host with TPU chips (four, for a collective between chips):
two steps of a small shard_map
program (a matmul, a ring ``ppermute``, a matmul) inside the harness's
host spans (``window``, ``data``, ``dispatch``, ``wait``), traced with
``jax.profiler``; the ``.xplane.pb`` it writes is copied to
``<out_dir>/small.xplane.pb``.
"""
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main(out_dir: str) -> int:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"record_trace: needs TPU chips, found {devs}",
              file=sys.stderr)
        return 2
    n = min(4, len(devs))
    mesh = Mesh(np.array(devs[:n]), ("x",))

    def body(a):
        y = jnp.tanh(a @ a.T)
        y = jax.lax.ppermute(y, "x", [(i, (i + 1) % n) for i in range(n)])
        return y @ a

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                              out_specs=P("x")))
    x = jax.device_put(jnp.full((n * 2048, 2048), 0.01, jnp.bfloat16),
                       NamedSharding(mesh, P("x")))
    f(x).block_until_ready()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from chipbench import tracing
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        span = jax.profiler.TraceAnnotation
        with span("window"):
            for _ in range(2):
                with span("data"):
                    time.sleep(0.002)
                with span("dispatch"):
                    y = f(x)
                with span("wait"):
                    y.block_until_ready()
        jax.profiler.stop_trace()
        os.makedirs(out_dir, exist_ok=True)
        shutil.copy(tracing.find_xplane(tmp),
                    os.path.join(out_dir, "small.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
