"""The data loader's counters (``DataLoader.counters``) and its profiler
spans: a slow source shows as consumer queue wait, a fast one does not,
and ``close`` still ends the worker."""
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import DataLoader

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Source:
    """``next_batch()`` of a small constant batch, ``delay`` s each."""

    def __init__(self, delay=0.0):
        self.delay, self.made = delay, 0

    def next_batch(self):
        time.sleep(self.delay)
        self.made += 1
        return {"tokens": np.full((2, 8), self.made, np.int32)}


def _drive(source, n, pause=0.0):
    loader = DataLoader(source, prefetch=2)
    try:
        for i in range(n):
            batch = next(loader)
            assert int(batch["tokens"][0, 0]) == i + 1
            time.sleep(pause)
        return loader.counters()
    finally:
        loader.close()


def test_slow_source_shows_as_queue_wait():
    jnp.asarray(0).block_until_ready()    # the backend up before
    c = _drive(Source(delay=0.03), 6)
    assert c["batches"] == 6
    # the consumer outruns a source of 30 ms a batch: it waits for
    # every batch but those prefetched while it was busy
    assert c["queue_wait_s"] > 0.5 * (6 - 2) * 0.03
    assert c["produce_s"] >= 6 * 0.03 * 0.9
    assert c["put_s"] > 0


def test_fast_source_leaves_no_queue_wait():
    c = _drive(Source(), 6, pause=0.02)
    assert c["batches"] == 6
    # a prefetched batch is always there: the wait is a small share of
    # the consumer's own time
    assert c["queue_wait_s"] < 0.25 * 6 * 0.02


def test_counters_start_at_zero_and_close_ends_the_worker():
    loader = DataLoader(Source(), prefetch=2)
    assert loader.counters()["batches"] == 0
    next(loader)
    loader.close()
    loader._thread.join(timeout=5)
    assert not loader._thread.is_alive()
    assert loader.counters()["batches"] == 1


@pytest.mark.parametrize("code", [
    # the scope names import without jax, as all of repro.obs but runtime
    "import sys; sys.modules['jax'] = None\n"
    "from repro.obs import scopes\n"
    "assert len(set(scopes.ALL)) == len(scopes.ALL) == 11\n"
    "assert all(s.startswith('data.') for s in scopes.DATA_SPANS)\n"
    "print('OK')\n",
    # the spans are harmless with a profiler running (and without)
    "import tempfile, jax, numpy as np\n"
    "from repro.data.pipeline import DataLoader\n"
    "class S:\n"
    "    def next_batch(self): return {'x': np.zeros(4, np.float32)}\n"
    "d = tempfile.mkdtemp()\n"
    "jax.profiler.start_trace(d)\n"
    "l = DataLoader(S()); [next(l) for _ in range(3)]\n"
    "jax.profiler.stop_trace(); l.close()\n"
    "assert l.counters()['batches'] == 3\n"
    "print('OK')\n",
], ids=["scopes_without_jax", "spans_under_profiler"])
def test_in_a_fresh_process(code):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env, cwd=ROOT)
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr
