"""The launch entry points run in-process, as ``chip_smoke.py`` drives them.

``train.main(argv)`` on the GSPMD path guards the mesh the sharding rules
need: ``jax.make_mesh`` builds Explicit axes by default, and
``with_sharding_constraint`` refuses those at the first embedding gather.
"""
import math
import os

import jax
import pytest

from repro.launch import compile_cache, serve, train

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_restored():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_gspmd_train_main_in_process(tmp_path, cache_dir_restored):
    run = train.main(["--arch", "qwen1p5_0p5b", "--smoke", "--steps", "2",
                      "--batch", "2", "--seq", "32", "--log-every", "1",
                      "--run-dir", str(tmp_path)])
    assert run.steps == [1, 2]
    assert all(math.isfinite(x) for x in run.losses), run.losses
    assert run.compile_s is not None and run.compile_s > 0
    assert int(run.state.step) == 2
    # the state is donated: its buffers alias the step's outputs
    assert "input_output_alias" in run.compiled.as_text()
    assert (tmp_path / "metrics.jsonl").exists()


def test_serve_main_in_process(tmp_path, cache_dir_restored):
    res = serve.main(["--arch", "qwen1p5_0p5b", "--smoke", "--batch", "2",
                      "--prompt-len", "16", "--gen", "4",
                      "--run-dir", str(tmp_path)])
    assert res.tokens.shape == (2, 4)
    assert res.decode_latency_s["count"] == 3
    assert res.decode_compile_s > 0


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_keeps_env_setting(monkeypatch, tmp_path,
                                         cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
