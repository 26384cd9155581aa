"""The Pallas kernels compile for a TPU v5e chip.

Each case lowers a kernel with ``interpret=False`` for one device of a
described (not attached) ``v5e:2x2`` topology and compiles it with the
TPU compiler that ships with libtpu.  Nothing runs: this catches what the
chip's compiler refuses (unaligned blocks, primitives Mosaic cannot
lower) without a chip.  Interpret-mode tests cannot see those refusals.

The topology is described inside a module fixture, never at import:
only one process at a time may load libtpu, and pytest-xdist workers
must all collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd_scan as ssd


@pytest.fixture(scope="module")
def chip():
    """One described v5e device; the persistent compile cache is off
    while the module's compiles run (entries written for a described
    chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("heads,head_dim", [(16, 64), (8, 128)],
                         ids=["qwen1p5_0p5b_hd64", "hd128"])
def test_flash_attention_compiles(chip, heads, head_dim):
    B, S = 2, 1024
    qkv = ((B, S, heads, head_dim), jnp.bfloat16)
    txt = _compiled_text(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           interpret=False),
        chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in txt


def test_flash_decode_batch4_gqa_compiles(chip):
    B, KV, G, hd, S = 4, 2, 8, 128, 1024
    txt = _compiled_text(
        lambda q, k, v, bias: fd.flash_decode(q, k, v, bias,
                                              interpret=False),
        chip, ((B, KV, G, hd), jnp.bfloat16), ((B, KV, S, hd), jnp.bfloat16),
        ((B, KV, S, hd), jnp.bfloat16), ((B, 1, S), jnp.float32))
    assert "tpu_custom_call" in txt


def test_ssd_scan_mamba2_780m_compiles(chip):
    b, S, h, p, g, n = 1, 512, 48, 64, 1, 128
    txt = _compiled_text(
        lambda x, dt, A, Bm, Cm: ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=128,
                                              interpret=False),
        chip, ((b, S, h, p), jnp.bfloat16), ((b, S, h), jnp.float32),
        ((h,), jnp.float32), ((b, S, g, n), jnp.bfloat16),
        ((b, S, g, n), jnp.bfloat16))
    assert "tpu_custom_call" in txt


def test_rmsnorm_ragged_rows_compiles(chip):
    rows, d = 300, 1024          # 300 rows: not a multiple of 8
    txt = _compiled_text(
        lambda x, s: rn.rmsnorm(x, s, interpret=False),
        chip, ((3, rows // 3, d), jnp.bfloat16), ((d,), jnp.float32))
    assert "tpu_custom_call" in txt
