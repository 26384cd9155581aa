"""Model and kernel work from the configurations' shapes, against
numbers worked out by hand."""
import os

import jax
import pytest

import chipbench_testlib as lib
from chipbench import bench, registry


# mamba2-780m's widths at 24 of its 48 layers (the mamba2_l24 cell that
# PERF.md keeps for a later PR)
MAMBA2_L24 = dict(family="ssm", num_layers=24, d_model=1536, num_heads=1,
                  num_kv_heads=1, d_ff=0, vocab_size=50280, ssm_state=128,
                  ssm_expand=2, ssm_headdim=64, ssm_ngroups=1,
                  dtype="bfloat16")


def _model(name):
    if name == "mamba2_l24":
        return MAMBA2_L24
    return registry.local_model(registry.load_json("configs", name))


def test_h2_tp8_l4_flops_per_token():
    fl = registry.load_module("flops", "model")
    m = _model("h2_tp8_l4")
    # per layer: wq 8192x1024 + wk, wv 8192x128 + wo 1024x8192
    # + 3 x 8192x4608 = 132,120,576; 4 layers + head 8192 x 11,568
    assert fl.matmul_params(m) == 4 * 132_120_576 + 94_765_056
    # 6 x 623,247,360 + 6 x 4 x 4096 x 8 x 128
    assert fl.per_token(m, 4096) == 3_739_484_160 + 100_663_296
    assert round(fl.per_token(m, 4096) / 1e9, 2) == 3.84


def test_mamba2_l24_flops_per_token():
    fl = registry.load_module("flops", "model")
    m = _model("mamba2_l24")
    # per layer: in_proj 1536 x (2x3072 + 2x128 + 48) + out 3072 x 1536
    assert fl.matmul_params(m) == 24 * 14_622_720 + 77_230_080
    # 6 x 428,175,360 + 12 x 24 x 48 x 64 x 128
    assert fl.per_token(m, 2048) == 2_569_052_160 + 113_246_208


def test_kernel_work():
    fa = registry.load_module("flops", "flash_attention")
    flops, nbytes = fa.work(_model("h2_tp8_l4"), 2, 4096)
    assert flops == 2 * 2 * 8 * 4096 * 4096 * 128      # causal half
    assert nbytes == 4 * 2 * 4096 * 8 * 128 * 2        # q, k, v, o bf16
    ssd = registry.load_module("flops", "ssd_scan")
    flops, nbytes = ssd.work(_model("mamba2_l24"), 1, 2048)
    assert flops == 4 * 2048 * 48 * 64 * 128
    assert nbytes == (2048 * 3072 * 2 + 2048 * 48 * 4 + 48 * 4
                      + 2 * 2048 * 128 * 2 + 2048 * 3072 * 4
                      + 48 * 64 * 128 * 4)


def test_attention_work_at_two_head_widths():
    fa = registry.load_module("flops", "flash_attention")
    # latent attention's widths (Moonlight-16B-A3B: 16 heads, q.k at
    # 128 + 64, p.v at 128), batch 2 x 1024
    mla = dict(d_model=2048, num_heads=16, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, dtype="bfloat16")
    flops, nbytes = fa.work(mla, 2, 1024)
    assert flops == 10_737_418_240     # 2 x 16 x 1024^2 x (192 + 128)
    assert nbytes == 41_943_040        # 2 x 1024 x 16 x (384 + 256) x 2 B
    # equal widths give what one head_dim gives
    same = dict(mla, qk_nope_head_dim=64, qk_rope_head_dim=64)
    plain = dict(d_model=2048, num_heads=16, head_dim=128,
                 dtype="bfloat16")
    assert fa.work(same, 2, 1024) == fa.work(plain, 2, 1024) == (
        2 * 2 * 16 * 1024 * 1024 * 128, 4 * 2 * 1024 * 16 * 128 * 2)


def test_other_families_are_told_to_add_a_count():
    fl = registry.load_module("flops", "model")
    with pytest.raises(NotImplementedError, match="flops/moe.py"):
        fl.per_token(dict(MAMBA2_L24, family="moe"), 1024)


def test_a_family_with_its_own_flops_file_gets_its_count(tmp_path):
    root = lib.make_root(str(tmp_path))
    with open(os.path.join(root, "flops", "dense.py"), "w",
              encoding="utf-8") as f:
        f.write("from chipbench import registry\n\n\n"
                "def per_token(m, seq):\n"
                "    return 2 * registry.load_module('flops', 'model')"
                ".per_token(m, seq)\n")
    peaks = registry.peaks("cpu", root)
    default = registry.load_module("flops", "model")
    for cell, factor in (("tiny_dense.t", 2), ("tiny_mamba.t", 1)):
        b = bench.Bench(cell, jax.devices()[:1], root)
        assert b.context(peaks).flops_per_token == factor * \
            default.per_token(b.model, b.traffic["seq"])
    assert registry.flops(dict(family="dense")) is default
    assert registry.flops(dict(family="dense"),
                          root).__name__.endswith("flops_dense")
