"""Every cell, configuration, traffic mix, metric and work function is a
file the harness finds by name, and BENCHMARK.json names only those."""
import json
import os
import subprocess
import sys

import pytest

import chipbench_testlib as lib
from chipbench import registry

with open(os.path.join(lib.REPO, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCH = json.load(f)


def test_benchmark_names_only_files_that_exist():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(lib.REPO, c["file"]))
        cfg = registry.load_json("configs", c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert c["source"] == cfg["source"]
        assert c["reduced"] == cfg["reduced"]
    for w in BENCH["workloads"]:
        cell = registry.cell(w["name"])
        assert w["config"] in configs
        assert cell["workload"]["config"] == w["config"]
        assert cell["workload"]["traffic"] == w["traffic"]
        assert cell["workload"]["chips"] == w["chips"]
        assert cell["workload"]["why"] == w["why"]
        limits = cell["workload"]["limits"]
        assert limits and set(limits) <= {
            "loss_gap", "gnorm_gap", "grad_gap", "change_gap"}
        assert all(v > 0 for v in limits.values())
    assert sorted(registry.names("workloads", ".json")) == sorted(
        w["name"] for w in BENCH["workloads"])
    assert sorted(registry.names("configs", ".json")) == sorted(configs)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader_that_agrees(kind):
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH[kind]:
        mod = registry.load_module("metrics", m["name"])
        assert (mod.UNIT, mod.BETTER, mod.SOURCE) == (
            m["unit"], m["better"], m["source"])
        if kind == "per_layer":
            assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])
            assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m.get("workloads", cells)) <= cells
    names = {m["name"] for k in ("end_to_end", "per_layer")
             for m in BENCH[k]}
    assert names <= set(registry.names("metrics", ".py"))


def test_every_config_builds_and_every_mode_and_reference_loads():
    from repro.models import model as M
    from repro.models.config import ModelConfig
    from chipbench import bench
    for name in registry.names("configs", ".json"):
        cfg = registry.load_json("configs", name)
        model = registry.local_model(cfg)
        ref = registry.load_module("references", cfg["reference"])
        bench.check_layout(ref.param_specs(model),
                           M.abstract_params(ModelConfig(**model)))
    for w in BENCH["workloads"]:
        registry.load_module("modes", registry.cell(
            w["name"])["workload"]["mode"])


def test_a_dropped_in_workload_is_found_without_a_code_edit(tmp_path):
    root = lib.make_root(str(tmp_path))
    w = registry.load_json("workloads", "h2_tp8_l4.s4k", root)
    w["why"] = "a cell added as a file"
    with open(os.path.join(root, "workloads", "h2_tp8_l4.new.json"), "w",
              encoding="utf-8") as f:
        json.dump(w, f)
    assert "h2_tp8_l4.new" in registry.names("workloads", ".json", root)
    cell = registry.cell("h2_tp8_l4.new", root)
    assert cell["workload"]["why"] == "a cell added as a file"
    assert cell["traffic"]["rows"] == 2
    with pytest.raises(KeyError):
        registry.cell("h2_tp8_l4.new")


def test_an_unknown_device_kind_has_no_peaks():
    assert registry.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks for device kind"):
        registry.peaks("TPU v9 imaginary")


def test_tensor_parallel_share_divides_the_named_keys():
    m = registry.local_model(registry.load_json("configs", "h2_tp8_l4"))
    assert (m["num_heads"], m["num_kv_heads"], m["d_ff"],
            m["vocab_size"], m["d_model"], m["head_dim"]) == (
        8, 1, 4608, 11568, 8192, 128)


def test_run_refuses_off_the_chip_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(lib.REPO, "chipbench", "run.py"),
         "--workload", "h2_tp8_l4.s4k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=lib.REPO, timeout=120)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == ""
