"""Tiny cells for the benchmark's CPU tests: a copy of the benchmark's
directory with small configurations of the same families, traffic
mixes, and workloads copied from the real cells with limits of their
own.  Those limits were set as the real cells' are, from readings at
the tiny size on the CPU (6 seeds of the program, 3 of the control and
of each fault), which are not the chip's: largest sound reading /
limit / smallest control reading:

    tiny_dense.t      loss 3.1e-3 / 1e-2 / 2.7e-2   grad 1.8e-3 / 8e-3 / 2.3e-2
    tiny_mamba.t      loss 9.4e-4 / 2.5e-3 / 6.9e-3 grad 4.4e-3 / 1e-2 / 1.7e-2
    tiny_dense4.pipe  loss 3.2e-3 / 8e-3 / 1.3e-2   grad 3.0e-3 / 8e-3 / 3.9e-2
"""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "chipbench")

TINY = {
    # cell: (real cell it copies, its config, config overrides, traffic,
    #        limits)
    "tiny_dense.t": ("h2_tp8_l4.s4k", "h2_tp8_l4",
                     dict(num_layers=2, d_model=64, num_heads=8,
                          num_kv_heads=4, head_dim=16, d_ff=256,
                          vocab_size=512), {"rows": 2, "seq": 32},
                     {"loss_gap": 1e-2, "gnorm_gap": 3e-2, "grad_gap": 8e-3,
                      "change_gap": 3e-3}),
    "tiny_mamba.t": ("h2_tp8_l4.s4k", "h2_tp8_l4",
                     dict(family="ssm", num_layers=2, d_model=64,
                          num_heads=1, num_kv_heads=1, d_ff=0,
                          vocab_size=256, ssm_state=16, ssm_expand=2,
                          ssm_headdim=16, ssm_ngroups=1, ssm_conv_width=4,
                          ssm_chunk=16, tie_embeddings=True,
                          max_seq_len=64),
                     {"rows": 1, "seq": 32},
                     {"loss_gap": 2.5e-3, "gnorm_gap": 0.05, "grad_gap": 1e-2,
                      "change_gap": 1.2e-2}),
    "tiny_dense4.pipe": ("h2_tp8_l8.pipe4_1f1b", "h2_tp8_l8",
                         dict(num_layers=4, d_model=64, num_heads=8,
                              num_kv_heads=4, head_dim=16, d_ff=256,
                              vocab_size=512), {"rows": 4, "seq": 32},
                         {"loss_gap": 8e-3, "gnorm_gap": 5e-3,
                          "grad_gap": 8e-3, "change_gap": 3e-3}),
}


def _dump(path, obj):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def make_root(dst: str, cpu_peaks: bool = True) -> str:
    """Copy the benchmark to ``dst`` with the tiny cells added; returns
    the copy's benchmark directory (the registry's ``root``)."""
    root = os.path.join(dst, "chipbench")
    shutil.copytree(BENCH_DIR, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"),
                os.path.join(dst, "BENCHMARK.json"))
    if cpu_peaks:
        peaks = _load(os.path.join(root, "peaks.json"))
        peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only")
        _dump(os.path.join(root, "peaks.json"), peaks)
    for cell, (real, config, model, traffic, limits) in TINY.items():
        cfg = _load(os.path.join(root, "configs", config + ".json"))
        name = cell.split(".")[0]
        if model.get("family", cfg["model"]["family"]) == "ssm":
            # Mamba2 (arXiv:2405.21060) in small: its own reference
            cfg = dict(cfg, reference="mamba2", model={
                "dtype": "bfloat16", "norm": "rmsnorm", **model})
            cfg.pop("tensor_parallel_share")
        cfg["model"].update(model, name=name)
        if "tensor_parallel_share" in cfg:
            cfg["tensor_parallel_share"]["tp"] = 2
        _dump(os.path.join(root, "configs", name + ".json"), cfg)
        _dump(os.path.join(root, "traffic", name + ".json"),
              dict(traffic, tokens="uniform"))
        w = _load(os.path.join(root, "workloads", real + ".json"))
        w.update(config=name, traffic=name, backend="auto", trace_steps=2,
                 limits=limits)
        if "pipeline" in w:
            w["pipeline"]["microbatches"] = traffic["rows"]
        _dump(os.path.join(root, "workloads", cell + ".json"), w)
    return root


# -- faults planted in the program, under the timed path ----------------

def _half(tokens):
    """The first half of the rows, or of the positions where there is
    one row (the last axis is the sequence)."""
    rows = tokens.shape[-2]
    if rows >= 2:
        return tokens[..., : rows // 2, :]
    return tokens[..., : tokens.shape[-1] // 2]


class planted:
    """Context manager that breaks the program for one fault:
    ``state_unchanged`` (the optimizer hands back the state it got),
    ``half_batch`` (the loss sees half of the batch, its mean over the
    rest), ``no_exchange`` (the pipeline's stage-to-stage ppermute sends
    nothing)."""

    def __init__(self, fault):
        self.fault, self.undo = fault, []

    def _set(self, obj, name, value):
        self.undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def __enter__(self):
        import jax
        import jax.numpy as jnp
        from repro.core import heteropp as HP
        from repro.models import model as M
        from repro.optim import adamw
        if self.fault == "state_unchanged":
            self._set(adamw, "apply_update",
                      lambda cfg, opt_state, grads, step, params, **kw: (
                          params, opt_state,
                          {"grad_norm": jnp.float32(0),
                           "lr": jnp.float32(0)}))
        elif self.fault == "half_batch":
            loss_fn, pipe_loss = M.loss_fn, HP.make_spmd_pipeline_loss
            self._set(M, "loss_fn", lambda p, cfg, batch, **kw: loss_fn(
                p, cfg, dict(batch, tokens=_half(batch["tokens"])), **kw))

            def half_pipe_loss(*a, **kw):
                f = pipe_loss(*a, **kw)
                return lambda sp, mask, tokens: f(sp, mask, _half(tokens))
            self._set(HP, "make_spmd_pipeline_loss", half_pipe_loss)
        elif self.fault == "no_exchange":
            self._set(jax.lax, "ppermute",
                      lambda x, axis_name, perm: jnp.zeros_like(x))
        elif self.fault is not None:
            raise ValueError(self.fault)
        return self

    def __exit__(self, *exc):
        for obj, name, value in reversed(self.undo):
            setattr(obj, name, value)


def run_cell(root, cell, fault=None, seed=2**31 + 11):
    """One whole run of a tiny cell on the CPU (the harness's look for a
    chip skipped), with ``fault`` planted; returns the result object."""
    import time
    import jax
    from chipbench import bench, registry
    n = registry.load_json("workloads", cell, root)["chips"]
    with planted(fault):
        return bench.run(cell, seed, 0.2, False, jax.devices()[:n],
                         t_start=time.perf_counter(), root=root,
                         log=lambda *a: None)
