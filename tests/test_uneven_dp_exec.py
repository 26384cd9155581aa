"""Non-uniform batch domains execute on the SPMD runtime (ISSUE 8
tentpole — DESIGN.md §13): the 8-device e2e helper, plus in-process
coverage of the per-replica tick programs on the real process devices."""
import dataclasses
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + ":" + \
        env.get("PYTHONPATH", "")
    return env


@pytest.mark.e2e
def test_spmd_uneven_dp_pipeline_subprocess():
    """Uneven domain (5, 3) on 8 virtual devices: loss/grads match the
    dp=1 reference, pad slots are bit-inert, both grad-sync modes land
    on bit-identical params, executed tick count equals the priced
    pacing term, and launch/train.py --plan drives the whole path."""
    script = os.path.join(ROOT, "tests", "helpers",
                          "run_spmd_uneven_dp_pipeline.py")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=600, env=_env(), cwd=ROOT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "UNEVEN_DP_OK" in r.stdout


@pytest.mark.skipif(
    len(__import__("jax").devices()) < 8,
    reason="needs ≥8 devices (CI runs an 8-device job)")
def test_spmd_uneven_dp_pipeline_in_process():
    """The uneven-domain path on the REAL process devices (exercised by
    the 8-virtual-device CI job; skipped on a 1-device laptop run):
    dp=2 with allocations (3, 1) matches the monolithic mean over the
    same 4 microbatches."""
    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.core import heteropp as HP
    from repro.launch.mesh import auto_mesh
    from repro.models import model as M

    cfg = dataclasses.replace(get_smoke_config("granite_8b"),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 16), 0,
                                cfg.vocab_size)
    mesh = auto_mesh((2, 2, 2), ("dp", "pipe", "tp"))
    spec = HP.PipelineSpec(2, (1, 1), microbatches=3, tensor_parallel=2,
                           data_parallel=2, batch_domain=(3, 1))
    assert spec.total_microbatches == 4
    sp, mask = HP.split_stage_params(params, cfg, spec)
    loss = float(HP.make_spmd_pipeline_loss(cfg, spec, mesh)(
        sp, mask, tokens))
    refs = [float(M.loss_fn(params, cfg, {"tokens": tokens[i]},
                            remat=False)[0]) for i in range(4)]
    ref = float(np.mean(refs))
    assert abs(loss - ref) / max(abs(ref), 1e-9) < 2e-3, (loss, ref)


def test_uneven_domain_token_layout_errors():
    """A token batch matching NEITHER the tight nor the padded layout is
    refused with a clear error naming both counts."""
    import jax.numpy as jnp
    from repro.core import heteropp as HP

    spec = HP.PipelineSpec(2, (1, 1), microbatches=3, data_parallel=2,
                           batch_domain=(3, 1))
    with pytest.raises(ValueError, match="tight replica-major"):
        HP._prepare_domain_tokens(spec, jnp.zeros((5, 2, 8), jnp.int32))
    # tight (4) packs to padded (6); padded passes through
    assert HP._prepare_domain_tokens(
        spec, jnp.zeros((4, 2, 8), jnp.int32)).shape[0] == 6
    assert HP._prepare_domain_tokens(
        spec, jnp.zeros((6, 2, 8), jnp.int32)).shape[0] == 6
