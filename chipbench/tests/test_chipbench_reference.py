"""The plain references compute what the program computes: at float32
and small sizes on the CPU, the same loss and the same gradient of every
parameter as the program's ``models.model.loss_fn`` (jnp path).  Each
reference module carries its own small model (``CPU_MODEL``), so a
reference added as a file is tested here with no edit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import registry, reftrain, traffic, weights
from repro.models import model as M
from repro.models.config import ModelConfig


@pytest.mark.parametrize("ref_name", registry.names("references", ".py"))
def test_reference_matches_the_program_at_float32(ref_name):
    ref = registry.load_module("references", ref_name)
    model = ref.CPU_MODEL
    specs = ref.param_specs(model)
    key = weights.seed_key(7)
    tokens = traffic.batch_tokens({"rows": 2, "seq": 32, "tokens": "uniform"},
                                  model["vocab_size"], 7, 0)
    cfg = ModelConfig(**model)
    params = weights.nest(weights.generate(specs, key))
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, {"tokens": jnp.asarray(tokens)},
                                remat=False, backend="einsum"),
            has_aux=True)(params)
        r = reftrain.Reference(ref, model, jax.devices()[:1])
        r.init(key)
        ref_loss, ref_grads, _ = r.grads(tokens, None)
    np.testing.assert_allclose(float(ref_loss), float(loss), rtol=1e-5)
    flat = weights.flatten(grads)
    for (path, layer), g in ref_grads.items():
        want = flat[path] if layer is None else flat[path][layer]
        scale = float(jnp.max(jnp.abs(want))) + 1e-12
        err = float(jnp.max(jnp.abs(g - want))) / scale
        assert err < 1e-4, (path, layer, err)
