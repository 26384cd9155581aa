"""The plain references compute what the program computes: at float32
and small sizes on the CPU, the same loss and the same gradient of every
parameter as the program's ``models.model.loss_fn`` (jnp path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import registry, reftrain, traffic, weights
from repro.models import model as M
from repro.models.config import ModelConfig

MODELS = {
    "dense": dict(name="t", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
                  vocab_size=128, norm="rmsnorm", mlp="swiglu",
                  rope_theta=1e6, max_seq_len=64, dtype="float32"),
    "mamba2": dict(name="t", family="ssm", num_layers=2, d_model=64,
                   num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=128,
                   ssm_state=16, ssm_expand=2, ssm_headdim=16,
                   ssm_ngroups=1, ssm_conv_width=4, ssm_chunk=16,
                   norm="rmsnorm", tie_embeddings=True, max_seq_len=64,
                   dtype="float32"),
}


@pytest.mark.parametrize("ref_name", sorted(MODELS))
def test_reference_matches_the_program_at_float32(ref_name):
    model = MODELS[ref_name]
    ref = registry.load_module("references", ref_name)
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
        ref_loss, ref_grads = r.grads(tokens, None)
    np.testing.assert_allclose(float(ref_loss), float(loss), rtol=1e-5)
    flat = weights.flatten(grads)
    for (path, layer), g in ref_grads.items():
        want = flat[path] if layer is None else flat[path][layer]
        scale = float(jnp.max(jnp.abs(want))) + 1e-12
        err = float(jnp.max(jnp.abs(g - want))) / scale
        assert err < 1e-4, (path, layer, err)
