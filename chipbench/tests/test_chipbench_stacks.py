"""A reference whose layers are of more than one kind, with a loss term
of each sequence and leaves that no gradient trains, is taken by the
registry and by ``reftrain`` with new files only: the toy of
``toy_moe_ref.py``, dropped into a copy of the benchmark, gives the
loss, the gradient of every (path, layer) and the state after two
steps that the whole toy model gives, written as one function under
``jax.grad``, with its AdamW and its update rule applied by hand."""
import math
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_testlib as lib
from chipbench import registry, reftrain, traffic, weights
from chipbench.references._common import Ops

TRAFFIC = {"rows": 3, "seq": 12, "tokens": "uniform"}
OPT = {"lr": 0.01, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "grad_clip": 1.0, "warmup_steps": 1, "total_steps": 10,
       "min_lr_ratio": 0.1}
SEED = 2**31 + 17


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root = lib.make_root(str(tmp_path_factory.mktemp("bench")))
    shutil.copy(os.path.join(os.path.dirname(__file__), "toy_moe_ref.py"),
                os.path.join(root, "references", "toy_moe.py"))
    return registry.load_module("references", "toy_moe", root)


def whole_model(toy, m, params, tokens):
    """(loss, {(stack, index): stats}) of the whole batch in one pass:
    mean token cross entropy plus the layers' loss terms (each already
    a mean over the batch's sequences)."""
    ops = Ops()
    x = params[toy.EMBED_PATH][tokens]
    terms, stats = 0.0, {}
    for prefix, count in toy.stacks(m):
        for j in range(count):
            p = {k[len(prefix):]: v[j] for k, v in params.items()
                 if k.startswith(prefix)}
            out = toy.block(m, p, x, ops, prefix)
            x, extras = out if isinstance(out, tuple) else (out, {})
            terms = terms + extras.get("loss", 0.0)
            if "stats" in extras:
                stats[(prefix, j)] = extras["stats"]
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    hp = {k: params[k] for k in toy.HEAD_PATHS}
    return toy.head_loss_sum(m, hp, x, tokens, ops) / n + terms, stats


def _reference(toy):
    r = reftrain.Reference(toy, toy.MODEL, jax.devices()[:1])
    r.init(weights.seed_key(SEED))
    return r


def _params(toy):
    return weights.generate(toy.param_specs(toy.MODEL),
                            weights.seed_key(SEED))


def _tokens(toy, step):
    return traffic.batch_tokens(TRAFFIC, toy.MODEL["vocab_size"], SEED,
                                step)


def _layer(v, j):
    return v if j is None else v[j]


def test_stacks_loss_terms_and_gradients_match_the_whole_model(toy):
    m = toy.MODEL
    tokens = _tokens(toy, 0)
    with jax.default_matmul_precision("highest"):
        r = _reference(toy)
        loss, grads, stats = r.grads(tokens, None)
        (want, want_stats), want_grads = jax.value_and_grad(
            lambda p: whole_model(toy, m, p, jnp.asarray(tokens)),
            has_aux=True)(_params(toy))
    assert [l for l, _ in r.stacks] == [toy.DENSE, toy.MOE]
    assert r.L == 3 and r.layers == [(toy.DENSE, 0), (toy.MOE, 0),
                                     (toy.MOE, 1)]
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    # every (path, layer), the dense layer's and the experts' alike
    assert set(grads) == set(r.master) and len(grads) == 3 + 4 + 2 * 6
    for (path, j), g in grads.items():
        w = _layer(want_grads[path], j)
        scale = float(jnp.max(jnp.abs(w)))
        if path.endswith("router/bias"):
            assert scale == 0.0       # it only picks: no gradient
        else:
            assert scale > 0, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5 * scale, err_msg=str(path))
    # the stats of each expert layer, summed over the sequences
    assert sorted(stats) == [1, 2]
    for l, s in stats.items():
        want_load = want_stats[r.layers[l]]["load"]
        np.testing.assert_array_equal(np.asarray(s["load"]),
                                      np.asarray(want_load))
        assert float(jnp.sum(want_load)) == (
            TRAFFIC["rows"] * TRAFFIC["seq"] * m["top_k"])


def _adamw(master, mom, vel, g, scale, t):
    g = g * scale
    b1, b2 = OPT["b1"], OPT["b2"]
    mom = b1 * mom + (1 - b1) * g
    vel = b2 * vel + (1 - b2) * g * g
    mhat, vhat = mom / (1 - b1 ** (t + 1)), vel / (1 - b2 ** (t + 1))
    step = mhat / (jnp.sqrt(vhat) + OPT["eps"]) + OPT["weight_decay"] * master
    return master - reftrain.lr_at(OPT, t) * step, mom, vel


def test_two_steps_with_the_update_rule_match_the_whole_model(toy):
    m = toy.MODEL
    bias = toy.MOE + "router/bias"
    with jax.default_matmul_precision("highest"):
        r = _reference(toy)
        out = r.train(TRAFFIC, SEED, OPT, 2)
        params = _params(toy)
        mom = {k: jnp.zeros_like(v) for k, v in params.items()}
        vel = {k: jnp.zeros_like(v) for k, v in params.items()}
        losses = []
        for t in range(2):
            tokens = jnp.asarray(_tokens(toy, t))
            (loss, stats), grads = jax.value_and_grad(
                lambda p: whole_model(toy, m, p, tokens),
                has_aux=True)(params)
            losses.append(float(loss))
            gnorm = math.sqrt(sum(float(jnp.sum(g * g))
                                  for g in grads.values()))
            scale = min(1.0, OPT["grad_clip"] / (gnorm + 1e-9))
            new = {}
            for k in params:
                if k == bias:      # no AdamW step: the rule alone
                    load = jnp.stack([stats[(toy.MOE, j)]["load"]
                                      for j in range(m["moe_layers"])])
                    new[k] = params[k] + m["bias_rate"] * jnp.sign(
                        jnp.mean(load, -1, keepdims=True) - load)
                else:
                    new[k], mom[k], vel[k] = _adamw(
                        params[k], mom[k], vel[k], grads[k], scale, t)
            params = new
    np.testing.assert_allclose(out["losses"], losses, rtol=1e-6)
    assert out["updated"] == [bias]
    # the rule moved the bias off zero; its moments never filled
    assert float(jnp.max(jnp.abs(params[bias]))) > 0
    for (path, j), v in r.master.items():
        want = _layer(params[path], j)
        np.testing.assert_allclose(np.asarray(v), np.asarray(want),
                                   atol=1e-6, err_msg=str((path, j)))
        if path == bias:
            assert float(jnp.max(jnp.abs(r.vel[(path, j)]))) == 0.0
    r.free()


def test_a_parameter_in_no_stack_raises(toy):
    stray = types.SimpleNamespace(**{
        k: getattr(toy, k) for k in ("EMBED_PATH", "HEAD_PATHS", "stacks",
                                     "block", "head_loss_sum")})
    stray.param_specs = lambda m: dict(
        toy.param_specs(m), **{"extra/w": ((4, 4), "float32", ("ones",))})
    with pytest.raises(ValueError, match="neither the embedding nor the"):
        reftrain.Reference(stray, toy.MODEL, jax.devices()[:1])
