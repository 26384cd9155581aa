"""Top-level model API: init / abstract init / forward / loss / serve.

The same functions cover all six families; family dispatch happens on
``cfg.family``.  Abstract init (``abstract_params``) is ``jax.eval_shape``
over the concrete initializer — the dry-run uses it so no memory is ever
allocated for full-size configs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention, layers, ssm as ssm_lib, transformer as tfm
from .config import ModelConfig
from ..obs import scopes
from ..sharding.ctx import constrain

PyTree = Any


def _block_kind(cfg: ModelConfig) -> str:
    return cfg.block_kind


def _hybrid_groups(cfg) -> Tuple[int, int]:
    per = cfg.hybrid_attn_every
    assert cfg.num_layers % per == 0, (cfg.num_layers, per)
    return cfg.num_layers // per, per


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> PyTree:
    dtype = layers.dtype_of(cfg)
    keys = jax.random.split(key, 8)
    p: Dict[str, PyTree] = {
        "embed": layers.init_embeddings(keys[0], cfg, dtype),
        "final_norm": layers.init_norm(cfg.norm, cfg.d_model),
    }
    if cfg.family == "audio":
        p["enc_blocks"] = tfm.init_stacked_blocks(
            keys[1], cfg, "dense", cfg.num_encoder_layers, dtype)
        p["dec_blocks"] = tfm.init_stacked_blocks(
            keys[2], cfg, "dec_cross", cfg.num_layers, dtype)
        p["enc_pos"] = layers.embed_init(
            keys[3], (cfg.encoder_seq_len, cfg.d_model), dtype)
        p["enc_final_norm"] = layers.init_norm(cfg.norm, cfg.d_model)
    elif cfg.family == "hybrid":
        G, per = _hybrid_groups(cfg)
        gkeys = jax.random.split(keys[1], G)
        p["blocks"] = jax.vmap(
            lambda k: tfm.init_stacked_blocks(k, cfg, "ssm", per, layers.dtype_of(cfg))
        )(gkeys)                                  # leading dims (G, per)
        p["shared_attn"] = tfm.init_block(keys[2], cfg, "dense", dtype)
    else:
        p["blocks"] = tfm.init_stacked_blocks(
            keys[1], cfg, _block_kind(cfg), cfg.num_layers, dtype)
    return p


def abstract_params(cfg: ModelConfig) -> PyTree:
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(lambda k: init_params(cfg, k), key)


def param_count(params: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# forward (training / full-sequence)
# ---------------------------------------------------------------------------

def forward(params: PyTree, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            *, remat: bool = True, remat_policy=None, backend: str = "auto",
            sp: bool = True, unembed: bool = True
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (logits over text positions, metrics); with ``unembed=False``
    returns final-norm hidden states instead (used by the chunked loss)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = layers.embed_tokens(params["embed"], tokens)
    x = constrain(x, "batch", None, None)
    prefix_len = 0
    metrics: Dict[str, jnp.ndarray] = {}

    if cfg.family == "vlm":
        img = batch["image_embeds"].astype(x.dtype)      # (B, P, d) stub frontend
        x = jnp.concatenate([img, x], axis=1)
        prefix_len = img.shape[1]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    # SSM stacks shard channels/heads over `model` (see ssm.py); sequence-
    # parallel inter-block activations would fight that layout (§Perf B)
    sp = sp and cfg.family not in ("ssm", "hybrid")
    kw = dict(remat=remat, remat_policy=remat_policy, backend=backend, sp=sp)

    if cfg.family == "audio":
        enc = batch["audio_embeds"].astype(x.dtype) + params["enc_pos"]
        enc, _ = tfm.run_stacked(params["enc_blocks"], cfg, enc, "dense",
                                 causal=False, **kw)
        enc = layers.apply_norm(params["enc_final_norm"], enc, cfg.norm)
        x = x + _sinusoidal(positions, cfg.d_model).astype(x.dtype)

        def one(x, inp):
            p = inp
            x = constrain(x, "batch", None, None)
            ekv = attention.encode_cross_kv(p["xattn"], cfg, enc)
            x, _ = tfm.block_forward(p, cfg, x, "dec_cross",
                                     positions=positions, enc_kv=ekv,
                                     backend=backend)
            return x, jnp.float32(0)

        body = jax.checkpoint(one) if remat else one
        x, _ = jax.lax.scan(body, x, params["dec_blocks"])
        aux = jnp.float32(0)
    elif cfg.family == "hybrid":
        G, per = _hybrid_groups(cfg)

        def group(x, gp):
            x, aux = tfm.run_stacked(gp, cfg, x, "ssm", **kw)
            x = constrain(x, "batch", None, "model")
            # the weight-shared attention block must be rematted too: its
            # S×S score intermediates would otherwise be saved per group
            x, _ = tfm.block_forward(
                params["shared_attn"], cfg, x, "dense", positions=positions,
                window=cfg.effective_long_window if S > cfg.max_seq_len else cfg.sliding_window,
                backend=backend)
            return x, aux

        body = jax.checkpoint(group, policy=remat_policy) if remat else group
        x, auxs = jax.lax.scan(body, x, params["blocks"])
        aux = jnp.sum(auxs)
    else:
        x, aux = tfm.run_stacked(params["blocks"], cfg, x, _block_kind(cfg),
                                 positions=positions, prefix_len=prefix_len,
                                 **kw)

    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    if cfg.family == "vlm":
        x = x[:, prefix_len:]
    metrics["aux_loss"] = aux
    if not unembed:
        return x, metrics
    logits = layers.unembed(params["embed"], x)
    logits = constrain(logits, "batch", None, "model")
    return logits, metrics


def _sinusoidal(positions, d):
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

LOSS_CHUNK = 1024


def _ce_chunk(embed_params, x_c, t_c, m_c):
    """CE over one sequence chunk; fp32 math, logits never leave the chunk."""
    lg = layers.unembed(embed_params, x_c)
    lg = constrain(lg, "batch", None, "model").astype(jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, t_c[..., None], axis=-1)[..., 0]
    ce = (logz - tgt) * m_c
    return jnp.sum(ce)


@jax.named_scope(scopes.LOSS_HEAD)
def chunked_ce(embed_params, hidden, targets, mask, chunk=LOSS_CHUNK):
    """Scan over sequence chunks with remat: peak memory = one chunk's
    logits instead of the full (B, S, V) fp32 tensor."""
    B, S, d = hidden.shape
    if S % chunk or S <= chunk:
        return _ce_chunk(embed_params, hidden, targets, mask)
    n = S // chunk
    xs = (hidden.reshape(B, n, chunk, d).swapaxes(0, 1),
          targets.reshape(B, n, chunk).swapaxes(0, 1),
          mask.reshape(B, n, chunk).swapaxes(0, 1))

    @jax.checkpoint
    def body(acc, inp):
        x_c, t_c, m_c = inp
        return acc + _ce_chunk(embed_params, x_c, t_c, m_c), None

    total, _ = jax.lax.scan(body, jnp.float32(0), xs)
    return total


def loss_fn(params, cfg, batch, *, remat=True, remat_policy=None,
            backend="auto", sp=True):
    hidden, metrics = forward(params, cfg, batch, remat=remat,
                              remat_policy=remat_policy, backend=backend,
                              sp=sp, unembed=False)
    tokens = batch["tokens"]
    # next-token targets aligned to all S positions; last position masked
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(tokens, jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    mask = mask.at[:, -1].set(0.0)
    ce_sum = chunked_ce(params["embed"], hidden, targets, mask)
    loss = ce_sum / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + metrics.get("aux_loss", 0.0)
    metrics = dict(metrics, ce_loss=loss)
    return total, metrics


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, cache_len: int, *, ring: bool = False):
    dtype = layers.dtype_of(cfg)
    if cfg.family == "ssm":
        one = lambda _: ssm_lib.init_ssm_cache(cfg, batch, dtype)
        return jax.vmap(one)(jnp.arange(cfg.num_layers))
    if cfg.family == "hybrid":
        G, per = _hybrid_groups(cfg)
        ssm_c = jax.vmap(jax.vmap(
            lambda _: ssm_lib.init_ssm_cache(cfg, batch, dtype)
        ))(jnp.zeros((G, per)))
        attn_c = jax.vmap(
            lambda _: attention.init_kv_cache(cfg, batch, cache_len, dtype)
        )(jnp.arange(G))
        return {"ssm": ssm_c, "attn": attn_c}
    n = cfg.num_layers
    kv = jax.vmap(lambda _: attention.init_kv_cache(cfg, batch, cache_len, dtype)
                  )(jnp.arange(n))
    if cfg.family == "audio":
        cross = (
            jnp.zeros((n, batch, cfg.encoder_seq_len, cfg.num_kv_heads,
                       cfg.head_dim), dtype),
            jnp.zeros((n, batch, cfg.encoder_seq_len, cfg.num_kv_heads,
                       cfg.head_dim), dtype),
        )
        return {"self": kv, "cross": cross}  # cross kv overwritten at prefill
    return kv


def prefill(params, cfg, batch, cache_len: int, *, ring: bool = False,
            backend: str = "auto"):
    """Run the prompt through the model, filling caches.

    Returns (cache, logits of the last position (B, V), prompt_len).
    For ring caches the prompt must fit in the window (serving code feeds the
    window tail only) — standard SWA semantics.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    dtype = layers.dtype_of(cfg)
    cache = init_cache(cfg, B, cache_len)
    x = layers.embed_tokens(params["embed"], tokens)
    prefix_len = 0
    if cfg.family == "vlm":
        img = batch["image_embeds"].astype(x.dtype)
        x = jnp.concatenate([img, x], axis=1)
        prefix_len = img.shape[1]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    if cfg.family == "ssm":
        def step(x, inp):
            p, _ = inp
            h = layers.apply_norm(p["ln1"], x, cfg.norm)
            y, final = ssm_lib.mamba2_forward(p["ssm"], cfg, h, backend=backend)
            conv_dim = cfg.ssm_dinner + 2 * cfg.ssm_ngroups * cfg.ssm_state
            zx = h @ p["ssm"]["in_proj"]
            _, xc, Bm, Cm, _ = ssm_lib._split_in_proj(cfg, zx)
            xBC = jnp.concatenate([xc, Bm, Cm], axis=-1)
            W = cfg.ssm_conv_width
            conv_tail = xBC[:, -(W - 1):, :].astype(dtype)
            return x + y, {"conv": conv_tail, "state": final}
        x, cache = jax.lax.scan(step, x, (params["blocks"], jnp.arange(cfg.num_layers)))
    elif cfg.family == "hybrid":
        G, per = _hybrid_groups(cfg)
        W = cfg.ssm_conv_width

        def ssm_one(x, p):
            h = layers.apply_norm(p["ln1"], x, cfg.norm)
            y, final = ssm_lib.mamba2_forward(p["ssm"], cfg, h, backend=backend)
            zx = h @ p["ssm"]["in_proj"]
            _, xc, Bm, Cm, _ = ssm_lib._split_in_proj(cfg, zx)
            xBC = jnp.concatenate([xc, Bm, Cm], axis=-1)
            conv_tail = xBC[:, -(W - 1):, :].astype(dtype)
            return x + y, {"conv": conv_tail, "state": final}

        def group(x, gp):
            x, ssm_c = jax.lax.scan(ssm_one, x, gp)
            h = layers.apply_norm(params["shared_attn"]["ln1"], x, cfg.norm)
            q, k, v = attention._project_qkv(params["shared_attn"]["attn"],
                                             cfg, h, positions)
            kc = attention.init_kv_cache(cfg, B, cache_len, dtype)
            kc = attention.prefill_into_cache(kc, k, v)
            x, _ = tfm.block_forward(params["shared_attn"], cfg, x, "dense",
                                     positions=positions, backend=backend)
            return x, {"ssm": ssm_c, "attn": kc}

        x, cache = jax.lax.scan(group, x, params["blocks"])
        cache = {"ssm": cache["ssm"], "attn": cache["attn"]}
    elif cfg.family == "audio":
        enc = batch["audio_embeds"].astype(x.dtype) + params["enc_pos"]
        enc, _ = tfm.run_stacked(params["enc_blocks"], cfg, enc, "dense",
                                 causal=False, remat=False, backend=backend)
        enc = layers.apply_norm(params["enc_final_norm"], enc, cfg.norm)
        x = x + _sinusoidal(positions, cfg.d_model).astype(x.dtype)

        def step(x, p):
            h = layers.apply_norm(p["ln1"], x, cfg.norm)
            q, k, v = attention._project_qkv(p["attn"], cfg, h, positions,
                                             rope=False)
            kc = attention.init_kv_cache(cfg, B, cache_len, dtype)
            kc = attention.prefill_into_cache(kc, k, v)
            ekv = attention.encode_cross_kv(p["xattn"], cfg, enc)
            x, _ = tfm.block_forward(p, cfg, x, "dec_cross",
                                     positions=positions, enc_kv=ekv,
                                     backend=backend)
            return x, {"self_kv": kc, "cross": ekv}
        x, scanned = jax.lax.scan(step, x, params["dec_blocks"])
        cache = {"self": scanned["self_kv"], "cross": scanned["cross"]}
    else:
        window = cfg.sliding_window

        def step(x, p):
            h = layers.apply_norm(p["ln1"], x, cfg.norm)
            q, k, v = attention._project_qkv(p["attn"], cfg, h, positions)
            kc = attention.init_kv_cache(cfg, B, cache_len, dtype)
            kc = attention.prefill_into_cache(kc, k, v)
            x, _ = tfm.block_forward(p, cfg, x, _block_kind(cfg),
                                     positions=positions,
                                     prefix_len=prefix_len, backend=backend)
            return x, kc
        x, cache = jax.lax.scan(step, x, params["blocks"])

    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    last = x[:, -1]
    logits = layers.unembed(params["embed"], last[:, None])[:, 0]
    return cache, logits, x.shape[1]


def decode_step(params, cfg, tokens, cache, pos, *, ring: bool = False,
                window: int = 0, backend: str = "auto"):
    """One decode step.  tokens: (B, 1) int32; pos: scalar int32 position of
    this token.  ``backend`` routes the per-layer attention to the paged
    ``flash_decode`` kernel (``"pallas"``, or ``"auto"`` on TPU) or the
    einsum cache path.  Returns (logits (B, V), new cache)."""
    x = layers.embed_tokens(params["embed"], tokens)
    x = constrain(x, "batch", None, None)

    if cfg.family == "ssm":
        def step(x, inp):
            p, c = inp
            h = layers.apply_norm(p["ln1"], x, cfg.norm)
            y, c2 = ssm_lib.mamba2_decode_step(p["ssm"], cfg, h, c)
            return x + y, c2
        x, new_cache = jax.lax.scan(step, x, (params["blocks"], cache))
    elif cfg.family == "hybrid":
        def group(x, inp):
            gp, gc_ssm, gc_attn = inp

            def sstep(x, sinp):
                p, c = sinp
                h = layers.apply_norm(p["ln1"], x, cfg.norm)
                y, c2 = ssm_lib.mamba2_decode_step(p["ssm"], cfg, h, c)
                return x + y, c2
            x, ssm_c2 = jax.lax.scan(sstep, x, (gp, gc_ssm))
            x, attn_c2 = tfm.block_decode(params["shared_attn"], cfg, x,
                                          gc_attn, pos, "dense", ring=ring,
                                          window=window, backend=backend)
            return x, (ssm_c2, attn_c2)
        x, (ssm_c, attn_c) = jax.lax.scan(
            group, x, (params["blocks"], cache["ssm"], cache["attn"]))
        new_cache = {"ssm": ssm_c, "attn": attn_c}
    elif cfg.family == "audio":
        x = x + _sinusoidal(jnp.full((1,), pos, jnp.int32), cfg.d_model).astype(x.dtype)

        def step(x, inp):
            p, c, ekv = inp
            x, c2 = tfm.block_decode(p, cfg, x, c, pos, "dec_cross",
                                     ring=ring, window=window, enc_kv=ekv,
                                     backend=backend)
            return x, c2
        x, self_c = jax.lax.scan(
            step, x, (params["dec_blocks"], cache["self"], cache["cross"]))
        new_cache = {"self": self_c, "cross": cache["cross"]}
    else:
        def step(x, inp):
            p, c = inp
            x, c2 = tfm.block_decode(p, cfg, x, c, pos, _block_kind(cfg),
                                     ring=ring, window=window,
                                     backend=backend)
            return x, c2
        x, new_cache = jax.lax.scan(step, x, (params["blocks"], cache))

    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    logits = layers.unembed(params["embed"], x)[:, 0]
    logits = constrain(logits, "batch", "model")
    return logits, new_cache
