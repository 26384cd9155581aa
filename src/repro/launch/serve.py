"""Serving launcher: batched prefill + greedy decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2_780m --smoke \
        --batch 4 --prompt-len 64 --gen 32 [--backend auto|einsum|pallas]

``--backend`` picks the kernel path for both prefill and decode:
``auto`` resolves to the Pallas kernels on TPU and the jnp paths
elsewhere; ``pallas`` forces the kernels (interpret mode off-TPU — a
correctness tool, not a fast path).  Decode reports per-step p50/p95
latency and tokens/s so a kernel change is visible from the launcher
output alone; the same numbers land as structured histogram/gauge rows
in ``<run-dir>/metrics.jsonl`` (``repro.obs.metrics`` — DESIGN.md §14).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from ..configs import canonical, get_config, get_smoke_config, list_configs
from ..data.pipeline import DataConfig, SyntheticTokens
from ..models import model as M
# re-exported for compat: the nearest-rank percentile moved to the
# metrics registry with the observability subsystem (DESIGN.md §14)
from ..obs.metrics import percentile  # noqa: F401
from ..training import serve_step as SS
from .compile_cache import enable_compile_cache

BACKENDS = ["auto", "einsum", "pallas"]


@dataclasses.dataclass
class ServeRun:
    """What a serving run hands back to an in-process caller."""
    tokens: Any                  # (batch, gen) generated token ids
    prefill_s: float             # first call: includes its compile
    decode_compile_s: float
    decode_latency_s: dict       # per-step histogram summary (p50, p95, ...)
    compiled_decode: Any         # its HLO shows which kernels decode runs


def main(argv: Optional[List[str]] = None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_configs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--backend", default="auto", choices=BACKENDS,
                    help="kernel path: auto (pallas on TPU, jnp "
                         "elsewhere), einsum, or pallas (forced; "
                         "interpret mode off-TPU)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--run-dir", default=None,
                    help="write decode latency histogram / tok-s rows to "
                         "<run-dir>/metrics.jsonl (default runs/<arch>)")
    ap.add_argument("--log-every", type=int, default=0,
                    help="also emit an interim decode histogram row "
                         "every N decode steps (0 = final row only)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    name = canonical(args.arch)
    cfg = get_smoke_config(name) if args.smoke else get_config(name)
    total = args.prompt_len + args.gen
    print(f"serving {cfg.name}: batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen} backend={args.backend}")

    from ..obs import MetricsLogger, MetricsRegistry
    reg = MetricsRegistry()
    metrics = MetricsLogger(
        args.run_dir or os.path.join("runs", cfg.name),
        meta={"arch": cfg.name, "family": cfg.family, "mode": "serve",
              "batch": args.batch, "prompt_len": args.prompt_len,
              "gen": args.gen, "backend": args.backend})

    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    src = SyntheticTokens(cfg, DataConfig(batch_size=args.batch,
                                          seq_len=args.prompt_len))
    batch = jax.tree.map(jnp.asarray, src.next_batch())

    decode, plan = SS.make_decode_step(cfg, total, backend=args.backend)
    decode = jax.jit(decode)

    t0 = time.perf_counter()
    cache, logits, plen = M.prefill(params, cfg, batch,
                                    cache_len=max(plan["cache_len"], total),
                                    backend=args.backend)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill: {t_prefill * 1e3:.1f} ms "
          f"({args.batch * args.prompt_len / t_prefill:.0f} tok/s)")
    reg.gauge("prefill_s").set(t_prefill)
    reg.gauge("prefill_tok_per_s").set(
        args.batch * args.prompt_len / t_prefill)

    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    out = [tok]
    # compile and warm the decode step outside the timed loop so step
    # times are steady-state, then time every step individually: the
    # mean hides exactly the tail the kernel work targets
    t_c = time.perf_counter()
    decode = decode.lower(params, cache, tok, jnp.int32(plen)).compile()
    t_compile = time.perf_counter() - t_c
    print(f"decode compile: {t_compile:.1f}s")
    _ = jax.block_until_ready(decode(params, cache, tok, jnp.int32(plen)))
    hist = reg.histogram("decode_latency_s")
    pos = plen
    for i in range(args.gen - 1):
        t1 = time.perf_counter()
        logits, tok, cache = decode(params, cache, tok, jnp.int32(pos))
        jax.block_until_ready(tok)
        hist.observe(time.perf_counter() - t1)
        out.append(tok)
        pos += 1
        if args.log_every and (i + 1) % args.log_every == 0:
            metrics.log_histogram("decode_latency_s", hist)
    gen = jnp.concatenate(out, axis=1)
    s = hist.summary()
    if hist.count:
        p50, p95, tot = s["p50"], s["p95"], s["mean"] * s["count"]
        reg.gauge("decode_tok_per_s").set(
            args.batch * hist.count / max(tot, 1e-9))
        reg.gauge("decode_tok_per_s_p50").set(
            args.batch / max(p50, 1e-9))
        # the structured rows carry the numbers the summary line prints
        metrics.log_histogram("decode_latency_s", hist)
        metrics.log(**reg.snapshot())
        print(f"decode: {tot * 1e3:.1f} ms over {hist.count} steps — "
              f"p50={p50 * 1e3:.2f} ms p95={p95 * 1e3:.2f} ms "
              f"({args.batch * hist.count / max(tot, 1e-9):.0f} tok/s, "
              f"{args.batch / max(p50, 1e-9):.0f} tok/s @p50)")
    metrics.close()
    print(f"generated[0][:16] = {gen[0, :16].tolist()}")
    return ServeRun(tokens=gen, prefill_s=t_prefill,
                    decode_compile_s=t_compile, decode_latency_s=s,
                    compiled_decode=decode)


if __name__ == "__main__":
    main()
