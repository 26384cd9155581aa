#!/usr/bin/env python3
"""Smoke run of the trainer and the server on a TPU chip.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the 4-chip HeteroPP pipeline only

One chip:

1. kernels: the four Pallas kernels, compiled for the chip, against the
   ``repro.kernels.ref`` oracles on small inputs;
2. train: qwen1.5-0.5b at its full published width (24 layers, d=1024,
   vocab 151,936) for a few steps through ``repro.launch.train.main``
   (GSPMD path, ``--backend auto``).  Losses must be finite and falling,
   the compiled step must hold the flash-attention kernel, and the chip
   must report its peak memory;
3. reference: the step-1 loss against the plain jnp (einsum) forward of
   ``models.model.loss_fn`` on the same params and batch;
4. serve: a batch-4 decode of the same model through
   ``repro.launch.serve.main``, whose compiled decode step must hold the
   paged ``flash_decode`` kernel.

Four chips (``--four-chips``): the same model as a pipe=4 1F1B pipeline
and as pipe=2 x tp=2, each step-1 loss against the monolithic
``models.model.loss_fn`` on one chip with the same params and batch, on a
mesh of four distinct devices.

Everything runs in this one process: a chip belongs to one process at a
time.  Off a TPU the script refuses to run.  Any failed phase exits
non-zero; the last line of a passing run is one JSON object naming the
device.  The numbers printed are smoke readings, not benchmarks.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1p5_0p5b"
SEED = 0
TRAIN_BATCH, TRAIN_SEQ = 8, 1024   # largest 2^k x 1024 tokens that fits
TRAIN_STEPS = 6                    # step 1 warms up; 2..6 are steady state
# the pipeline keeps every tick's residuals (GPipe memory): batch 8 would
# leave under 0.3 GB per chip at pipe=4, batch 4 needs ~10.8 GB
PIPE_BATCH, PIPE_MICROBATCHES, PIPE_STEPS = 4, 4, 3
KERNEL_TOL = 2e-2                  # max |out - ref| / max |ref|, bf16 I/O
# |step-1 loss - reference loss|: both run the bf16 model, and the
# layouts differ in reduction order and in kernel vs jnp attention
LOSS_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def hlo_kernels(hlo: str) -> set:
    """Names of the Pallas kernels (``tpu_custom_call``) in compiled HLO."""
    return {m for line in hlo.splitlines() if "tpu_custom_call" in line
            for m in re.findall(r"(\w+)/pallas_call", line)}


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def rnd(shape, dtype=jnp.bfloat16, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    def check(name, out, want):
        out = jnp.asarray(out, jnp.float32)
        want = jnp.asarray(want, jnp.float32)
        err = float(jnp.max(jnp.abs(out - want)) / jnp.max(jnp.abs(want)))
        ok = bool(jnp.all(jnp.isfinite(out))) and err <= KERNEL_TOL
        log(f"kernel {name}: rel_err={err:.3e} (tol {KERNEL_TOL}) "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            raise AssertionError(f"{name} disagrees with its reference")

    with jax.default_matmul_precision("highest"):
        q, k, v = (rnd((2, 256, 4, 64)) for _ in range(3))
        check("flash_attention", ops.flash_attention(q, k, v),
              ref.attention_ref(q, k, v))

        qd = rnd((4, 8, 128))
        kd, vd = rnd((4, 2, 384, 128)), rnd((4, 2, 384, 128))
        pos = jnp.int32(300)
        check("flash_decode", ops.flash_decode(qd, kd, vd, pos),
              ref.decode_attention_ref(qd, kd, vd, pos))

        b, S, h, p, n = 1, 256, 4, 64, 128
        x = rnd((b, S, h, p), jnp.float32)
        dt = jax.nn.softplus(rnd((b, S, h), jnp.float32) - 2.0)
        A = -jnp.exp(rnd((h,), jnp.float32, 0.5))
        Bm, Cm = rnd((b, S, 1, n), jnp.float32), rnd((b, S, 1, n), jnp.float32)
        y, fin = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=128)
        y_ref, fin_ref = ref.ssd_ref(x, dt, A, Bm, Cm)
        check("ssd_scan.y", y, y_ref)
        check("ssd_scan.state", fin, fin_ref)

        xr, sc = rnd((3, 100, 1024)), rnd((1024,), jnp.float32)
        check("rmsnorm", ops.rmsnorm(xr, sc), ref.rmsnorm_ref(xr, sc))


def first_batch(cfg, batch: int, seq: int):
    """The first batch ``train.main`` consumes at ``--seed SEED``."""
    from repro.data.pipeline import DataConfig, SyntheticTokens
    return SyntheticTokens(cfg, DataConfig(batch_size=batch, seq_len=seq,
                                           seed=1234 + SEED)).next_batch()


def reference_loss(backend: str, device, batch_size: int) -> float:
    """Monolithic ``loss_fn`` of the seeded full-width model on one chip,
    on the first batch of ``batch_size`` sequences."""
    import jax
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config(ARCH)
    params = jax.device_put(M.init_params(cfg, jax.random.PRNGKey(SEED)),
                            device)
    batch = jax.device_put(first_batch(cfg, batch_size, TRAIN_SEQ), device)
    loss = jax.jit(lambda p, b: M.loss_fn(p, cfg, b, remat=False,
                                          backend=backend)[0])(params, batch)
    return float(loss)


def train_argv(run_dir: str, steps: int, batch: int, *extra: str) -> list:
    return ["--arch", ARCH, "--steps", str(steps),
            "--batch", str(batch), "--seq", str(TRAIN_SEQ),
            "--seed", str(SEED), "--backend", "auto", "--log-every", "1",
            "--run-dir", run_dir, *extra]


def check_losses(losses, *, falling: bool) -> None:
    import math
    log("losses: " + " ".join(f"{x:.4f}" for x in losses))
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError("a step loss is not finite")
    if falling and not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses[0]} -> "
                             f"{losses[-1]}")


def phase_train(out: dict) -> None:
    import jax
    from repro.launch import train

    run = train.main(train_argv(os.path.join(ROOT, "runs", "chip_smoke"),
                                TRAIN_STEPS, TRAIN_BATCH))
    check_losses(run.losses, falling=True)
    out["train_loss_step1"] = run.losses[0]
    steady = run.step_times_s[1:]
    log(f"compile (train step): {run.compile_s:.2f} s")
    log(f"step 1 (first run after compile): {run.step_times_s[0]:.4f} s")
    log(f"steady-state step time (steps 2-{len(run.losses)}, mean): "
        f"{sum(steady) / len(steady):.4f} s  "
        f"[{' '.join(f'{t:.4f}' for t in steady)}]")
    kernels = hlo_kernels(run.compiled.as_text())
    log(f"Pallas kernels in the compiled train step: {sorted(kernels)}")
    if "flash_attention" not in kernels:
        raise AssertionError("train step holds no flash_attention "
                             "tpu_custom_call")
    stats = jax.devices()[0].memory_stats()
    if not stats or stats.get("peak_bytes_in_use") is None:
        raise AssertionError(f"the chip reports no peak memory: {stats}")
    peak = stats["peak_bytes_in_use"]
    log(f"peak HBM bytes_in_use: {peak} ({peak / 1e9:.3f} GB of "
        f"{stats.get('bytes_limit', 0) / 1e9:.3f} GB limit)")


def phase_reference(out: dict) -> None:
    import jax
    ref = reference_loss("einsum", jax.devices()[0], TRAIN_BATCH)
    got = out["train_loss_step1"]
    ok = abs(got - ref) <= LOSS_TOL
    log(f"step-1 loss {got:.5f} vs jnp reference {ref:.5f}: "
        f"|diff|={abs(got - ref):.2e} (tol {LOSS_TOL}) "
        f"{'ok' if ok else 'FAIL'}")
    if not ok:
        raise AssertionError("step-1 loss disagrees with the reference")


def phase_serve() -> None:
    from repro.configs import get_config
    from repro.launch import serve

    batch, prompt, gen = 4, 128, 16
    res = serve.main(["--arch", ARCH, "--batch", str(batch),
                      "--prompt-len", str(prompt), "--gen", str(gen),
                      "--seed", str(SEED), "--backend", "auto",
                      "--run-dir", os.path.join(ROOT, "runs", "chip_smoke")])
    vocab = get_config(ARCH).vocab_size
    toks = res.tokens
    if toks.shape != (batch, gen) or int(toks.min()) < 0 \
            or int(toks.max()) >= vocab:
        raise AssertionError(f"bad generated tokens: shape {toks.shape}")
    kernels = hlo_kernels(res.compiled_decode.as_text())
    log(f"Pallas kernels in the compiled decode step: {sorted(kernels)}")
    if "flash_decode" not in kernels:
        raise AssertionError("decode step holds no flash_decode "
                             "tpu_custom_call")
    lat = res.decode_latency_s
    log(f"decode (batch {batch}): p50={lat['p50'] * 1e3:.3f} ms "
        f"p95={lat['p95'] * 1e3:.3f} ms over {lat['count']} steps; "
        f"decode compile {res.decode_compile_s:.2f} s; prefill (incl. "
        f"compile) {res.prefill_s:.2f} s")


def phase_pipeline(name: str, ref: float, *extra: str) -> None:
    import jax
    from repro.launch import train

    run = train.main(train_argv(
        os.path.join(ROOT, "runs", f"chip_smoke_{name}"), PIPE_STEPS,
        PIPE_BATCH, "--microbatches", str(PIPE_MICROBATCHES), *extra))
    check_losses(run.losses, falling=False)
    ids = {d.id for d in run.mesh.devices.flat}
    # the stacked block params are split over the pipe axis: each stage's
    # layers must live on their own devices, not all on the first
    leaf = jax.tree.leaves(run.state[0]["blocks"])[0]
    placed = {s.device.id for s in leaf.addressable_shards}
    log(f"{name}: mesh {dict(run.mesh.shape)} on devices {sorted(ids)}; "
        f"block params {leaf.sharding.spec} on devices {sorted(placed)}")
    if len(ids) != 4 or len(placed) != 4 \
            or leaf.sharding.is_fully_replicated:
        raise AssertionError(f"{name} does not span 4 distinct devices")
    diff = abs(run.losses[0] - ref)
    ok = diff <= LOSS_TOL
    log(f"{name}: step-1 loss {run.losses[0]:.5f} vs monolithic "
        f"{ref:.5f}: |diff|={diff:.2e} (tol {LOSS_TOL}) "
        f"{'ok' if ok else 'FAIL'}")
    if not ok:
        raise AssertionError(f"{name} step-1 loss disagrees with the "
                             f"monolithic loss")


def build_phases(four_chips: bool, dev) -> list:
    """(name, callable) pairs: the 4-chip pipeline layouts and their
    reference, or the one-chip phases."""
    if four_chips:
        refs = {}

        def reference():
            refs["loss"] = reference_loss("auto", dev, PIPE_BATCH)
            log(f"monolithic loss_fn on one chip: {refs['loss']:.5f}")

        return [
            ("monolithic reference", reference),
            ("pipe=4 1f1b", lambda: phase_pipeline(
                "pipe4_1f1b", refs["loss"], "--pipeline-parallel", "4",
                "--schedule", "1f1b")),
            ("pipe=2 x tp=2", lambda: phase_pipeline(
                "pipe2_tp2", refs["loss"], "--pipeline-parallel", "2",
                "--tensor-parallel", "2", "--schedule", "1f1b")),
        ]
    out: dict = {}
    return [
        ("kernels vs reference", phase_kernels),
        ("train qwen1.5-0.5b full width", lambda: phase_train(out)),
        ("step-1 loss vs jnp reference", lambda: phase_reference(out)),
        ("serve batch 4 via flash_decode", phase_serve),
    ]


def run_phases(phases) -> list:
    failed = []
    for name, fn in phases:
        log(f"=== {name}")
        t = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"=== {name}: FAILED")
            continue
        log(f"=== {name}: ok ({time.perf_counter() - t:.1f} s)")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip pipeline layouts and their "
                         "comparison with the one-chip monolithic loss")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    phases = build_phases(args.four_chips, dev)
    failed = run_phases(phases)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
