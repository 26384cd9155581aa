"""Synthetic deterministic data pipeline.

Produces an infinite, seeded stream of packed token batches (plus modality
stubs for VLM/audio archs), sharded onto the active mesh with host-side
prefetch.  The generator is a cheap LCG-mixed zipfian sampler so loss curves
are reproducible bit-for-bit across runs and hosts — which is exactly what
the DiTorch precision-alignment harness (repro.precision) needs.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig
from ..obs import scopes
from ..obs.metrics import MetricsRegistry


@dataclasses.dataclass(frozen=True)
class DataConfig:
    batch_size: int = 8
    seq_len: int = 256
    seed: int = 1234
    zipf_alpha: float = 1.1
    prefetch: int = 2
    structured: bool = True   # inject learnable n-gram structure


class SyntheticTokens:
    """Deterministic synthetic corpus with learnable structure.

    Tokens follow a zipfian marginal; with ``structured=True`` every even
    position deterministically hashes the previous token (a learnable bigram
    rule) so a real model's loss visibly decreases during training.
    """

    def __init__(self, cfg: ModelConfig, dcfg: DataConfig):
        self.cfg, self.dcfg = cfg, dcfg
        self._rng = np.random.default_rng(dcfg.seed)
        v = cfg.vocab_size
        ranks = np.arange(1, v + 1, dtype=np.float64)
        probs = ranks ** (-dcfg.zipf_alpha)
        self._probs = probs / probs.sum()
        self._step = 0

    def _sample(self, shape) -> np.ndarray:
        flat = self._rng.choice(self.cfg.vocab_size, size=int(np.prod(shape)),
                                p=self._probs)
        return flat.reshape(shape).astype(np.int32)

    def next_batch(self) -> Dict[str, np.ndarray]:
        d = self.dcfg
        toks = self._sample((d.batch_size, d.seq_len))
        if d.structured:
            prev = toks[:, :-1].astype(np.int64)
            rule = (prev * 2654435761 % self.cfg.vocab_size).astype(np.int32)
            even = (np.arange(1, d.seq_len) % 2 == 0)[None, :]
            toks[:, 1:] = np.where(even, rule, toks[:, 1:])
        batch: Dict[str, np.ndarray] = {"tokens": toks}
        if self.cfg.family == "vlm":
            k = self._step % 97
            batch["image_embeds"] = _unit_noise(
                (d.batch_size, self.cfg.num_prefix_tokens, self.cfg.d_model),
                self.dcfg.seed + k)
        if self.cfg.family == "audio":
            k = self._step % 97
            batch["audio_embeds"] = _unit_noise(
                (d.batch_size, self.cfg.encoder_seq_len, self.cfg.d_model),
                self.dcfg.seed + k)
        self._step += 1
        return batch


def _unit_noise(shape, seed) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal(shape).astype(np.float32)


class DataLoader:
    """Host-side prefetching iterator that device_puts with a sharding.

    It counts what it does (``counters()``): ``batches`` handed out,
    ``queue_wait_s`` the consumer spent blocked on the prefetch queue,
    ``put_s`` in ``device_put``, and ``produce_s`` the worker spent
    making batches.  The same three phases are profiler spans
    (``obs.scopes.DATA_SPANS``) in a profiler trace."""

    def __init__(self, source: SyntheticTokens, shardings: Optional[Any] = None,
                 prefetch: int = 2):
        self.source = source
        self.shardings = shardings
        self._q: queue.Queue = queue.Queue(maxsize=max(prefetch, 1))
        self._stop = threading.Event()
        self._metrics = MetricsRegistry()
        # each counter has one writer: produce_s the worker, the rest
        # the consumer
        self._batches = self._metrics.counter("batches")
        self._queue_wait = self._metrics.counter("queue_wait_s")
        self._put = self._metrics.counter("put_s")
        self._produce = self._metrics.counter("produce_s")
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def counters(self) -> Dict[str, float]:
        """Snapshot of the loader's counters since it was made."""
        return self._metrics.snapshot()

    def _worker(self):
        span = jax.profiler.TraceAnnotation
        try:
            while not self._stop.is_set():
                t0 = time.perf_counter()
                with span(scopes.DATA_PRODUCE):
                    batch = self.source.next_batch()
                self._produce.inc(time.perf_counter() - t0)
                while not self._stop.is_set():
                    try:
                        self._q.put(batch, timeout=1.0)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # surface worker crashes to the consumer
            self._error = e
            self._q.put(e)

    def __iter__(self) -> Iterator[Dict[str, jnp.ndarray]]:
        return self

    def __next__(self) -> Dict[str, jnp.ndarray]:
        span = jax.profiler.TraceAnnotation
        t0 = time.perf_counter()
        with span(scopes.DATA_QUEUE_WAIT):
            batch = self._q.get()
        t1 = time.perf_counter()
        self._queue_wait.inc(t1 - t0)
        if isinstance(batch, BaseException):
            raise RuntimeError("data worker failed") from batch
        with span(scopes.DATA_DEVICE_PUT):
            if self.shardings is not None:
                batch = jax.device_put(batch, self.shardings)
            else:
                batch = jax.tree.map(jnp.asarray, batch)
        self._put.inc(time.perf_counter() - t1)
        self._batches.inc()
        return batch

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


def make_loader(cfg: ModelConfig, dcfg: DataConfig, shardings=None) -> DataLoader:
    return DataLoader(SyntheticTokens(cfg, dcfg), shardings, dcfg.prefetch)
