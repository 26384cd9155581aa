"""Names of the program's layers inside the compiled step (DESIGN.md §14).

Each name is given to ``jax.named_scope`` at one site of the program, so
it lands in the ``op_name`` metadata of every HLO instruction that site
lowers to, forward and backward, and a device trace can be attributed
to the model's layers instead of to ``fusion.NNN``.  The innermost of
these names on an op's path is its layer.

This module holds only the strings: the program and the readers of its
traces both import it, and it imports nothing (``repro.obs`` is
importable without jax).
"""
from __future__ import annotations

EMBED = "embed"                      # layers.embed_tokens
LAYERS = "layers"                    # the stacked-layer scans
ATTENTION = "attention"              # attention.self_attention
ATTENTION_CORE = "attention_core"    # attention.attend, ops.flash_attention
MLP = "mlp"                          # layers.apply_mlp
SSD = "ssd"                          # ssm.mamba2_forward
SSD_CORE = "ssd_core"                # ssm.ssd_chunked, ops.ssd_scan
LOSS_HEAD = "loss_head"              # model.chunked_ce: unembed and CE
OPTIMIZER = "optimizer"              # adamw.apply_update
PIPE_TICK = "pipe_tick"              # the heteropp tick body
PIPE_SEND = "pipe_send"              # the tick body's ppermutes

ALL = (EMBED, LAYERS, ATTENTION, ATTENTION_CORE, MLP, SSD, SSD_CORE,
       LOSS_HEAD, OPTIMIZER, PIPE_TICK, PIPE_SEND)

# host spans of the data loader (``jax.profiler.TraceAnnotation``)
DATA_QUEUE_WAIT = "data.queue_wait"  # consumer blocked on the queue
DATA_DEVICE_PUT = "data.device_put"  # consumer's device_put
DATA_PRODUCE = "data.produce"        # worker thread's next_batch
DATA_SPANS = (DATA_QUEUE_WAIT, DATA_DEVICE_PUT, DATA_PRODUCE)
