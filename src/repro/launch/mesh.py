"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run entrypoint sets
XLA_FLAGS before any jax initialization.

Single pod : (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips; the ``pod`` axis is
             the HeteroPP island/pipeline axis (DESIGN.md §2).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding rules
    (``sharding.ctx.constrain``) emit ``with_sharding_constraint``, which
    only accepts Auto axes; ``jax.make_mesh`` defaults to Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: int = 0, pod: int = 0) -> Mesh:
    """Mesh over whatever devices exist (tests / laptop runs)."""
    n = len(jax.devices())
    if pod:
        data = data or (n // (model * pod))
        return auto_mesh((pod, data, model), ("pod", "data", "model"))
    data = data or (n // model)
    return auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used for the roofline (assignment-provided)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s per chip
HBM_BW = 819e9                # B/s per chip
ICI_BW = 50e9                 # B/s per link
