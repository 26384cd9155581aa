"""One ``shard_map`` signature for the runtime.

``manual_axes`` names the axes the body handles with explicit
collectives; every other mesh axis stays GSPMD-automatic.  Replication
checking (``check_vma``) is off.
"""
from __future__ import annotations

from typing import Optional, Set

import jax


def shard_map(f, *, mesh, in_specs, out_specs,
              manual_axes: Optional[Set[str]] = None):
    kwargs = {"check_vma": False}
    if manual_axes is not None and set(manual_axes) != set(mesh.axis_names):
        kwargs["axis_names"] = set(manual_axes)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
