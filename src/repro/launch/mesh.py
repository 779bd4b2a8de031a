"""Production mesh construction.

Single pod: 256 TPU v5e chips as (16, 16) over ("data", "model").
Multi-pod: 2 pods = 512 chips as (2, 16, 16) over ("pod", "data", "model")
— the "pod" axis maps to DCN; pure data parallelism crosses it.

A FUNCTION (not a module constant) so importing this module never touches
jax device state; the dry-run sets XLA_FLAGS before first jax init.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(devices: Union[int, Sequence, None] = None) -> Mesh:
    """A 1-D ``"data"`` mesh over ``devices``: a list of devices, a count
    (the first ``n`` of ``jax.devices()``), or every device when None."""
    if devices is None:
        devices = jax.devices()
    elif isinstance(devices, int):
        if not 0 < devices <= len(jax.devices()):
            raise ValueError(f"asked for {devices} device(s); "
                             f"{len(jax.devices())} visible")
        devices = jax.devices()[:devices]
    return Mesh(np.asarray(devices), ("data",), axis_types=(AxisType.Auto,))
