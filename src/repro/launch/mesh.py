"""Device meshes for the train and serve drivers.

A FUNCTION (not a module constant) so importing this module never touches
jax device state.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_local_mesh"]


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
