"""Mesh construction helpers."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(n_devices: int | None = None, axis: str = "nonce",
              obj_axis: str | None = None, obj_size: int = 1,
              devices=None) -> Mesh:
    """Build a mesh over the first ``n_devices`` devices (of
    ``devices``, where the caller has been given its own).

    1D by default (all chips on the nonce axis).  With ``obj_axis`` a 2D
    ``(obj, nonce)`` mesh is built: pending objects are data-parallel
    over ``obj_axis`` while each object's nonce range is partitioned
    over ``axis``.
    """
    devices = list(devices) if devices else jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    devices = devices[:n_devices]
    if obj_axis is None:
        return Mesh(np.array(devices), (axis,))
    assert n_devices % obj_size == 0
    grid = np.array(devices).reshape(obj_size, n_devices // obj_size)
    return Mesh(grid, (obj_axis, axis))
