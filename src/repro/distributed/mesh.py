"""Mesh helpers (device-count agnostic; see launch.mesh for production)."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np

__all__ = ["make_mesh"]


def _resolve_axis_types(axis_types: Sequence[Union[str, object]],
                        n_axes: int):
    """Map 'auto'/'explicit'/'manual' strings (or AxisType values) to
    ``jax.sharding.AxisType``."""
    AxisType = jax.sharding.AxisType
    if len(axis_types) != n_axes:
        raise ValueError(f"axis_types has {len(axis_types)} entries for "
                         f"{n_axes} mesh axes")
    by_name = {"auto": AxisType.Auto, "explicit": AxisType.Explicit,
               "manual": AxisType.Manual}
    out = []
    for t in axis_types:
        if isinstance(t, AxisType):
            out.append(t)
        else:
            try:
                out.append(by_name[str(t).lower()])
            except KeyError:
                raise ValueError(f"unknown axis type {t!r}; "
                                 f"have {sorted(by_name)}") from None
    return tuple(out)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None,
              axis_types: Optional[Sequence[Union[str, object]]] = None
              ) -> jax.sharding.Mesh:
    """Build a mesh over the first prod(shape) devices.

    The single mesh constructor (``launch.mesh.make_production_mesh``
    routes through here).  ``axis_types`` optionally names each axis's
    GSPMD mode ('auto' | 'explicit' | 'manual', or ``jax.sharding.AxisType``
    values); omitted means JAX's default.
    """
    n = int(np.prod(shape))
    devices = list(devices if devices is not None else jax.devices())[:n]
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    arr = np.asarray(devices).reshape(shape)
    if axis_types is not None:
        return jax.sharding.Mesh(
            arr, axes, axis_types=_resolve_axis_types(axis_types, len(axes)))
    return jax.sharding.Mesh(arr, axes)
