"""Mesh construction.

Every function here is called, never run at import: importing this module
touches no device state.

* ``make_host_mesh`` builds the mesh from the chips present: on the
  four-chip TPU v5e host, ``(pod=2, data=2)``, itself a d=2 torus whose
  both axes are ICI.  The EP dispatch spans ``("data", "pod")`` with the
  d=2 round schedule.
* ``make_production_mesh`` describes the 16x16 = 256-chip pod (TPU v5e
  2-D ICI torus), and 2 pods over DCN for the multi-pod configuration:
  "data" and "model" are ICI dimensions, "pod" is the slow DCN dimension.
* ``make_debug_mesh`` is the reduced mesh of that axis structure that the
  CPU tests run on 8 / 16 forced host devices.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.core.dims import dims_create


def make_host_mesh(devices=None):
    """``(pod, data)`` mesh over ``devices`` (default: every device
    present), balanced by ``dims_create``: 2x2 on four chips, 1x1 on
    one."""
    devices = list(jax.devices() if devices is None else devices)
    return jax.make_mesh(dims_create(len(devices), 2), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Reduced mesh of the same axis structure (8 / 16 CPU devices)."""
    shape = (2, 2, 4) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)
