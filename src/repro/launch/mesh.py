"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, and everything else must see the real single device.

Mesh discipline (DESIGN.md §6):
* ``data``  — batch / spatial-domain parallelism (PIC domains live here);
* ``model`` — tensor/expert parallelism for the LM substrate (replicated or
  species-parallel for PIC);
* ``pod``   — a second data-parallel tier whose gradient reduction is
  hierarchical (reduce-scatter intra-pod, all-reduce inter-pod) so the
  slower cross-pod links carry only one tensor-worth of traffic.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto(axes) -> tuple[AxisType, ...]:
    return (AxisType.Auto,) * len(axes)


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) == need:
        return _make_mesh(shape, axes)
    # dry-run container exposes 512 host devices; a single-pod 256-mesh
    # takes the first 256
    assert len(devs) >= need, (len(devs), need)
    grid = np.asarray(devs[:need]).reshape(shape)
    return Mesh(grid, axes, axis_types=_auto(axes))


def make_debug_mesh(data: int = 1, model: int = 1, pod: int = 0) -> Mesh:
    """Small mesh for tests on whatever devices exist."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def domain_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes carrying the PIC spatial decomposition: ('pod','data') if the
    pod axis exists, else ('data',)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
