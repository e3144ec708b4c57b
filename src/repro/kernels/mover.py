"""Pallas TPU kernel: fused particle mover (Boris push + boundary).

This is the 'explicit data movement' strategy of the paper, adapted to TPU:
instead of `#pragma acc enter data copyin(...)` staging whole arrays to GPU
memory each PIC cycle, the kernel declares BlockSpec tiles and Pallas's grid
pipeline double-buffers the HBM->VMEM DMAs — tile k+1 streams in while tile
k computes, which is precisely the overlap the paper gets from CUDA streams
(C4, DESIGN.md §2).

Layout: particle arrays are viewed as (rows, 128) planes (SoA: x, vx, vy, vz,
alive each its own plane) so tiles are VREG-aligned (8x128 multiples). The
CIC field at each particle arrives as one more plane, ``ex``: Mosaic lowers
no data-dependent gather from a 1-D VMEM ref ("Only 2D gather is
supported"), so ``ops.mover_push`` gathers E at the XLA level
(``grid.gather``) and the kernel stays gather-free, which also leaves it
with no limit on the grid width.

Work per tile is uniform by construction (a tile is just 'the next TM*128
particles'), which is the TPU-native answer to the per-cell load imbalance
BIT1 fights with OpenMP tasks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

LANES = 128


def _mover_kernel(x_ref, vx_ref, vy_ref, vz_ref, alive_ref, ex_ref,
                  xo_ref, vxo_ref, vyo_ref, vzo_ref, ao_ref, hl_ref, hr_ref,
                  *, length: float, qm_dt: float, dt: float,
                  b: tuple[float, float, float], boundary: str):
    x = x_ref[...]
    vx, vy, vz = vx_ref[...], vy_ref[...], vz_ref[...]
    alive = alive_ref[...]                      # float32 0/1 mask
    e_x = ex_ref[...] * alive                   # dead particles feel no field

    # ---- Boris push (half kick, rotate, half kick) ----
    half = 0.5 * qm_dt
    vx = vx + half * e_x
    bx, by, bz = b
    if bx != 0.0 or by != 0.0 or bz != 0.0:
        tx, ty, tz = bx * half, by * half, bz * half
        t2 = tx * tx + ty * ty + tz * tz
        sx, sy, sz = (2.0 * tx / (1.0 + t2), 2.0 * ty / (1.0 + t2),
                      2.0 * tz / (1.0 + t2))
        # v' = v + v x t
        vpx = vx + (vy * tz - vz * ty)
        vpy = vy + (vz * tx - vx * tz)
        vpz = vz + (vx * ty - vy * tx)
        # v+ = v + v' x s
        vx = vx + (vpy * sz - vpz * sy)
        vy = vy + (vpz * sx - vpx * sz)
        vz = vz + (vpx * sy - vpy * sx)
    vx = vx + half * e_x

    # ---- position update + boundary ----
    xn = x + vx * dt
    if boundary == "open":
        hl = jnp.zeros_like(alive)
        hr = jnp.zeros_like(alive)
        an = alive
    elif boundary == "periodic":
        xn = xn - jnp.floor(xn / length) * length
        hl = jnp.zeros_like(alive)
        hr = jnp.zeros_like(alive)
        an = alive
    else:
        hl = alive * (xn < 0.0).astype(x.dtype)
        hr = alive * (xn >= length).astype(x.dtype)
        an = alive * (1.0 - hl) * (1.0 - hr)
        eps = jnp.asarray(length, x.dtype) * (1.0 - 1e-7)
        xn = jnp.clip(xn, 0.0, eps)

    xo_ref[...] = xn
    vxo_ref[...] = vx
    vyo_ref[...] = vy
    vzo_ref[...] = vz
    ao_ref[...] = an
    hl_ref[...] = hl
    hr_ref[...] = hr


def mover_push_pallas(x: Array, vx: Array, vy: Array, vz: Array,
                      alive_f: Array, ex: Array, *, length: float, qm: float,
                      dt: float, b: tuple[float, float, float], boundary: str,
                      tile_rows: int = 8, interpret: bool = True):
    """Launch the fused mover. All inputs, ``ex`` (the field at each
    particle) included, are (rows, 128) planes."""
    rows = x.shape[0]
    assert rows % tile_rows == 0, (rows, tile_rows)
    grid = (rows // tile_rows,)
    tile = pl.BlockSpec((tile_rows, LANES), lambda r: (r, 0))

    kernel = functools.partial(
        _mover_kernel, length=length, qm_dt=qm * dt, dt=dt, b=b,
        boundary=boundary)

    out_shape = [jax.ShapeDtypeStruct((rows, LANES), x.dtype)] * 7
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile] * 6,
        out_specs=[tile] * 7,
        out_shape=out_shape,
        interpret=interpret,
    )(x, vx, vy, vz, alive_f, ex)
    return outs
