"""Pallas TPU kernel: single-pass fused PIC cycle (push + deposit).

The PIC hot loop reads every particle twice per step: once to move it, once
to deposit its charge for the next field solve. Hariri et al. 2016 fuse
gather/push/deposit into one pass over the particle list; this kernel is the
TPU form of that fusion. Each grid step stages one particle tile HBM->VMEM
(double-buffered by the Pallas pipeline), moves it, and deposits its
POST-push charge into a (1, ng_pad) accumulator that stays VMEM-resident
across all grid steps (constant index_map) — so the particle state makes
ONE HBM round-trip through the kernel per cycle (the XLA-side field gather
reads x once more) and the field sees exactly one (ng,) write.

Layout contract (see ``core/particles.py``): particle arrays arrive as
(rows, 128) planes — SoA with x, vx, vy, vz, alive, w each its own plane,
VREG-aligned tiles of ``tile_rows`` sublanes. The CIC field at each
particle arrives as one more plane, ``ex``, gathered by XLA in
``ops.fused_push_deposit``: Mosaic lowers no data-dependent gather from a
1-D VMEM ref. Dead particles carry alive == 0 AND w == 0, so they feel no
field and deposit no charge; pad slots are dead by construction.

The deposit itself is the per-tile one-hot reduction of ``deposit.py``
(broadcast/compare/reduce on the VPU — no data-dependent addressing), done
sublane row by sublane row over the freshly-pushed positions while the tile
is still on-chip. Its width limit is ``MAX_NG_PAD``; with
``do_deposit=False`` the kernel has none.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.deposit import onehot_rows

Array = jax.Array

LANES = 128

# widest padded node axis for which the kernel compiles on TPU v5e with
# do_deposit=True (see deposit.py); without the deposit there is no limit
MAX_NG_PAD = 14208


def _fused_kernel(x_ref, vx_ref, vy_ref, vz_ref, alive_ref, w_ref, ex_ref,
                  rho0_ref, xo_ref, vxo_ref, vyo_ref, vzo_ref, ao_ref,
                  hl_ref, hr_ref, wo_ref, rho_ref, *, x0: float, dx: float,
                  nc: int, length: float, qm_dt: float, dt: float,
                  charge: float, b: tuple[float, float, float],
                  boundary: str, ng_pad: int, do_deposit: bool):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        # the VMEM accumulator starts from rho0 (zeros normally): a raw-
        # unit (times-dx) seed for chaining multiple launches over one
        # accumulator. ops.fused_push_deposit adds its (ng,)/dx rho_carry
        # OUTSIDE instead, keeping bitwise parity with the jnp path.
        rho_ref[...] = rho0_ref[...]

    x = x_ref[...]
    vx, vy, vz = vx_ref[...], vy_ref[...], vz_ref[...]
    alive = alive_ref[...]                      # float32 0/1 mask
    w = w_ref[...]
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
        vpx = vx + (vy * tz - vz * ty)
        vpy = vy + (vz * tx - vx * tz)
        vpz = vz + (vx * ty - vy * tx)
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
    wn = w * an

    xo_ref[...] = xn
    vxo_ref[...] = vx
    vyo_ref[...] = vy
    vzo_ref[...] = vz
    ao_ref[...] = an
    hl_ref[...] = hl
    hr_ref[...] = hr
    wo_ref[...] = wn

    # ---- deposit the post-push charge while the tile is in VMEM ----
    # per-sublane one-hot reduction (static unroll over tile_rows): each row
    # of 128 particles expands CIC weights against the node axis and reduces.
    # Statically compiled out when the caller wants no deposit (e.g. the
    # field-solve-off benchmark scenario) — the rho output stays zero.
    if not do_deposit:
        return
    acc = onehot_rows(xn, charge * wn, x0=x0, dx=dx, nc=nc, ng_pad=ng_pad)
    rho_ref[...] += acc[None, :].astype(rho_ref.dtype)


def fused_push_deposit_pallas(x: Array, vx: Array, vy: Array, vz: Array,
                              alive_f: Array, w: Array, ex: Array,
                              rho0_pad: Array | None = None, *,
                              x0: float, dx: float, nc: int, ng_pad: int,
                              length: float, qm: float, dt: float,
                              charge: float,
                              b: tuple[float, float, float], boundary: str,
                              tile_rows: int = 8, interpret: bool = True,
                              do_deposit: bool = True):
    """Launch the fused cycle. All particle planes, ``ex`` (the field at
    each particle) included, are (rows, 128).

    Returns (xn, vxn, vyn, vzn, alive_n, hit_l, hit_r, wn, rho) where rho is
    the (1, ng_pad) node charge (times dx — the caller divides, matching
    ``kernels/deposit.py``). ``rho0_pad`` (1, ng_pad), same units, seeds the
    VMEM accumulator — the carried-rho hook for multi-call accumulation.
    """
    rows = x.shape[0]
    assert rows % tile_rows == 0, (rows, tile_rows)
    grid = (rows // tile_rows,)
    if rho0_pad is None:
        rho0_pad = jnp.zeros((1, ng_pad), x.dtype)

    tile = pl.BlockSpec((tile_rows, LANES), lambda r: (r, 0))
    acc = pl.BlockSpec((1, ng_pad), lambda r: (0, 0))  # VMEM-resident

    kernel = functools.partial(
        _fused_kernel, x0=x0, dx=dx, nc=nc, length=length, qm_dt=qm * dt,
        dt=dt, charge=charge, b=b, boundary=boundary, ng_pad=ng_pad,
        do_deposit=do_deposit)

    out_shape = ([jax.ShapeDtypeStruct((rows, LANES), x.dtype)] * 8
                 + [jax.ShapeDtypeStruct((1, ng_pad), x.dtype)])
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile] * 7 + [acc],
        out_specs=[tile] * 8 + [acc],
        out_shape=out_shape,
        interpret=interpret,
    )(x, vx, vy, vz, alive_f, w, ex, rho0_pad)
    return outs
