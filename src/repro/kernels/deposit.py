"""Pallas TPU kernel: CIC charge deposition via per-tile one-hot reduction.

Deposition is PIC's scatter-add hot spot. A per-lane scatter into VMEM has no
efficient TPU lowering, so we adapt (DESIGN.md §2): each row of 128
particles expands its CIC weights into a dense (128, ng) one-hot-weighted
plane and reduces over the particle axis — a pure VPU broadcast/compare/
reduce pattern with no data-dependent addressing. A grid step takes one
(tile_rows, 128) tile, the smallest block the TPU tiling accepts, and
reduces it row by row. The (1, ng) accumulator block stays resident in VMEM
across all grid steps (constant index_map) and is initialized at step 0, so
partial histograms accumulate on-chip and HBM sees exactly one (ng,) write —
the explicit-staging discipline of the paper.

The (128, ng_pad) temporaries grow with the grid width, and so does the
work: about 2 * ng_pad compare-adds per particle. ``MAX_NG_PAD`` is the
widest padded node axis the TPU v5e compiler accepts for this kernel
(``fused_cycle.MAX_NG_PAD`` for the fused cycle; both pinned by
``tests/test_tpu_compile.py``); ``ops`` calls ``check_width`` on TPU and
refuses anything wider instead of falling back to another path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

LANES = 128

# widest padded node axis for which deposit_pallas compiles on TPU v5e
MAX_NG_PAD = 14336


class KernelWidthError(ValueError):
    """The grid is wider than a one-hot deposit kernel can hold in VMEM."""


def check_width(kernel: str, ng_pad: int, limit: int) -> None:
    if ng_pad > limit:
        raise KernelWidthError(
            f"{kernel}: the Pallas one-hot deposit holds (128, ng_pad) "
            f"temporaries in VMEM and compiles on TPU v5e up to "
            f"ng_pad={limit} nodes; this grid needs ng_pad={ng_pad}. Use "
            f"the XLA deposit (equal species capacities with strategy "
            f"'unified' or 'fused') or fewer cells per domain")


def onehot_rows(x: Array, q: Array, *, x0: float, dx: float, nc: int,
                ng_pad: int) -> Array:
    """(ng_pad,) CIC deposit of a (rows, 128) tile, reduced row by row
    (static unroll) so only one row's (128, ng_pad) one-hot is live."""
    s = (x - x0) / dx
    i = jnp.clip(jnp.floor(s).astype(jnp.int32), 0, nc - 1)
    f = jnp.clip(s - i.astype(x.dtype), 0.0, 1.0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (LANES, ng_pad), 1)
    acc = jnp.zeros((ng_pad,), x.dtype)
    for r in range(x.shape[0]):
        ir, fr, qr = i[r, :], f[r, :], q[r, :]
        left = jnp.where(cols == ir[:, None], (qr * (1.0 - fr))[:, None], 0.0)
        right = jnp.where(cols == (ir + 1)[:, None], (qr * fr)[:, None], 0.0)
        acc = acc + jnp.sum(left + right, axis=0)
    return acc


def _deposit_kernel(x_ref, q_ref, rho_ref, *, x0: float, dx: float, nc: int,
                    ng_pad: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        rho_ref[...] = jnp.zeros_like(rho_ref)

    acc = onehot_rows(x_ref[...], q_ref[...], x0=x0, dx=dx, nc=nc,
                      ng_pad=ng_pad)
    rho_ref[...] += acc[None, :].astype(rho_ref.dtype)


def deposit_pallas(x: Array, q: Array, *, x0: float, dx: float, nc: int,
                   ng_pad: int, tile_rows: int = 8,
                   interpret: bool = True) -> Array:
    """x, q: (rows, 128) planes; returns (1, ng_pad) node charge density*dx."""
    rows = x.shape[0]
    assert rows % tile_rows == 0, (rows, tile_rows)
    grid = (rows // tile_rows,)
    tile = pl.BlockSpec((tile_rows, LANES), lambda r: (r, 0))
    acc = pl.BlockSpec((1, ng_pad), lambda r: (0, 0))  # VMEM-resident accum

    kernel = functools.partial(_deposit_kernel, x0=x0, dx=dx, nc=nc,
                               ng_pad=ng_pad)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile, tile],
        out_specs=acc,
        out_shape=jax.ShapeDtypeStruct((1, ng_pad), x.dtype),
        interpret=interpret,
    )(x, q)
