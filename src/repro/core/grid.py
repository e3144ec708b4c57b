"""1D grid geometry, CIC charge deposition and field gather.

BIT1 is 1D3V: one spatial dimension (the field line through the divertor
sheath), three velocity components. The grid has ``nc`` cells of width
``dx``; node-centred quantities (rho, phi, E) live on ``nc + 1`` nodes.

Deposition is the classic PIC scatter-add hot spot. Two paths:

* ``deposit`` — XLA scatter-add (``.at[].add``), the "unified memory" path
  where XLA owns data movement;
* the Pallas ``kernels/deposit.py`` MXU path — per-tile one-hot matmul
  partial histograms accumulated in VMEM (see kernel docstring), the
  "explicit" path of the paper.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.particles import SpeciesBuffer

Array = jax.Array


@partial(jax.tree_util.register_dataclass,
         data_fields=(), meta_fields=("nc", "dx", "x0"))
@dataclasses.dataclass(frozen=True)
class Grid1D:
    nc: int          # number of cells owned by this domain
    dx: float
    x0: float = 0.0  # left edge (global coordinate of node 0)

    @property
    def ng(self) -> int:       # nodes
        return self.nc + 1

    @property
    def length(self) -> float:
        return self.nc * self.dx

    def nodes(self) -> Array:
        return self.x0 + jnp.arange(self.ng) * self.dx


def _cic_weights(grid: Grid1D, x: Array) -> tuple[Array, Array]:
    """Left node index i and fraction f for cloud-in-cell weighting."""
    s = (x - grid.x0) / grid.dx
    i = jnp.clip(jnp.floor(s).astype(jnp.int32), 0, grid.nc - 1)
    f = jnp.clip(s - i, 0.0, 1.0)
    return i, f


def deposit(grid: Grid1D, buf: SpeciesBuffer, charge: float) -> Array:
    """Charge density on nodes from one species (CIC / linear weighting)."""
    i, f = _cic_weights(grid, buf.x)
    q = charge * buf.w * buf.alive          # dead particles carry w == 0 too
    rho = jnp.zeros((grid.ng,), buf.x.dtype)
    rho = rho.at[i].add(q * (1.0 - f))
    rho = rho.at[i + 1].add(q * f)
    return rho / grid.dx


def deposit_flat(grid: Grid1D, x: Array, q: Array) -> Array:
    """CIC deposition of charges ``q`` at positions ``x``, any shape.

    x/q are flattened, which is how the stacked multi-species deposit
    collapses S sequential scatters into one pass. The two CIC nodes are
    two scalar scatter-adds. (A single scatter with a length-2 update window
    is faster on CPU, but its (N, 2) updates put 2 in the TPU's 128-lane
    minor tile: 25 GB at 3 x 16 Mi particles.) ``_cic_weights`` clips i to
    [0, nc-1], so i+1 <= ng-1 and PROMISE_IN_BOUNDS is safe (it removes
    XLA's per-update clamping).
    """
    xf = x.reshape(-1)
    qf = q.reshape(-1).astype(xf.dtype)
    i, f = _cic_weights(grid, xf)
    rho = jnp.zeros((grid.ng,), xf.dtype)
    rho = rho.at[i].add(qf * (1.0 - f), mode="promise_in_bounds")
    rho = rho.at[i + 1].add(qf * f, mode="promise_in_bounds")
    return rho / grid.dx


def deposit_stacked(grid: Grid1D, x: Array, w: Array, alive: Array,
                    charges: Array) -> Array:
    """Total charge density from stacked (S, cap) species in one scatter.

    ``charges`` is (S,); neutral species contribute zero weight and simply
    ride along (cheaper than branching per species under jit).
    """
    q = charges[:, None] * w * alive
    return deposit_flat(grid, x, q)


def deposit_density(grid: Grid1D, buf: SpeciesBuffer) -> Array:
    """Number density on nodes (charge = +1), used by the MC collision rates."""
    return deposit(grid, buf, 1.0)


def gather(grid: Grid1D, field: Array, x: Array) -> Array:
    """Interpolate a node field to particle positions (CIC)."""
    i, f = _cic_weights(grid, x)
    return field[i] * (1.0 - f) + field[i + 1] * f


def gather_onehot(grid: Grid1D, field: Array, x: Array) -> Array:
    """MXU-friendly gather: one-hot matmul instead of dynamic gather.

    On TPU a per-lane dynamic gather from VMEM serializes on the sublane
    crossbar; for small per-domain grids (ng <~ 2k nodes) a (T, ng) one-hot
    matmul runs on the MXU at full rate. This is the TPU-native adaptation of
    the mover's field access; selected by ``PICConfig.gather='onehot'``.
    """
    i, f = _cic_weights(grid, x)
    ng = grid.ng
    left = jax.nn.one_hot(i, ng, dtype=field.dtype)
    right = jax.nn.one_hot(i + 1, ng, dtype=field.dtype)
    w = left * (1.0 - f)[:, None] + right * f[:, None]
    return w @ field
