"""Public jit'd wrappers around the Pallas kernels.

Dtype plumbing and backend selection live here; the planar
(cap,) <-> (rows, 128) relayout contract lives in ``core/particles.py``
(``to_planes`` / ``from_planes``), shared with the buffers themselves so the
layout is defined exactly once. On the CPU backend the kernels run in
interpret mode (Python evaluation of the kernel body — how the tests check
them); on TPU they always compile through Mosaic, and a width the compiler
refuses raises ``deposit.KernelWidthError`` here, at trace time. Any other
backend is refused.

The mover and the fused cycle take the CIC field at each particle as a
plane gathered here by XLA (``grid.gather``): Mosaic lowers no
data-dependent gather from a 1-D VMEM ref.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.grid import Grid1D, gather
from repro.core.particles import LANES, from_planes, to_planes
from repro.kernels import collide as _collide
from repro.kernels import deposit as _deposit
from repro.kernels import fused_cycle as _fused
from repro.kernels import interleave as _interleave
from repro.kernels import mover as _mover

Array = jax.Array


def _interpret() -> bool:
    """True on the CPU backend, False on TPU; any other backend raises."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for TPU or run interpreted on CPU; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


def _ng_pad(ng: int) -> int:
    return ng + (-ng) % LANES


def _field_plane(x: Array, e: Array, x0: float, dx: float, nc: int,
                 tile_rows: int) -> Array:
    return to_planes(gather(Grid1D(nc=nc, dx=dx, x0=x0), e, x), tile_rows)


def _particle_planes(x: Array, v: Array, alive: Array, tile_rows: int):
    return (to_planes(x, tile_rows), to_planes(v[:, 0], tile_rows),
            to_planes(v[:, 1], tile_rows), to_planes(v[:, 2], tile_rows),
            to_planes(alive.astype(x.dtype), tile_rows))


@partial(jax.jit, static_argnames=("x0", "dx", "length", "qm", "dt", "b",
                                   "boundary", "gather_mode", "tile_rows"))
def mover_push(x: Array, v: Array, alive: Array, e: Array, *, x0: float,
               dx: float, length: float, qm: float, dt: float,
               b: tuple[float, float, float] = (0.0, 0.0, 0.0),
               boundary: str = "periodic", gather_mode: str = "take",
               tile_rows: int = 8):
    """Fused mover. x: (cap,), v: (cap,3), alive: (cap,) bool, e: (ng,).

    Returns (x, v, alive, hit_left, hit_right) with original shapes.
    """
    del gather_mode  # the field gather is grid.gather at the XLA level
    cap = x.shape[0]
    nc = round(length / dx)
    xp, vxp, vyp, vzp, ap = _particle_planes(x, v, alive, tile_rows)
    exp = _field_plane(x, e, x0, dx, nc, tile_rows)

    xn, vxn, vyn, vzn, an, hl, hr = _mover.mover_push_pallas(
        xp, vxp, vyp, vzp, ap, exp, length=length, qm=qm, dt=dt, b=b,
        boundary=boundary, tile_rows=tile_rows, interpret=_interpret())

    def unpad(p):
        return from_planes(p, cap)

    v_out = jnp.stack([unpad(vxn), unpad(vyn), unpad(vzn)], axis=-1)
    return (unpad(xn), v_out, unpad(an) > 0.5, unpad(hl) > 0.5,
            unpad(hr) > 0.5)


@partial(jax.jit, static_argnames=("x0", "dx", "length", "qm", "dt",
                                   "charge", "b", "boundary", "tile_rows",
                                   "deposit"))
def fused_push_deposit(x: Array, v: Array, alive: Array, w: Array, e: Array,
                       rho_carry: Array | None = None, *, x0: float,
                       dx: float, length: float, qm: float, dt: float,
                       charge: float,
                       b: tuple[float, float, float] = (0.0, 0.0, 0.0),
                       boundary: str = "periodic", tile_rows: int = 8,
                       deposit: bool = True):
    """Single-pass fused cycle (kernels/fused_cycle.py).

    Returns (x, v, alive, hit_left, hit_right, w, rho) — the pushed state
    plus the POST-push node charge density rho: (ng,)/dx, accumulated on top
    of ``rho_carry`` (same (ng,)/dx units) when one is given. The carry is
    added OUTSIDE the kernel so the result is bitwise-identical to the
    pure-jnp ``rho_carry + deposit`` path (seeding the VMEM accumulator
    would send the carry through a *dx/dx float round trip; the kernel's
    ``rho0_pad`` seed remains available for raw-unit multi-launch
    chaining). With ``deposit=False`` the in-kernel deposition is compiled
    out and rho passes the carry through (zeros without one).
    """
    cap = x.shape[0]
    nc = round(length / dx)
    ng = e.shape[0]
    interpret = _interpret()
    if deposit and not interpret:
        _deposit.check_width("fused_push_deposit", _ng_pad(ng),
                             _fused.MAX_NG_PAD)
    xp, vxp, vyp, vzp, ap = _particle_planes(x, v, alive, tile_rows)
    wp = to_planes(w, tile_rows)
    exp = _field_plane(x, e, x0, dx, nc, tile_rows)

    xn, vxn, vyn, vzn, an, hl, hr, wn, rho = _fused.fused_push_deposit_pallas(
        xp, vxp, vyp, vzp, ap, wp, exp, None, x0=x0, dx=dx, nc=nc,
        ng_pad=_ng_pad(ng), length=length, qm=qm, dt=dt, charge=charge, b=b,
        boundary=boundary, tile_rows=tile_rows, interpret=interpret,
        do_deposit=deposit)

    def unpad(p):
        return from_planes(p, cap)

    v_out = jnp.stack([unpad(vxn), unpad(vyn), unpad(vzn)], axis=-1)
    rho_out = rho[0, :ng] / dx
    if rho_carry is not None:
        rho_out = rho_carry + rho_out
    return (unpad(xn), v_out, unpad(an) > 0.5, unpad(hl) > 0.5,
            unpad(hr) > 0.5, unpad(wn), rho_out)


# lanes of each input per grid step of the interleave kernel
INTERLEAVE_BLOCK = 4096


def interleave(xs: tuple[Array, ...], axis: int) -> Array:
    """n arrays of one shape (2-D or more), interleaved along ``axis``:
    ``out[..., n*i + k, ...] = xs[k][..., i, ...]`` (kernels/interleave.py),
    bit for bit, for any n and length and for 32-bit and bool dtypes. The
    axis is moved last (a bitcast where it is the minor axis of the TPU
    layout, as for the engine's (S, cap, 3) velocities) and padded to whole
    kernel blocks when its length needs it."""
    n = len(xs)
    if n == 1:
        return xs[0]
    dtype = xs[0].dtype
    m = xs[0].shape[axis]
    block = min(INTERLEAVE_BLOCK, m + (-m) % LANES)
    pad = (-m) % block
    rows = []
    for x in xs:
        x = jnp.moveaxis(x, axis, -1)
        if dtype == jnp.bool_:
            x = x.astype(jnp.uint8)
        if pad:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        rows.append(x)
    out = _interleave.interleave_pallas(*rows, block=block,
                                        interpret=_interpret())[..., :n * m]
    if dtype == jnp.bool_:
        out = out != 0
    return jnp.moveaxis(out, -1, axis)


@partial(jax.jit, static_argnames=("tile_rows",))
def ta_kick(u: Array, delta: Array, phi: Array, *,
            tile_rows: int = 8) -> Array:
    """Takizuka–Abe pair deflection (kernels/collide.py).

    ``u`` (M, 3) are pair relative velocities, ``delta`` (M,) the sampled
    tan(theta/2), ``phi`` (M,) the azimuths; returns du (M, 3) with
    |u + du| = |u|. Pad rows enter with delta == 0 and deflect by exactly
    zero. The jnp reference is ``collisions.ta_kick_ref`` (parity-pinned).
    """
    m = u.shape[0]
    up = [to_planes(u[:, i], tile_rows) for i in range(3)]
    dp = to_planes(delta, tile_rows)
    pp = to_planes(phi, tile_rows)
    dux, duy, duz = _collide.ta_kick_pallas(
        up[0], up[1], up[2], dp, pp, tile_rows=tile_rows,
        interpret=_interpret())
    return jnp.stack([from_planes(dux, m), from_planes(duy, m),
                      from_planes(duz, m)], axis=-1)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, block_q: int = 512,
                    block_k: int = 512) -> Array:
    """Flash attention over (bh, s, hd) head-folded inputs (see
    kernels/flash_attention.py for the VMEM tiling contract)."""
    from repro.kernels.flash_attention import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


@partial(jax.jit, static_argnames=("x0", "dx", "nc", "ng"))
def deposit(x: Array, q: Array, *, x0: float, dx: float, nc: int,
            ng: int) -> Array:
    """CIC deposition of per-particle charge q at positions x -> (ng,)/dx."""
    interpret = _interpret()
    if not interpret:
        _deposit.check_width("deposit", _ng_pad(ng), _deposit.MAX_NG_PAD)
    xp = to_planes(x)
    qp = to_planes(q)                        # padded q == 0 -> no deposit
    rho = _deposit.deposit_pallas(xp, qp, x0=x0, dx=dx, nc=nc,
                                  ng_pad=_ng_pad(ng), interpret=interpret)
    return rho[0, :ng] / dx
