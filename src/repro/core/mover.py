"""The particle mover — the paper's optimization target.

BIT1 profiling [Williams et al. 2023] shows the mover dominating runtime; the
paper parallelizes it with OpenMP tasks / OpenACC on CPU and offloads it with
OpenMP target / OpenACC on GPU, comparing *explicit* and *unified-memory*
data movement. The TPU/JAX mapping (DESIGN.md §2):

* ``strategy='unified'``  — pure jnp push; XLA manages all HBM traffic and
  fusion (the unified-memory analogue).
* ``strategy='explicit'`` — fused Pallas kernel with explicit BlockSpec
  HBM->VMEM staging and double-buffered tile pipeline (the explicit-copy
  analogue, and the paper's "CUDA streams" overlap, which Pallas's grid
  pipeline provides structurally).
* ``strategy='async_batched'`` — the assigned title's *asynchronous* mode:
  ``lax.scan`` over particle batches so migration/collective work of batch k
  overlaps the push of batch k+1 (see ``decomposition.py`` for the
  multi-device form).
* ``strategy='fused'``    — single-pass push+deposit [Hariri et al. 2016]:
  the post-push charge is deposited in the same pass that moves the
  particles, so the cycle reads the particle arrays from HBM once instead of
  twice. On TPU this is the ``kernels/fused_cycle.py`` Pallas kernel (the
  deposit accumulates in VMEM while the tile is resident); on other backends
  a pure-jnp equivalent whose deposition is one flattened pass over all
  species (``grid.deposit_flat``).

Every strategy returns a ``PushResult`` carrying the wall-hit masks of this
push. The masks are what the plasma-wall sources (SEE / sputtering,
``boundaries.py``) consume — returning them directly is what lets the cycle
push each species exactly ONCE per step (the seed pushed wall-emitting
species twice: once open to find the hits, once more to apply the boundary).

Physics: non-relativistic Boris push, 1D3V. E = (Ex(x), 0, 0) gathered from
the node field; optional constant background B rotates the 3V velocity.
With B = 0 this reduces to v_x += (q/m) E dt; x += v_x dt — exactly the
loops in the paper's Listings 1.1-1.4.
"""

from __future__ import annotations

import dataclasses
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.grid import (Grid1D, deposit_stacked, deposit_flat,
                             gather, gather_onehot)
from repro.core.particles import SpeciesBuffer, StackedSpecies
from repro.obs import tracing

Array = jax.Array

Strategy = Literal["unified", "explicit", "async_batched", "fused"]
# 'open': leave positions raw — the domain-decomposed step routes crossers
# to neighbor domains (decomposition.py) instead of wrapping/absorbing here.
Boundary = Literal["periodic", "absorb", "open"]

STRATEGIES = ("unified", "explicit", "async_batched", "fused")
BOUNDARIES = ("periodic", "absorb", "open")


class PushResult(NamedTuple):
    """What one mover invocation produces.

    ``hit_left`` / ``hit_right`` are per-slot wall masks (all-False unless
    ``boundary='absorb'``); ``rho`` is the post-push charge density and is
    only populated by the fused strategy when a deposit was requested.
    """

    buf: SpeciesBuffer
    hit_left: Array
    hit_right: Array
    diag: dict
    rho: Array | None = None


def boris_kick(v: Array, e_x: Array, qm_dt: Array | float,
               b: Array | tuple[float, float, float] = (0.0, 0.0, 0.0)
               ) -> Array:
    """Boris rotation push. v: (N, 3); e_x: (N,) field at particles.

    ``b`` may be a static (bx, by, bz) tuple — all-zero skips the rotation
    at trace time — or a (3,) array (traced runtime value); an array always
    takes the rotation branch, so callers with a statically-zero field
    should pass the tuple to keep the cheaper program.
    """
    half = 0.5 * qm_dt
    vm = v.at[:, 0].add(half * e_x)              # half electric kick
    if isinstance(b, jax.Array) or any(c != 0.0 for c in b):
        t = jnp.asarray(b, v.dtype) * half
        t2 = jnp.dot(t, t)
        s = 2.0 * t / (1.0 + t2)
        vprime = vm + jnp.cross(vm, t[None, :])
        vp = vm + jnp.cross(vprime, s[None, :])
    else:
        vp = vm
    return vp.at[:, 0].add(half * e_x)           # second half kick


def apply_boundary(x: Array, alive: Array, length: float,
                   boundary: Boundary) -> tuple[Array, Array, Array, Array]:
    """Returns (x, alive, absorbed_left, absorbed_right masks)."""
    if boundary == "open":
        return x, alive, jnp.zeros_like(alive), jnp.zeros_like(alive)
    if boundary == "periodic":
        return jnp.mod(x, length), alive, jnp.zeros_like(alive), \
            jnp.zeros_like(alive)
    hit_l = alive & (x < 0.0)
    hit_r = alive & (x >= length)
    new_alive = alive & ~(hit_l | hit_r)
    # park dead particles inside the domain so cell indices stay valid
    xc = jnp.clip(x, 0.0, jnp.nextafter(jnp.asarray(length, x.dtype),
                                        jnp.asarray(0.0, x.dtype)))
    return xc, new_alive, hit_l, hit_r


def _wall_diag(v: Array, w: Array, hl: Array, hr: Array) -> dict:
    """Divertor diagnostics: particle + energy flux absorbed at each wall."""
    ke = 0.5 * jnp.sum(v * v, axis=-1) * w
    return {
        "absorbed_left": jnp.sum(hl.astype(jnp.int32), axis=-1),
        "absorbed_right": jnp.sum(hr.astype(jnp.int32), axis=-1),
        "power_left": jnp.sum(jnp.where(hl, ke, 0.0), axis=-1),
        "power_right": jnp.sum(jnp.where(hr, ke, 0.0), axis=-1),
    }


def _field_at(x: Array, alive: Array, e: Array, grid: Grid1D,
              gather_mode: str) -> Array:
    """E at the particles (the CIC gather; zero on dead slots)."""
    g = gather_onehot if gather_mode == "onehot" else gather
    return g(grid, e, x) * alive


def _move(x: Array, v: Array, alive: Array, e_x: Array, grid: Grid1D,
          qm_dt: Array | float, dt: Array | float,
          b: tuple[float, float, float], boundary: Boundary):
    """Boris + drift + boundary on raw arrays, given E at the particles."""
    v = boris_kick(v, e_x, qm_dt, b)
    x = x + v[:, 0] * dt
    x, alive, hl, hr = apply_boundary(x, alive, grid.length, boundary)
    return x, v, alive, hl, hr


def _push_core(x: Array, v: Array, alive: Array, e: Array, grid: Grid1D,
               qm_dt: Array | float, dt: Array | float,
               b: tuple[float, float, float], boundary: Boundary,
               gather_mode: str):
    """Gather + Boris + drift + boundary on raw arrays, under the
    ``field_gather`` and ``move`` scopes."""
    with tracing.phase_scope("field_gather"):
        e_x = _field_at(x, alive, e, grid, gather_mode)
    with tracing.phase_scope("move"):
        return _move(x, v, alive, e_x, grid, qm_dt, dt, b, boundary)


def push_unified(buf: SpeciesBuffer, e: Array, grid: Grid1D, qm: float,
                 dt: float, b: tuple[float, float, float] = (0.0, 0.0, 0.0),
                 boundary: Boundary = "periodic",
                 gather_mode: str = "take",
                 qm_dt: Array | None = None) -> PushResult:
    """Pure-jnp mover (XLA-managed data movement — the 'unified' strategy).

    ``qm_dt`` (optional, possibly traced) overrides the host-side ``qm*dt``
    product — the RuntimeParams path supplies it precomputed so the traced
    step stays bit-identical to the constant-folded one.
    """
    x, v, alive, hl, hr = _push_core(buf.x, buf.v, buf.alive, e, grid,
                                     qm * dt if qm_dt is None else qm_dt,
                                     dt, b, boundary, gather_mode)
    diag = _wall_diag(v, buf.w, hl, hr)
    out = dataclasses.replace(buf, x=x, v=v, alive=alive, w=buf.w * alive)
    return PushResult(out, hl, hr, diag)


def push_explicit(buf: SpeciesBuffer, e: Array, grid: Grid1D, qm: float,
                  dt: float, b: tuple[float, float, float] = (0.0, 0.0, 0.0),
                  boundary: Boundary = "periodic",
                  gather_mode: str = "take") -> PushResult:
    """Pallas fused mover (explicit VMEM staging — the 'explicit' strategy)."""
    from repro.kernels import ops  # local import: kernels are optional deps
    x, v, alive, hl, hr = ops.mover_push(
        buf.x, buf.v, buf.alive, e, x0=grid.x0, dx=grid.dx,
        length=grid.length, qm=qm, dt=dt, b=b, boundary=boundary,
        gather_mode=gather_mode)
    diag = _wall_diag(v, buf.w, hl, hr)
    out = dataclasses.replace(buf, x=x, v=v, alive=alive, w=buf.w * alive)
    return PushResult(out, hl, hr, diag)


def push_fused(buf: SpeciesBuffer, e: Array, grid: Grid1D, qm: float,
               dt: float, b: tuple[float, float, float] = (0.0, 0.0, 0.0),
               boundary: Boundary = "periodic", gather_mode: str = "take",
               deposit_charge: float | None = None,
               rho_carry: Array | None = None,
               qm_dt: Array | None = None) -> PushResult:
    """Single-pass push+deposit (the 'fused' strategy).

    When ``deposit_charge`` is given, the POST-push charge density
    ``deposit_charge * w * alive`` lands in ``PushResult.rho`` — computed in
    the same pass over the particle arrays as the push itself, so HBM sees
    them once. On TPU this runs as the ``kernels/fused_cycle.py`` Pallas
    kernel; elsewhere as pure jnp with the flattened deposit.
    ``rho_carry`` seeds the deposit accumulator (the Pallas kernel's VMEM
    accumulator starts from it instead of zeros): callers accumulating a
    multi-call rho — per-queue engine loops, pre-deposited birth charge —
    fold it in without a separate add pass.
    """
    if jax.default_backend() == "tpu":
        if qm_dt is not None:
            raise NotImplementedError(
                "fused Pallas kernel bakes qm/dt as compile-time scalars; "
                "traced qm_dt is unsupported on TPU")
        from repro.kernels import ops
        x, v, alive, hl, hr, w, rho = ops.fused_push_deposit(
            buf.x, buf.v, buf.alive, buf.w, e, rho_carry, x0=grid.x0,
            dx=grid.dx, length=grid.length, qm=qm, dt=dt,
            charge=0.0 if deposit_charge is None else deposit_charge,
            b=b, boundary=boundary, deposit=deposit_charge is not None)
        diag = _wall_diag(v, buf.w, hl, hr)
        out = dataclasses.replace(buf, x=x, v=v, alive=alive, w=w)
        return PushResult(out, hl, hr, diag,
                          rho if deposit_charge is not None else None)

    x, v, alive, hl, hr = _push_core(buf.x, buf.v, buf.alive, e, grid,
                                     qm * dt if qm_dt is None else qm_dt,
                                     dt, b, boundary, gather_mode)
    diag = _wall_diag(v, buf.w, hl, hr)
    w = buf.w * alive
    rho = None
    if deposit_charge is not None:
        with tracing.phase_scope("deposit"):
            rho = deposit_flat(grid, x, deposit_charge * w)
            if rho_carry is not None:
                rho = rho_carry + rho
    out = dataclasses.replace(buf, x=x, v=v, alive=alive, w=w)
    return PushResult(out, hl, hr, diag, rho)


def push_async_batched(buf: SpeciesBuffer, e: Array, grid: Grid1D, qm: float,
                       dt: float, num_batches: int = 4,
                       b: tuple[float, float, float] = (0.0, 0.0, 0.0),
                       boundary: Boundary = "periodic",
                       gather_mode: str = "take",
                       qm_dt: Array | None = None) -> PushResult:
    """Batched mover: scan over particle batches (paper's async extension).

    On one device this pipelines HBM traffic per batch; under shard_map the
    per-batch migration collective of batch k overlaps batch k+1's compute
    (XLA schedules the ppermute async against the next scan body).
    """
    cap = buf.capacity
    if cap % num_batches != 0:
        raise ValueError(
            f"strategy='async_batched' needs the species capacity ({cap}) "
            f"to be divisible by num_batches ({num_batches}); pick a batch "
            f"count that divides every species capacity or pad the buffers")
    bs = cap // num_batches

    def reshape(a):
        return a.reshape((num_batches, bs) + a.shape[1:])

    batched = SpeciesBuffer(x=reshape(buf.x), v=reshape(buf.v),
                            w=reshape(buf.w), alive=reshape(buf.alive))

    def body(carry, sl):
        sbuf = SpeciesBuffer(x=sl[0], v=sl[1], w=sl[2], alive=sl[3])
        out, hl, hr, diag, _ = push_unified(sbuf, e, grid, qm, dt, b,
                                            boundary, gather_mode, qm_dt)
        acc = jax.tree.map(jnp.add, carry, diag)
        return acc, (out.x, out.v, out.w, out.alive, hl, hr)

    # derive the zero carry from the actual per-batch diag structure so the
    # dtypes track whatever the boundary/dtype combination produces
    first = jax.tree.map(lambda a: a[0], batched)
    diag_shape = jax.eval_shape(
        lambda bb: push_unified(bb, e, grid, qm, dt, b, boundary,
                                gather_mode, qm_dt).diag, first)
    zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), diag_shape)
    diag, (x, v, w, alive, hl, hr) = jax.lax.scan(
        body, zero, (batched.x, batched.v, batched.w, batched.alive))

    def unshape(a):
        return a.reshape((cap,) + a.shape[2:])

    out = SpeciesBuffer(x=unshape(x), v=unshape(v), w=unshape(w),
                        alive=unshape(alive))
    return PushResult(out, unshape(hl), unshape(hr), diag)


def push_stacked(st: StackedSpecies, e: Array, grid: Grid1D, qm: Array,
                 dt: Array, b: tuple[float, float, float] = (0.0, 0.0, 0.0),
                 boundary: Boundary = "periodic", gather_mode: str = "take",
                 charges: Array | None = None,
                 rho_carry: Array | None = None
                 ) -> tuple[StackedSpecies, Array, Array, dict, Array | None]:
    """vmap'd Boris push over the species axis of a StackedSpecies.

    ``qm`` and ``dt`` are (S,) per-species arrays (q/m and dt*stride). When
    ``charges`` (S,) is given the post-push TOTAL charge density of all
    species is deposited in the same pass (one flattened deposit)
    and returned as ``rho``; pass None to skip deposition. ``rho_carry``
    seeds the deposit accumulator — the distributed engine threads its
    per-queue rho through here so the accumulation is part of the fused
    in-pass deposit rather than a separate add.

    Returns (stacked, hit_left (S, cap), hit_right (S, cap),
    diag dict of (S,) arrays, rho | None).

    The scopes ``field_gather``, ``move`` and ``deposit`` open outside the
    vmaps: a scope entered inside a vmapped function is named
    ``vmap(<scope>)`` in the op_name.
    """
    with tracing.phase_scope("field_gather"):
        e_x = jax.vmap(lambda x, alive: _field_at(x, alive, e, grid,
                                                  gather_mode))(
            st.x, st.alive)
    with tracing.phase_scope("move"):
        x, v, alive, hl, hr = jax.vmap(
            lambda x, v, alive, e_x, qm_s, dt_s: _move(
                x, v, alive, e_x, grid, qm_s * dt_s, dt_s, b, boundary))(
            st.x, st.v, st.alive, e_x, qm, dt)
        diag = _wall_diag(v, st.w, hl, hr)      # reductions over axis=-1
        w = st.w * alive
    out = StackedSpecies(x=x, v=v, w=w, alive=alive)
    rho = None
    if charges is not None:
        with tracing.phase_scope("deposit"):
            rho = deposit_stacked(grid, x, w, alive, charges)
            if rho_carry is not None:
                rho = rho_carry + rho
    return out, hl, hr, diag, rho


PUSH = {
    "unified": push_unified,
    "explicit": push_explicit,
    "async_batched": push_async_batched,
    "fused": push_fused,
}


def push(buf: SpeciesBuffer, e: Array, grid: Grid1D, qm: float, dt: float,
         strategy: Strategy = "unified", **kw) -> PushResult:
    if strategy not in PUSH:
        raise ValueError(
            f"unknown mover strategy {strategy!r}; valid: {STRATEGIES}")
    return PUSH[strategy](buf, e, grid, qm, dt, **kw)
