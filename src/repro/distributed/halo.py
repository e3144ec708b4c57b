"""Halo-exchange field phase + ring-communication primitives.

BIT1's MPI field assembly ships every rank's full density slab to every
other rank (an ``MPI_Allgather``) and solves the global Poisson system
redundantly on each. The seed's ``core/decomposition.py`` reproduced that:
an ``all_gather`` of the whole (ng_local,) rho on every device, every step —
O(D * ng_local) wire traffic and a redundant O(ng_global) solve per device.

This module replaces it with a locality-preserving field phase in which no
collective ever carries more than a few scalars per domain:

* ``halo_sum``         — the shared edge node between neighboring slabs holds
  only the local partial deposit on each side; one edge-node ``ppermute``
  pair makes both copies carry the full global value.
* ``smooth_halo``      — the (1/4, 1/2, 1/4) binomial smoother needs exactly
  one halo node per side per pass; exchanged with edge ``ppermute``.
* ``solve_poisson_halo`` — the exact double-prefix-sum Dirichlet solve
  (``core/fields.solve_poisson``) distributed: each domain cumsums its own
  slab and the cross-domain carry is an ``all_gather`` of ONE SCALAR block
  total per prefix pass (O(D), never O(D * ng_local)).
* ``efield_halo``      — centered E = -dphi/dx with one phi halo per side.

Everything here runs *inside* ``shard_map``: arguments are the per-device
local slabs, ``axis_names`` the mesh axes carrying the domain decomposition.
The global system is Dirichlet regardless of the particle boundary, so the
ring wraps of edge domains are masked with ``is_first`` / ``is_last`` (the
one-sided wall stencils take over there).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.obs import tracing

Array = jax.Array


def rank(axis_names) -> Array:
    """Linearized domain index over possibly-multiple mesh axes."""
    r = jnp.zeros((), jnp.int32)
    for a in axis_names:
        r = r * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return r


def ring_perm(axis_names, shift: int, mesh: Mesh):
    """Ring permutation over the linearized domain axes."""
    d = 1
    for a in axis_names:
        d *= mesh.shape[a]
    return [(i, (i + shift) % d) for i in range(d)]


def ppermute_tree(tree, axis_names, shift: int, mesh: Mesh):
    perm = ring_perm(axis_names, shift, mesh)
    with tracing.phase_scope("halo/ppermute"):
        return jax.tree.map(
            lambda a: jax.lax.ppermute(a, axis_names, perm), tree)


def neighbor_vals(send_left: Array, send_right: Array, axis_names, mesh: Mesh,
                  is_first: Array, is_last: Array, fill=0.0
                  ) -> tuple[Array, Array]:
    """One halo-exchange round: returns (from_left, from_right).

    ``send_left`` travels to the left neighbor, ``send_right`` to the right;
    each domain receives its right neighbor's ``send_left`` as ``from_right``
    and its left neighbor's ``send_right`` as ``from_left``. The ring wraps,
    so the values arriving across the global walls are replaced by ``fill``.
    """
    from_left = ppermute_tree(send_right, axis_names, +1, mesh)
    from_right = ppermute_tree(send_left, axis_names, -1, mesh)
    from_left = jnp.where(is_first, fill, from_left)
    from_right = jnp.where(is_last, fill, from_right)
    return from_left, from_right


def gather_scalars(x: Array, axis_names) -> Array:
    """(D,) vector of one scalar per domain, ordered by linearized rank.

    This is the ONLY all_gather in the halo field phase, and its payload is a
    single scalar — the jaxpr inspection test asserts exactly that.
    """
    g = jax.lax.all_gather(x, axis_names, tiled=False)
    return g.reshape(-1)


def halo_sum(rho: Array, axis_names, mesh: Mesh, is_first: Array,
             is_last: Array) -> Array:
    """Complete the shared edge nodes of a locally-deposited density.

    Domain r's node ``ncl`` and domain r+1's node 0 are the same global node;
    after a local deposit each copy holds only the particles of its own slab.
    Exchange the two partials so both copies carry the full sum.
    """
    with tracing.phase_scope("halo/sum"):
        from_left, from_right = neighbor_vals(rho[0], rho[-1], axis_names,
                                              mesh, is_first, is_last)
        return rho.at[0].add(from_left).at[-1].add(from_right)


def smooth_halo(f: Array, passes: int, axis_names, mesh: Mesh,
                is_first: Array, is_last: Array) -> Array:
    """Distributed (1/4, 1/2, 1/4) binomial smoother (BIT1's filter).

    Matches ``fields.smooth_binomial`` on the assembled global array: interior
    nodes use the centered stencil with one exchanged halo node per side;
    the global walls use the integral-conserving (3/4, 1/4) one-sided stencil.
    """
    with tracing.phase_scope("halo/smooth"):
        for _ in range(passes):
            # my left halo is the left neighbor's f[-2] (f[0]/f[-1] are the
            # shared copies); my right halo is the right neighbor's f[1]
            hl, hr = neighbor_vals(f[1], f[-2], axis_names, mesh,
                                   is_first, is_last)
            ext = jnp.concatenate([hl[None], f, hr[None]])
            out = 0.25 * ext[:-2] + 0.5 * ext[1:-1] + 0.25 * ext[2:]
            out = out.at[0].set(
                jnp.where(is_first, 0.75 * f[0] + 0.25 * f[1], out[0]))
            out = out.at[-1].set(
                jnp.where(is_last, 0.25 * f[-2] + 0.75 * f[-1], out[-1]))
            f = out
    return f


def solve_poisson_halo(rho: Array, dx: float, eps0: float, axis_names,
                       mesh: Mesh, phi_left: float = 0.0,
                       phi_right: float = 0.0) -> Array:
    """Distributed exact solve of -phi'' = rho/eps0 (Dirichlet walls).

    The single-domain solver (``fields.solve_poisson``) is two chained prefix
    sums. Each becomes: a local cumsum over the owned slab plus a carry-in
    equal to the sum of the earlier domains' block totals — D scalars moved
    per pass, assembled from ``gather_scalars``. With D=1 this reduces
    bitwise to the single-domain solver (offsets are exact zeros).
    """
    with tracing.phase_scope("halo/poisson"):
        ngl = rho.shape[0]
        ncl = ngl - 1                   # owned nodes per domain (non-overlap)
        d = 1
        for a in axis_names:
            d *= mesh.shape[a]
        r = rank(axis_names)
        earlier = jnp.arange(d) < r     # domains left of mine

        f = rho * (dx * dx) / eps0
        # ---- first prefix: S1_i = sum_{k<=i} f_k ----
        c1 = jnp.cumsum(f)
        t1 = c1[ncl - 1]                # block total over my owned nodes
        off1 = jnp.sum(
            jnp.where(earlier, gather_scalars(t1, axis_names), 0.0))
        s1 = off1 + c1
        # global f_0 enters every interior equation; broadcast from domain 0
        f0 = jax.lax.psum(jnp.where(r == 0, f[0], 0.0), axis_names)
        inner = s1 - f0                 # sum_{k=1..i} f_k
        # ---- second prefix: S2_i = sum_{j<=i} inner_j ----
        c2 = jnp.cumsum(inner)
        t2 = c2[ncl - 1]
        t2s = gather_scalars(t2, axis_names)
        off2 = jnp.sum(jnp.where(earlier, t2s, 0.0))
        s2 = off2 + c2
        # S2_{i-1}: shift by one; the carry-in IS S2 at my left edge minus one
        s2m1 = jnp.concatenate([off2[None], s2[:-1]])

        n = d * ncl                     # ng_global - 1
        s2_last = jnp.sum(t2s)          # S2 at global node ng-2
        g0 = (phi_right - phi_left + s2_last) / n
        i_glob = (r * ncl + jnp.arange(ngl)).astype(f.dtype)
        phi = phi_left + i_glob * g0 - s2m1
        # enforce boundaries exactly against rounding (edge domains only)
        phi = phi.at[0].set(jnp.where(r == 0, phi_left, phi[0]))
        phi = phi.at[-1].set(jnp.where(r == d - 1, phi_right, phi[-1]))
        return phi


def efield_halo(phi: Array, dx: float, axis_names, mesh: Mesh,
                is_first: Array, is_last: Array) -> Array:
    """E = -dphi/dx: centered with exchanged phi halos, one-sided at walls."""
    with tracing.phase_scope("halo/efield"):
        hl, hr = neighbor_vals(phi[1], phi[-2], axis_names, mesh,
                               is_first, is_last)
        ext = jnp.concatenate([hl[None], phi, hr[None]])
        e = -(ext[2:] - ext[:-2]) / (2.0 * dx)
        e = e.at[0].set(jnp.where(is_first, -(phi[1] - phi[0]) / dx, e[0]))
        e = e.at[-1].set(
            jnp.where(is_last, -(phi[-1] - phi[-2]) / dx, e[-1]))
        return e


def field_phase(rho_local: Array, *, dx: float, eps0: float,
                smoothing_passes: int, axis_names, mesh: Mesh,
                is_first: Array, is_last: Array) -> Array:
    """Local-deposit rho -> halo-sum -> smooth -> Poisson -> local E slab.

    The all_gather-free replacement for the seed's ``global_field``: every
    collective is either an edge-node ppermute or a scalar gather.
    """
    rho = halo_sum(rho_local, axis_names, mesh, is_first, is_last)
    rho = smooth_halo(rho, smoothing_passes, axis_names, mesh,
                      is_first, is_last)
    phi = solve_poisson_halo(rho, dx, eps0, axis_names, mesh)
    return efield_halo(phi, dx, axis_names, mesh, is_first, is_last)
