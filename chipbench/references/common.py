"""Plain ``jax.numpy`` pieces of the field-free PIC-MC step, written from
the physics and not from the program: the drift push, cloud-in-cell
deposit and gather on a periodic ring of domains, and the bookkeeping that
matches the rows a step created against the rows it should have created.

Particle states are ``{species: (x, v, alive)}`` with x (D, C), v (3, D, C)
(component-major, so that no array has a minor dimension of 3) and alive
(D, C), in each domain's local frame [0, L/D).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Phys:
    """What the reference needs of a cell, from its data files alone."""
    nc: int                  # global cells
    dx: float
    dt: float
    domains: int
    species: tuple           # ({name, charge, mass, vth, ...}, ...)
    ionization: dict | None  # {neutral, electron, ion, rate, vth_e}
    collisions: tuple        # ({kind, species, partner, rate}, ...)
    async_n: int
    max_births: int          # per domain and step
    max_migration: int       # per domain, species, direction and step

    @property
    def ncl(self) -> int:
        return self.nc // self.domains

    @property
    def l_loc(self) -> float:
        return self.ncl * self.dx

    def vth(self, name: str) -> float:
        return float({s["name"]: s for s in self.species}[name]["vth"])


def phys_of(config: dict, traffic: dict, max_births: int) -> Phys:
    g = config["grid"]
    if traffic["field_solve"] or g["boundary"] != "periodic":
        raise NotImplementedError(
            "this reference covers the field-free periodic step only")
    return Phys(nc=int(g["nc"]), dx=float(g["dx"]), dt=float(g["dt"]),
                domains=int(config["domains"]),
                species=tuple(config["species"]),
                ionization=traffic.get("ionization") or None,
                collisions=tuple(traffic.get("collisions", [])),
                async_n=int(traffic["async_n"]), max_births=int(max_births),
                max_migration=int(traffic["max_migration"]))


# diag counters of rows the step refused or lost (births over budget,
# crossers over the send budget, arrivals without a slot)
DROPS = ("birth_overflow", "migration_overflow", "merge_dropped",
         "emission_overflow")


def dropped(diag: dict) -> int:
    return sum(int(np.sum(v)) for k, v in diag.items() if k.endswith(DROPS))


def component_major(state: dict) -> dict:
    """(x, v (D, C, 3), alive) -> (x, v (3, D, C), alive), on the host."""
    return {k: (x, np.ascontiguousarray(np.moveaxis(v, -1, 0)), a)
            for k, (x, v, a) in state.items()}


def drift(x, vx, dt):
    """Field-free push: E = 0 and B = 0 leave v as it is; x moves by
    vx dt, computed in the dtype of its inputs."""
    return x + vx * jnp.asarray(dt, x.dtype)


def node_density(x, alive, ncl: int, dx: float):
    """Number density on each domain's ncl + 1 nodes (cloud-in-cell, unit
    weight), with the node that two neighbouring domains share, and the
    node that closes the periodic ring, holding the sum of both sides.
    x, alive: (D, C). Returns (D, ncl + 1)."""
    s = x / dx
    i = jnp.clip(jnp.floor(s).astype(jnp.int32), 0, ncl - 1)
    f = jnp.clip(s - i, 0.0, 1.0)
    q = alive.astype(x.dtype)

    def one(i_d, f_d, q_d):
        rho = jnp.zeros((ncl + 1,), x.dtype)
        rho = rho.at[i_d].add(q_d * (1.0 - f_d))
        return rho.at[i_d + 1].add(q_d * f_d) / dx

    rho = jax.vmap(one)(i, f, q)
    # node ncl of domain r is node 0 of domain r + 1 (mod D)
    edge = rho[:, -1] + jnp.roll(rho[:, 0], -1)
    rho = rho.at[:, -1].set(edge)
    return rho.at[:, 0].set(jnp.roll(edge, 1))


def gather_nodes(field, x, ncl: int, dx: float):
    """Cloud-in-cell interpolation of a (D, ncl + 1) node field to (D, C)."""
    s = x / dx
    i = jnp.clip(jnp.floor(s).astype(jnp.int32), 0, ncl - 1)
    f = jnp.clip(s - i, 0.0, 1.0)
    take = jax.vmap(lambda fd, idx: fd[idx])
    return take(field, i) * (1.0 - f) + take(field, i + 1) * f


@partial(jax.jit, static_argnames=("size",))
def pack(mask, cols, size: int):
    """The first ``size`` rows of each domain where ``mask`` holds:
    (valid (D, size), [col (D, size) for col in cols])."""
    def one(m, *cs):
        idx = jnp.nonzero(m, size=size, fill_value=m.shape[0])[0]
        ok = idx < m.shape[0]
        idx = jnp.minimum(idx, m.shape[0] - 1)
        return (ok,) + tuple(c[idx] for c in cs)
    out = jax.vmap(one)(mask, *cols)
    return out[0], list(out[1:])


# the gap reported when one set is empty and the other is not
NO_MATCH = 1e30


def match_rows(got: np.ndarray, want: np.ndarray, known: np.ndarray,
               scale: np.ndarray) -> float:
    """Widest gap between two sets of rows, each (n, k) with column 0 the
    position: every row of each set is set against the row of the other
    set nearest to it in position, and the gap is the larger of the
    position gap and, where the wanted row's other columns are determined
    (``known``; a newborn electron's velocity is drawn, not determined) and
    no other wanted row shares its position, the gap in those columns.
    ``scale`` (k,) divides each column's gap. Sets of different sizes give
    a gap too; the caller counts the difference in size apart."""
    if len(got) == 0 or len(want) == 0:
        return 0.0 if len(got) == len(want) else NO_MATCH
    ow = np.argsort(want[:, 0], kind="stable")
    w, kn = want[ow], known[ow]
    uniq = np.ones(len(w), bool)
    uniq[1:] &= w[1:, 0] != w[:-1, 0]
    uniq[:-1] &= w[:-1, 0] != w[1:, 0]
    kn = kn & uniq

    def nearest(xs, table):
        if len(table) == 1:
            return np.zeros(len(xs), int)
        i = np.clip(np.searchsorted(table, xs), 1, len(table) - 1)
        return np.where(np.abs(xs - table[i - 1]) <= np.abs(xs - table[i]),
                        i - 1, i)

    j = nearest(got[:, 0], w[:, 0])
    gap = np.abs(got[:, 0] - w[j, 0]) / scale[0]
    rest = np.abs(got[:, 1:] - w[j, 1:]) / scale[1:]
    gap = np.maximum(gap, np.where(kn[j], rest.max(axis=1), 0.0))
    gs = np.sort(got[:, 0])
    back = np.abs(w[:, 0] - gs[nearest(w[:, 0], gs)]) / scale[0]
    return float(max(gap.max(), back.max()))


def route(ok, x, cols, l_loc: float, domains: int) -> list:
    """Rows that left their domain, moved to the neighbour they enter and
    into its frame: for each destination domain a list of (n, 1 + k) row
    blocks (position, then ``cols``). ok, x: (D, n); cols: (D, n, k);
    host arrays."""
    out = [[] for _ in range(domains)]
    for d in range(domains):
        left = x[d] < 0.0
        for dest, sel, shift in (((d - 1) % domains, ok[d] & left, l_loc),
                                 ((d + 1) % domains, ok[d] & ~left, -l_loc)):
            out[dest].append(np.concatenate(
                [(x[d][sel] + np.float32(shift))[:, None], cols[d][sel]],
                axis=1).astype(np.float32))
    return out


def land(x, v, alive, rows: list, dead) -> None:
    """Write each domain's new rows (position, v) into slots that were dead
    before the step (a slot freed in a step is reused only after it).
    x, alive: (D, C); v: (3, D, C); rows: per domain, blocks as ``route``
    gives them; in place, on the host."""
    for d, blocks in enumerate(rows):
        if not blocks:
            continue
        r = np.concatenate(blocks)
        free = np.flatnonzero(dead[d])[:len(r)]
        r = r[:len(free)]
        x[d, free], v[:, d, free], alive[d, free] = r[:, 0], r[:, 1:].T, True
