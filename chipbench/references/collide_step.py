"""Reference of one field-free step with BIT1's binary-collision menu and
no ionization: drift push, migration round a periodic ring of domains,
then, inside each cell of each domain and among the rows of each async
queue, elastic e-D scattering (speed kept), D+-D charge exchange (a
velocity swap) and e-e Coulomb scattering (Takizuka-Abe pairs, momentum
and energy kept). Crossers do not collide in the step they cross.

``compare`` reads one step of the program and returns:

* ``x_err``, ``new_err``, ``lost_rows``, ``count_err``: as in
  ``ionize_step`` (no row may vanish in place; the created rows are the
  arrivals, whose velocities the step must leave as they were);
* ``e_energy_err``: widest relative change of a cell's electron kinetic
  energy (elastic and Coulomb keep it);
* ``hd_swap_err``: widest change of a cell's D+ and D momentum (against
  the sum of speeds) and energy (relative): charge exchange keeps both;
* ``cx_rows``: rows of D+ and of D whose velocity changed, against the
  swaps the step reports (exact);
* ``coulomb_pairs``: pairs the step reports against the floor(n/2) pairs
  of every (queue, cell) (exact);
* ``elastic_z``, ``cx_z``: reported events against sum(P) over eligible
  rows, P = 1 - exp(-n_partner(cell) rate dt), n_partner from the
  partner's rows before the push, in standard deviations;
* ``e_rows``: electron rows that stayed in their slot and whose velocity
  changed, against what the menu changes: every row of the floor(n/2)
  Coulomb pairs of each (queue, cell), and at most the one unpaired row of
  an odd cell besides. Paired rows left unchanged plus changed rows past
  that bound, as a share of the paired rows;
* ``coulomb_kick_z``: the size of the electron kicks. Elastic scattering
  keeps each speed, so W = sum over rows that stayed of
  (|v'|^2 - |v|^2)^2 reads the Coulomb kicks alone. The program's W
  against W of the reference's own draw of the electron menu on the same
  rows (its pairs, deflections and azimuths), in standard deviations of
  their difference (2 sum t^2 for each side: a pair's two rows read
  alike).

``step`` is the same physics as a program of its own in a given dtype; in
bfloat16 it is the control.
"""

from __future__ import annotations

from functools import partial

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.common import (drift, dropped, land, match_rows,
                                         pack, route)


def _cells(x, ok, ncl: int, dx: float):
    c = jnp.clip(jnp.floor(x / dx).astype(jnp.int32), 0, ncl - 1)
    return jnp.where(ok, c, ncl)


def _per_cell(vals, cell, ncl: int):
    """(D, C) values summed into (D, ncl + 1) cells (the last is the
    sentinel of ineligible rows)."""
    return jax.vmap(lambda v, c: jnp.zeros((ncl + 1,), v.dtype).at[c].add(
        v))(vals, cell)


@partial(jax.jit, static_argnames=("l_loc", "ncl", "dx"))
def _moved(xb, vb, ab, xa, va, aa, dt, l_loc, ncl, dx):
    xr = drift(xb, vb[0], dt)
    inside = (xr >= 0.0) & (xr < l_loc)
    keep = ab & aa
    x_err = jnp.max(jnp.where(keep, jnp.abs(xa - xr), 0.0))
    stuck = jnp.sum(ab & aa & ~inside)
    gone = jnp.sum(ab & ~aa & inside)
    elig = keep & inside
    cell = _cells(xr, elig, ncl, dx)
    changed = jnp.sum(elig & jnp.any(va != vb, axis=0))
    # crossers keep their velocity; their arrival rows are matched apart
    return x_err, stuck, gone, xr, inside, elig, cell, changed


@partial(jax.jit, static_argnames=("ncl",))
def _energy(vb, va, cell, ncl):
    kb = _per_cell(jnp.sum(vb * vb, axis=0), cell, ncl)[:, :ncl]
    ka = _per_cell(jnp.sum(va * va, axis=0), cell, ncl)[:, :ncl]
    pb = jnp.stack([_per_cell(vb[i], cell, ncl)[:, :ncl] for i in range(3)])
    pa = jnp.stack([_per_cell(va[i], cell, ncl)[:, :ncl] for i in range(3)])
    sb = _per_cell(jnp.sqrt(jnp.sum(vb * vb, axis=0)), cell, ncl)[:, :ncl]
    return kb, ka, pb, pa, sb


def _density_before(x, a, ncl: int, dx: float, l_loc: float):
    """Rows per cell (weight 1) of a species before the push, per dx."""
    ok = a & (x >= 0.0) & (x < l_loc)
    return _per_cell(ok.astype(jnp.float32), _cells(x, ok, ncl, dx),
                     ncl)[:, :ncl] / dx


@partial(jax.jit, static_argnames=("ncl",))
def _expect(n_cell, cell, rate, dt, ncl):
    """Mean and variance of an event count over eligible rows whose cell
    partner density is ``n_cell``."""
    dens = jnp.concatenate([n_cell, jnp.zeros_like(n_cell[:, :1])], axis=1)
    at = jax.vmap(lambda d, c: d[c])(dens, cell)
    p = jnp.where(cell < ncl, -jnp.expm1(-at * rate * dt), 0.0)
    return jnp.sum(p), jnp.sum(p * (1.0 - p))


@partial(jax.jit, static_argnames=("ncl", "n_q"))
def _pairs(cell, ncl, n_q):
    """Sums over (queue, cell) of floor(rows / 2) and of rows mod 2; slot
    c is in queue c % n_q."""
    pairs = odd = 0
    for q in range(n_q):
        cq = cell[:, q::n_q]
        n = _per_cell(jnp.ones(cq.shape, jnp.int32), cq, ncl)[:, :ncl]
        pairs = pairs + jnp.sum(n // 2)
        odd = odd + jnp.sum(n % 2)
    return pairs, odd


@jax.jit
def _kick_moments(vb, va, keep):
    """Over the ``keep`` rows, t = (|va|^2 - |vb|^2)^2: (sum t, sum t^2)."""
    d = jnp.sum((va - vb) * (va + vb), axis=0)
    t = jnp.where(keep, d * d, 0.0)
    return jnp.sum(t), jnp.sum(t * t)


def _electron_draw(phys, key, e: str, before: dict, moved: dict):
    """The reference's own draw of the menu entries that change electron
    velocities (elastic, then Coulomb), on the electrons that stayed:
    v after it, (3, D, C)."""
    menu = tuple(c for c in phys.collisions if c["species"] == e)
    ph = dataclasses.replace(phys, collisions=menu)
    need = {e} | {c["partner"] for c in menu if c.get("partner")}
    dens = {s: _density_before(before[s][0], before[s][2], phys.ncl,
                               phys.dx, phys.l_loc) for s in need}
    xr, inside = moved[e][0], moved[e][1]
    elig = before[e][2] & inside
    draw = jax.jit(lambda k, x, v, ok, n: _collide_domain(
        k, {e: x}, {e: v}, {e: ok}, n, ph, jnp.float32)[0][e])
    keys = jax.random.split(key, phys.domains)
    return jnp.stack([draw(keys[d], xr[d], before[e][1][:, d], elig[d],
                           {s: dens[s][d] for s in need})
                      for d in range(phys.domains)], axis=1), elig


def compare(phys, before: dict, after: dict, diag: dict, key) -> dict:
    """The numbers of one step (see the module docstring); ``key`` seeds
    the reference's own draws."""
    sp = [s["name"] for s in phys.species]
    menu = {c["kind"]: c for c in phys.collisions}
    ncl, dx, l_loc, D = phys.ncl, phys.dx, phys.l_loc, phys.domains
    counted = {s: int(np.sum(after[s][2])) for s in sp}
    before = {s: tuple(jnp.asarray(a) for a in before[s]) for s in sp}
    after = {s: tuple(jnp.asarray(a) for a in after[s]) for s in sp}
    out = {"x_err": 0.0, "new_err": 0.0, "lost_rows": 0}
    moved = {}
    for s in sp:
        xb, vb, ab = before[s]
        xa, va, aa = after[s]
        x_err, stuck, gone, xr, inside, elig, cell, changed = _moved(
            xb, vb, ab, xa, va, aa, np.float32(phys.dt), l_loc, ncl, dx)
        out["x_err"] = max(out["x_err"], float(x_err))
        out["lost_rows"] += int(stuck) + int(gone)
        moved[s] = (xr, inside, cell, int(changed))
        # created rows: the arrivals of every domain's crossers
        size = 2 * phys.max_migration
        ok_g, got = pack(~ab & aa, [xa, va[0], va[1], va[2]], size)
        ok_w, want = pack(ab & ~inside, [xr, vb[0], vb[1], vb[2]], size)
        ok_g, ok_w = np.asarray(ok_g), np.asarray(ok_w)
        got = [np.asarray(c) for c in got]
        want = [np.asarray(c) for c in want]
        out["lost_rows"] += int(jnp.sum(~ab & aa)) - int(ok_g.sum())
        out["lost_rows"] += int(jnp.sum(ab & ~inside)) - int(ok_w.sum())
        rows = route(ok_w, want[0], np.stack(want[1:], axis=-1), l_loc, D)
        scale = np.array([dx] + [phys.vth(s)] * 3, np.float32)
        for d in range(D):
            g = np.stack([c[d][ok_g[d]] for c in got], axis=1)
            w = np.concatenate(rows[d])
            out["lost_rows"] += abs(len(g) - len(w))
            out["new_err"] = max(out["new_err"], match_rows(
                g, w, np.ones(len(w), bool), scale))
    out["lost_rows"] += dropped(diag)
    out["count_err"] = sum(abs(int(diag[f"{s}/count"]) - counted[s])
                           for s in sp)

    # energy and momentum per cell
    def cell_sums(names):
        tot = None
        for s in names:
            r = _energy(before[s][1], after[s][1], moved[s][2], ncl)
            tot = r if tot is None else tuple(a + b for a, b in zip(tot, r))
        return tot

    e = menu["elastic"]["species"] if "elastic" in menu else \
        menu["coulomb"]["species"]
    kb, ka, _, _, _ = cell_sums([e])
    out["e_energy_err"] = float(jnp.max(jnp.abs(ka - kb)
                                        / jnp.maximum(kb, 1e-30)))
    cx = menu.get("charge_exchange")
    if cx:
        ion, neu = cx["species"], cx["partner"]
        kb, ka, pb, pa, sb = cell_sums([ion, neu])
        mom = jnp.max(jnp.abs(pa - pb), axis=0) / jnp.maximum(sb, 1e-30)
        ene = jnp.abs(ka - kb) / jnp.maximum(kb, 1e-30)
        out["hd_swap_err"] = float(jnp.maximum(jnp.max(mom), jnp.max(ene)))
        n_cx = int(diag["coll_cx"])
        out["cx_rows"] = abs(moved[ion][3] - n_cx) + abs(moved[neu][3] - n_cx)
        dens = _density_before(before[neu][0], before[neu][2], ncl, dx,
                               l_loc)
        mean, var = _expect(dens, moved[ion][2], cx["rate"], phys.dt, ncl)
        out["cx_z"] = (n_cx - float(mean)) / max(float(var), 1e-30) ** 0.5
    el = menu.get("elastic")
    if el:
        dens = _density_before(before[el["partner"]][0],
                               before[el["partner"]][2], ncl, dx, l_loc)
        mean, var = _expect(dens, moved[el["species"]][2], el["rate"],
                            phys.dt, ncl)
        out["elastic_z"] = (int(diag["coll_elastic"]) - float(mean)) / max(
            float(var), 1e-30) ** 0.5
    co = menu.get("coulomb")
    if co:
        e = co["species"]
        pairs, odd = (int(a) for a in _pairs(moved[e][2], ncl, phys.async_n))
        out["coulomb_pairs"] = abs(int(diag["coll_coulomb"]) - pairs)
        changed = moved[e][3]
        out["e_rows"] = (max(0, 2 * pairs - changed)
                         + max(0, changed - 2 * pairs - odd)) / max(
                             1, 2 * pairs)
        keep = before[e][2] & after[e][2] & moved[e][1]
        w_p, q_p = _kick_moments(before[e][1], after[e][1], keep)
        v_ref, elig = _electron_draw(phys, key, e, before, moved)
        w_r, q_r = _kick_moments(before[e][1], v_ref, elig)
        out["coulomb_kick_z"] = float(w_p - w_r) / max(
            2.0 * float(q_p + q_r), 1e-30) ** 0.5
    return out


def _group_order(key, group, n_groups):
    """Rows sorted by group, in random order within each group, and each
    group's (count, start) in that order. ``group == n_groups`` marks rows
    that take no part (sorted to the tail)."""
    u = jax.random.uniform(key, group.shape)
    order = jnp.lexsort((u, group))
    counts = jnp.zeros((n_groups + 1,), jnp.int32).at[group].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    return order, counts, starts


def _rotate(u, delta, phi):
    """Takizuka-Abe: u turned through theta, tan(theta / 2) = delta, about
    azimuth phi; returns u' - u (|u'| = |u|). u: (3, M)."""
    ux, uy, uz = u
    one = jnp.ones_like(delta)
    sin_t = 2.0 * delta / (one + delta * delta)
    omc = 2.0 * delta * delta / (one + delta * delta)
    up = jnp.sqrt(ux * ux + uy * uy)
    um = jnp.sqrt(up * up + uz * uz)
    safe = up > 0
    upn = jnp.where(safe, up, one)
    c, s = jnp.cos(phi), jnp.sin(phi)
    dx_ = jnp.where(safe, ux / upn * uz * sin_t * c - uy / upn * um * sin_t * s,
                    uz * sin_t * c) - ux * omc
    dy_ = jnp.where(safe, uy / upn * uz * sin_t * c + ux / upn * um * sin_t * s,
                    uz * sin_t * s) - uy * omc
    dz_ = jnp.where(safe, -up * sin_t * c, 0.0) - uz * omc
    return jnp.stack([dx_, dy_, dz_])


def _collide_domain(key, x, v, elig, n_part, phys, dtype):
    """The menu on one domain: x, v, elig of every species (post-push),
    n_part the partner densities per cell. Returns (v, diag)."""
    ncl, n_q, dt = phys.ncl, phys.async_n, jnp.asarray(phys.dt, dtype)
    cells = {s: _cells(x[s], elig[s], ncl, phys.dx) for s in x}
    q = {s: jnp.arange(x[s].shape[0]) % n_q for s in x}
    group = {s: jnp.where(elig[s], q[s] * ncl + cells[s], n_q * ncl)
             for s in x}
    v = dict(v)
    diag = {}
    for k, c in enumerate(phys.collisions):
        key, ka, kb, kc, kd = jax.random.split(key, 5)
        s = c["species"]
        rate = jnp.asarray(c["rate"], dtype)
        if c["kind"] == "elastic":
            dens = jnp.concatenate([n_part[c["partner"]], jnp.zeros((1,),
                                                                   dtype)])
            p = 1.0 - jnp.exp(-dens[cells[s]] * rate * dt)
            hit = elig[s] & (jax.random.uniform(ka, p.shape, dtype) < p)
            speed = jnp.sqrt(jnp.sum(v[s] * v[s], axis=0))
            ct = jax.random.uniform(kb, p.shape, dtype, -1.0, 1.0)
            ph = jax.random.uniform(kc, p.shape, dtype, 0.0, 2 * jnp.pi)
            st = jnp.sqrt(jnp.maximum(0.0, 1.0 - ct * ct))
            new = speed * jnp.stack([ct, st * jnp.cos(ph), st * jnp.sin(ph)])
            v[s] = jnp.where(hit, new, v[s])
            diag["coll_elastic"] = jnp.sum(hit)
        elif c["kind"] == "charge_exchange":
            n = c["partner"]
            dens = jnp.concatenate([n_part[n], jnp.zeros((1,), dtype)])
            p = 1.0 - jnp.exp(-dens[cells[s]] * rate * dt)
            hit = elig[s] & (jax.random.uniform(ka, p.shape, dtype) < p)
            g_i = jnp.where(hit, group[s], n_q * ncl)
            oi, _, si = _group_order(kb, g_i, n_q * ncl)
            on, cn, sn = _group_order(kc, group[n], n_q * ncl)
            gi = g_i[oi]
            rank = jnp.arange(gi.shape[0]) - si[gi]
            ok = (gi < n_q * ncl) & (rank < cn[gi])
            partner = on[jnp.clip(sn[gi] + rank, 0, gi.shape[0] - 1)]
            ion_rows = jnp.where(ok, oi, v[s].shape[1])
            nrows = jnp.where(ok, partner, v[n].shape[1])
            vi, vn = v[s][:, oi], v[n][:, partner]
            v[s] = v[s].at[:, ion_rows].set(vn, mode="drop")
            v[n] = v[n].at[:, nrows].set(vi, mode="drop")
            diag["coll_cx"] = jnp.sum(ok)
        else:
            dens = jnp.concatenate([n_part[s], jnp.zeros((1,), dtype)])
            o, cnt, st_ = _group_order(ka, group[s], n_q * ncl)
            g = group[s][o]
            off = jnp.arange(g.shape[0]) - st_[g]
            nxt = jnp.minimum(jnp.arange(g.shape[0]) + 1, g.shape[0] - 1)
            head = (g < n_q * ncl) & (off % 2 == 0) & (off + 1 < cnt[g])
            a, b = o, o[nxt]
            u = v[s][:, a] - v[s][:, b]
            um = jnp.sqrt(jnp.sum(u * u, axis=0))
            var = rate * dens[cells[s][a]] * dt / jnp.maximum(um ** 3, 1e-12)
            delta = jnp.sqrt(var) * jax.random.normal(kb, um.shape, dtype)
            phi = jax.random.uniform(kc, um.shape, dtype, 0.0, 2 * jnp.pi)
            du = jnp.where(head, _rotate(u, delta, phi), 0.0)
            v[s] = v[s].at[:, a].add(0.5 * du).at[:, b].add(-0.5 * du)
            diag["coll_coulomb"] = jnp.sum(head)
    return v, diag


def step(phys, before: dict, key, dtype) -> tuple[dict, dict]:
    """One step of the reference as a program of its own, every float in
    ``dtype`` (the control in bfloat16). Returns (effective state after,
    diag), float32 and component-major on the host."""
    sp = [s["name"] for s in phys.species]
    D, ncl, l_loc, dx = phys.domains, phys.ncl, phys.l_loc, phys.dx
    cast = lambda a: jnp.asarray(a).astype(dtype)
    dt = jnp.asarray(phys.dt, dtype)
    x, v, elig, alive = {}, {}, {}, {}
    n_part = {}
    for s in sp:
        xb, vb, ab = before[s]
        x0 = cast(xb)
        ok = jnp.asarray(ab) & (x0 >= 0) & (x0 < l_loc)
        n_part[s] = _per_cell(ok.astype(dtype), _cells(x0, ok, ncl, dx),
                              ncl)[:, :ncl] / jnp.asarray(dx, dtype)
        x[s] = drift(x0, cast(vb[0]), dt)
        v[s] = cast(vb)
        alive[s] = jnp.asarray(ab)
        elig[s] = alive[s] & (x[s] >= 0) & (x[s] < l_loc)
    diag = {}
    keys = jax.random.split(key, D)
    vs = {s: [] for s in sp}
    for d in range(D):
        vd, dd = _collide_domain(
            keys[d], {s: x[s][d] for s in sp},
            {s: v[s][:, d] for s in sp}, {s: elig[s][d] for s in sp},
            {s: n_part[s][d] for s in sp}, phys, dtype)
        for s in sp:
            vs[s].append(vd[s])
        for k, val in dd.items():
            diag[k] = diag.get(k, 0) + int(val)
    after = {}
    for s in sp:
        v32 = np.array(jnp.stack(vs[s], axis=1).astype(jnp.float32))
        x32 = np.array(x[s].astype(jnp.float32))
        al = np.array(elig[s])
        a0 = np.asarray(before[s][2])
        # crossers do not collide: they arrive with the velocity they had
        rows = route(a0 & ~al, x32, np.moveaxis(np.asarray(before[s][1]), 0, -1),
                     l_loc, D)
        land(x32, v32, al, rows, ~a0)
        after[s] = (x32, v32, al)
        diag[f"{s}/count"] = int(al.sum())
    return after, diag
