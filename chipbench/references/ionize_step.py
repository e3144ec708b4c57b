"""Reference of one field-free step with MC electron-impact ionization
(the paper's §3.3 test): drift push, migration round a periodic ring of
domains, and e + D -> 2e + D+ at P = 1 - exp(-n_e(x) R dt).

``compare`` reads one step of the program (the effective state before and
after it, and its diag) and returns the numbers that decide ``correct``:

* ``x_err``      widest |x - (x0 + vx0 dt)| over rows that stayed in
                 their slot, in cells (the mover's positions);
* ``v_err``      widest |v - v0| / vth over those rows (field off: the
                 mover leaves v as it is);
* ``new_err``    widest gap between the rows the step created (arrivals
                 from the neighbours, ionization births) and the rows the
                 reference says it should have created: position in cells,
                 velocity in vth where the physics fixes it;
* ``ionize_z``   ionizations drawn against the expectation sum(P) over
                 the neutrals, in standard deviations (the n_e deposit,
                 the gather and the event draw);
* ``lost_rows``  rows unaccounted for: particles that vanished without
                 leaving or ionizing, crossers left in place, a created
                 set of the wrong size, anything dropped or refused.
* ``count_err``  the step's diag counts against the rows alive after it.
* ``birth_v_z``  the newborn electrons' velocities against a Maxwellian of
                 ``vth_e``: the z-score of each component's mean and of
                 the sum of v^2, the one farthest from 0 (signed).

``step`` is the same physics as a program of its own, in a given dtype:
in bfloat16 it is the control that ``compare`` must refuse.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.common import (drift, dropped, gather_nodes,
                                         land, match_rows, node_density,
                                         pack, route)


@partial(jax.jit, static_argnames=("l_loc",))
def _stay(xb, vb, ab, xa, va, aa, dt, l_loc):
    """Per-species reductions over the whole buffers (on the device)."""
    xr = drift(xb, vb[0], dt)
    inside = (xr >= 0.0) & (xr < l_loc)
    keep = ab & aa
    x_err = jnp.max(jnp.where(keep, jnp.abs(xa - xr), 0.0))
    v_err = jnp.max(jnp.where(keep, jnp.max(jnp.abs(va - vb), axis=0), 0.0))
    stuck = jnp.sum(ab & aa & ~inside)
    gone = ab & ~aa & inside
    return x_err, v_err, stuck, jnp.sum(gone), xr, inside, gone


def _expected_new(phys, sp, before, after_xr, pk_size):
    """Rows the step should create, per domain: arrivals of every
    species' crossers from the neighbours, and the ionization births.
    Returns {species: [(D) list of (rows (n, 4), known (n,))]}."""
    D = phys.domains
    want = {s: [[] for _ in range(D)] for s in sp}
    for s in sp:
        xr, inside, _ = after_xr[s]
        x, v, a = before[s]
        ok, (xs, v0, v1, v2) = pack(a & ~inside, [xr, v[0], v[1], v[2]],
                                    pk_size)
        cols = np.stack([np.asarray(c) for c in (v0, v1, v2)], axis=-1)
        for dest, blocks in enumerate(route(np.asarray(ok), np.asarray(xs),
                                            cols, phys.l_loc, D)):
            want[s][dest] += [(b, np.ones(len(b), bool)) for b in blocks]
    ion = phys.ionization
    if ion:
        n = ion["neutral"]
        xr, _, gone = after_xr[n]
        x, v, a = before[n]
        ok, (xs, v0, v1, v2) = pack(gone, [xr, v[0], v[1], v[2]],
                                    phys.max_births)
        ok, xs, v0, v1, v2 = map(np.asarray, (ok, xs, v0, v1, v2))
        for d in range(D):
            m = ok[d]
            rows = np.stack([xs[d][m], v0[d][m], v1[d][m], v2[d][m]], 1)
            want[ion["ion"]][d].append((rows, np.ones(len(rows), bool)))
            drawn = rows.copy()
            drawn[:, 1:] = 0.0
            want[ion["electron"]][d].append(
                (drawn, np.zeros(len(rows), bool)))
    return want


def _ionize_expectation(phys, before, after_xr):
    """Mean and variance of the step's ionization count: n_e deposited
    from the electrons before the step, gathered at each neutral's pushed
    position; neutrals that crossed ionize on their new domain next step."""
    ion = phys.ionization
    xe, _, ae = before[ion["electron"]]
    ne = node_density(xe, ae, phys.ncl, phys.dx)
    xr, inside, _ = after_xr[ion["neutral"]]
    an = before[ion["neutral"]][2]
    p = -jnp.expm1(-gather_nodes(ne, xr, phys.ncl, phys.dx)
                   * ion["rate"] * phys.dt)
    # the program draws with 1 - exp(-n_e R dt); it differs from -expm1
    # by rounding only
    p = jnp.where(an & inside, p, 0.0)
    return float(jnp.sum(p, dtype=jnp.float32)), float(
        jnp.sum(p * (1.0 - p), dtype=jnp.float32))


def _birth_velocity_z(got: np.ndarray, arrivals: np.ndarray,
                      vth: float) -> float:
    """Velocities (n, 3) of the created electron rows and of the arrivals
    among them: the births are the rest. Each component's sum against 0
    (variance n vth^2) and the sum of v^2 against 3 n vth^2 (variance
    6 n vth^4), in standard deviations; the one farthest from 0."""
    n = len(got) - len(arrivals)
    if n <= 0:
        return 0.0
    got, arrivals = got.astype(np.float64), arrivals.astype(np.float64)
    s1 = got.sum(axis=0) - arrivals.sum(axis=0)
    s2 = np.sum(got * got) - np.sum(arrivals * arrivals)
    z = list(s1 / (vth * n ** 0.5))
    z.append((s2 - 3 * n * vth ** 2) / (vth ** 2 * (6 * n) ** 0.5))
    return float(max(z, key=abs))


def compare(phys, before: dict, after: dict, diag: dict, key=None) -> dict:
    """The numbers of one step (see the module docstring). ``before`` and
    ``after`` are component-major effective states on the host; ``diag``
    maps the step's diag keys to host scalars. ``key`` is unused: nothing
    here draws."""
    sp = [s["name"] for s in phys.species]
    counted = {s: int(np.sum(after[s][2])) for s in sp}
    before = {s: tuple(jnp.asarray(a) for a in before[s]) for s in sp}
    after = {s: tuple(jnp.asarray(a) for a in after[s]) for s in sp}
    x_err = v_err = 0.0
    lost = 0
    after_xr = {}
    got_new = {}
    size_new = phys.max_births + 2 * phys.max_migration
    for s in sp:
        xb, vb, ab = before[s]
        xa, va, aa = after[s]
        xe, ve, st, gone_n, xr, inside, gone = _stay(
            xb, vb, ab, xa, va, aa, np.float32(phys.dt), phys.l_loc)
        x_err = max(x_err, float(xe))
        v_err = max(v_err, float(ve) / phys.vth(s))
        lost += int(st)
        ion = phys.ionization
        if not ion or s != ion["neutral"]:
            lost += int(gone_n)        # only neutrals may vanish in place
        after_xr[s] = (xr, inside, gone)
        ok, cols = pack(~ab & aa, [xa, va[0], va[1], va[2]], size_new)
        got_new[s] = (np.asarray(ok), [np.asarray(c) for c in cols])
        lost += int(jnp.sum(~ab & aa)) - int(np.asarray(ok).sum())
    ionize_z = 0.0
    if phys.ionization:
        n_ion = int(diag["n_ionized"])
        events = n_ion + int(diag["birth_overflow"])
        mean, var = _ionize_expectation(phys, before, after_xr)
        ionize_z = (events - mean) / max(var, 1e-30) ** 0.5
        lost += abs(int(jnp.sum(after_xr[phys.ionization["neutral"]][2]))
                    - n_ion)
    want = _expected_new(phys, sp, before, after_xr, 2 * phys.max_migration)
    new_err = 0.0
    born = {"got": [], "arrivals": []}
    for s in sp:
        ok, cols = got_new[s]
        scale = np.array([phys.dx] + [phys.vth(s)] * 3, np.float32)
        for d in range(phys.domains):
            got = np.stack([c[d][ok[d]] for c in cols], axis=1)
            rows = [r for r, _ in want[s][d]]
            rows = (np.concatenate(rows) if rows
                    else np.zeros((0, 4), np.float32))
            known = np.concatenate([k for _, k in want[s][d]] or
                                   [np.zeros(0, bool)])
            lost += abs(len(got) - len(rows))
            new_err = max(new_err, match_rows(got, rows, known, scale))
            if phys.ionization and s == phys.ionization["electron"]:
                born["got"].append(got[:, 1:])
                born["arrivals"].append(rows[known][:, 1:])
    out = {}
    if phys.ionization:
        out["birth_v_z"] = _birth_velocity_z(
            np.concatenate(born["got"]), np.concatenate(born["arrivals"]),
            float(phys.ionization["vth_e"]))
    lost += dropped(diag)
    count_err = sum(abs(int(diag[f"{s}/count"]) - counted[s]) for s in sp)
    return dict(out, x_err=x_err, v_err=v_err, new_err=new_err,
                ionize_z=float(ionize_z), lost_rows=lost,
                count_err=count_err)


def step(phys, before: dict, key, dtype) -> tuple[dict, dict]:
    """One step of the reference as a program of its own, every float
    computed in ``dtype``: the control when ``dtype`` is bfloat16. Returns
    (effective state after, diag) in the program's formats (float32 on
    the host, component-major)."""
    sp = [s["name"] for s in phys.species]
    D, l_loc = phys.domains, phys.l_loc
    cast = lambda a: jnp.asarray(a).astype(dtype)
    dt = jnp.asarray(phys.dt, dtype)
    moved = {}
    for s in sp:
        x, v, a = before[s]
        xr = drift(cast(x), cast(v[0]), dt)
        inside = (xr >= 0.0) & (xr < l_loc)
        moved[s] = [xr, cast(v), jnp.asarray(a), inside]
    diag = {}
    births = {}
    ion = phys.ionization
    if ion:
        ne = node_density(cast(before[ion["electron"]][0]),
                          jnp.asarray(before[ion["electron"]][2]),
                          phys.ncl, phys.dx)
        xr, vn, an, inside = moved[ion["neutral"]]
        p = 1.0 - jnp.exp(-gather_nodes(ne, xr, phys.ncl, phys.dx)
                          * jnp.asarray(ion["rate"], dtype) * dt)
        ku, kv = jax.random.split(key)
        u = jax.random.uniform(ku, xr.shape, dtype)
        hit = an & inside & (u < p)
        rank = jnp.cumsum(hit.astype(jnp.int32), axis=1) - 1
        born = hit & (rank < phys.max_births)
        diag["n_ionized"] = int(jnp.sum(born))
        diag["birth_overflow"] = int(jnp.sum(hit)) - diag["n_ionized"]
        moved[ion["neutral"]][2] = an & ~born
        ok, (bx, b0, b1, b2) = pack(born, [xr, vn[0], vn[1], vn[2]],
                                    phys.max_births)
        ve = jnp.asarray(ion["vth_e"], dtype) * jax.random.normal(
            kv, (3,) + bx.shape, dtype)
        births[ion["ion"]] = (ok, bx, jnp.stack([b0, b1, b2]))
        births[ion["electron"]] = (ok, bx, ve)
    after = {}
    for s in sp:
        xr, v, a, inside = moved[s]
        ok, (cx, c0, c1, c2) = pack(a & ~inside, [xr, v[0], v[1], v[2]],
                                    2 * phys.max_migration)
        f32 = lambda c: np.asarray(c.astype(jnp.float32))
        rows = route(np.asarray(ok), f32(cx),
                     np.stack([f32(c) for c in (c0, c1, c2)], -1), l_loc, D)
        if s in births:
            bok, bx, bv = births[s]
            bok, bx, bv = np.asarray(bok), f32(bx), f32(bv)
            for d in range(D):
                m = bok[d]
                rows[d].append(np.concatenate(
                    [bx[d][m][:, None], bv[:, d][:, m].T], axis=1))
        x, vv, al = f32(xr).copy(), f32(v).copy(), np.array(a & inside)
        land(x, vv, al, rows, ~before[s][2])
        after[s] = (x, vv, al)
        diag[f"{s}/count"] = int(al.sum())
        diag[f"{s}/migration_overflow"] = 0
    return after, diag
