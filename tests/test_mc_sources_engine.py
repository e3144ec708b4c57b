"""Ring-aware Monte-Carlo sources in the async engine: conservation laws.

The §3.3 ionization scenario and the SEE plasma-wall source now run on the
async(n) queue pipeline through the persistent free-slot ring (ionization
kills push packed neutral slots, electron/ion births pop pre-claimed pair
slots; SEE secondaries claim off the absorbed migration-pack rows). These
tests pin

* count + charge conservation, bitwise-exact, for ionization and SEE
  across D in {1, 2, 4} x async_n in {1, 2, 4} x {rebalance on, off},
  with and without the field solve;
* the ring's landing of each step's pending rows (arrivals and births in
  their pre-claimed slots) against a full-capacity scan that injects the
  same rows into the first dead slots;
* the ``birth_overflow`` budget clamp (mirroring ``migration_overflow``):
  refused births leave the neutral alive to retry — never a lost particle;
* the carried-rho fast path with MC sources active (birth charge folded
  into ``PICState.rho``), against a from-scratch recompute;
* no full-rho all_gather in the ionization engine step (jaxpr-asserted;
  the no-full-capacity-scan assertions live in ``test_slot_ring.py``).

All weights are 1.0 so every charge total is an exact small integer in
float32 — "bitwise-exact" is then a plain equality against the counting
prediction. Multi-device checks follow the ``test_async_engine`` pattern:
in-process when 4 devices exist, else a subprocess with emulated devices.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import pic
from repro.core.particles import stack_species
from repro.distributed import engine
from repro.launch.mesh import make_debug_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
HERE = os.path.dirname(__file__)

N0 = 2048          # per-species initial population (global)
CAP = 8192         # per-species capacity (global): 4x headroom for births


def _dispatch(func_name: str, *args) -> None:
    """Run a check in-process when 4 devices exist, else in a subprocess."""
    if jax.device_count() >= 4:
        globals()[func_name](*args)
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + HERE
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    prog = (f"from test_mc_sources_engine import {func_name}; "
            f"{func_name}(*{args!r})")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]


def _ion_cfg(*, field_solve=False, dt=0.4, rate=3e-3, boundary="periodic",
             see=False, emission_yield=0.7):
    """The paper's (e-, D+, D) ionization triple, weight 1.0 (exact-integer
    charges); optionally with absorbing walls + SEE on top."""
    sp = (
        pic.SpeciesConfig("e", -1.0, 1.0, CAP, N0, vth=1.0),
        pic.SpeciesConfig("D+", 1.0, 3672.0, CAP, N0, vth=0.02),
        pic.SpeciesConfig("D", 0.0, 3672.0, CAP, N0, vth=0.05),
    )
    kw = {}
    if see:
        boundary = "absorb"
        kw = dict(wall_emission=((0, 0),), emission_yield=emission_yield,
                  emission_vth=0.5)
    return pic.PICConfig(
        nc=256, dx=1.0, dt=dt if not field_solve else 0.1, species=sp,
        field_solve=field_solve, boundary=boundary, strategy="fused",
        ionization=(2, 0, 1), ionization_rate=rate, ionization_vth_e=1.0,
        **kw)


def _see_cfg():
    """Two-species bounded plasma: electrons re-emit electrons (SEE)."""
    sp = (
        pic.SpeciesConfig("e", -1.0, 1.0, CAP, N0, vth=1.5),
        pic.SpeciesConfig("D+", 1.0, 3672.0, CAP, N0, vth=0.02),
    )
    return pic.PICConfig(
        nc=256, dx=1.0, dt=0.4, species=sp, field_solve=False,
        boundary="absorb", strategy="unified", wall_emission=((0, 0),),
        emission_yield=0.8, emission_vth=0.5)


_SOURCE_KEYS = ("n_ionized", "birth_overflow")
_SOURCE_SUFFIXES = ("migration_overflow", "merge_dropped", "wall_absorbed",
                    "emitted", "emission_overflow", "migrated_left",
                    "migrated_right")


def _accumulate(sums: dict, diag) -> None:
    for k, v in diag.items():
        if k in _SOURCE_KEYS or k.endswith(_SOURCE_SUFFIXES):
            sums[k] = sums.get(k, 0) + int(np.asarray(v))


def _host_diag(diag) -> dict:
    return {k: (float(np.asarray(v)) if np.asarray(v).ndim == 0
                else np.asarray(v)) for k, v in diag.items()}


def _run(cfg, d, an, steps, *, rebalance_every=0, rebalance_skew=0,
         max_births=512, seed=3):
    """Run the engine; returns (final diag, per-key accumulated sums)."""
    mesh = make_debug_mesh(data=d, model=1)
    ecfg = engine.EngineConfig(
        pic=cfg, axis_names=("data",), async_n=an, max_migration=512,
        max_births=max_births, rebalance_every=rebalance_every,
        rebalance_skew=rebalance_skew)
    state = engine.init_engine_state(ecfg, mesh, seed)
    step = engine.make_engine_step(ecfg, mesh)
    sums: dict = {}
    for _ in range(steps):
        state, diag = step(state)
        _accumulate(sums, diag)
    return _host_diag(diag), sums


def _assert_ionization_conserved(diag, sums, tag):
    """Exact pair accounting + bitwise-exact integer charge totals."""
    ion = sums["n_ionized"]
    absorbed = {s: sums.get(f"{s}/wall_absorbed", 0)
                for s in ("e", "D+", "D")}
    emitted = sums.get("e/emitted", 0)
    assert ion > 0, (tag, "MC source inactive — test underpowered")
    assert int(diag["e/count"]) == N0 + ion + emitted - absorbed["e"], tag
    assert int(diag["D+/count"]) == N0 + ion - absorbed["D+"], tag
    assert int(diag["D/count"]) == N0 - ion - absorbed["D"], tag
    # charge: weight 1.0 makes every total an exact integer in float32
    assert diag["e/charge"] == -float(N0 + ion + emitted - absorbed["e"]), tag
    assert diag["D+/charge"] == float(N0 + ion - absorbed["D+"]), tag
    assert diag["D/charge"] == 0.0, tag
    assert sums.get("e/migration_overflow", 0) == 0, tag
    assert sums.get("e/merge_dropped", 0) == 0, tag


# ---------------------------------------------------------------- in-process


@pytest.mark.parametrize("an,reb,skew,fs", [
    (1, 0, 0, False), (2, 3, 0, False), (4, 0, 8, False), (2, 3, 0, True)])
def test_ionization_conservation_single_domain(an, reb, skew, fs):
    """D=1 across async_n and both rebalance modes (period + skew trigger),
    with and without the field solve: exact pair/charge accounting."""
    cfg = _ion_cfg(field_solve=fs)
    diag, sums = _run(cfg, 1, an, 12, rebalance_every=reb,
                      rebalance_skew=skew)
    _assert_ionization_conserved(diag, sums, (an, reb, skew, fs))
    assert sums["birth_overflow"] == 0


def test_birth_budget_overflow_conserves():
    """A tiny max_births clamps the MC events; the refused neutrals stay
    alive and retry (mirror of migration_overflow) — nothing is lost."""
    diag, sums = _run(_ion_cfg(rate=1e-2), 1, 2, 10, max_births=8)
    assert sums["birth_overflow"] > 0
    _assert_ionization_conserved(diag, sums, "budget")


_LANDING_CFGS = {
    "ionize": lambda: _ion_cfg(),
    "ionize+field": lambda: _ion_cfg(field_solve=True),
    "see": _see_cfg,
    "ionize+see": lambda: _ion_cfg(see=True),
}


def _live_rows(x, v, w, alive) -> np.ndarray:
    """The live rows (x, v, w) of one species buffer, sorted: a multiset
    that compares equal whatever slots the rows sit in."""
    rows = np.column_stack([x, v, w])[alive]
    return rows[np.lexsort(rows.T[::-1])]


@pytest.mark.parametrize("tag", list(_LANDING_CFGS))
def test_ring_landing_matches_full_scan_injection(tag):
    """After every step, the pending rows (migration arrivals, ionization
    pairs, SEE secondaries) land in their ring-claimed slots
    (``engine._flush_pending``, what the next ingest runs). A full-capacity
    scan that writes the same rows into the first dead slots of the
    pre-step buffers (``particles.inject_masked``'s rule, in numpy) must
    leave the same live rows, per species, as multisets; every accepted
    ``dest`` must be a distinct slot that was dead before the flush; the
    step's diag counts must be the flushed buffers'; and the run keeps
    exact count and charge accounting."""
    cfg = _LANDING_CFGS[tag]()
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=512, max_births=512)
    state = engine.init_engine_state(ecfg, mesh, 3)
    step = engine.make_engine_step(ecfg, mesh, donate=False)
    flush = jax.jit(engine._flush_pending)
    groups = engine._capacity_groups(ecfg, mesh)
    sums: dict = {}
    landed = 0
    for it in range(10):
        state, diag = step(state)
        _accumulate(sums, diag)
        for g, idxs in enumerate(groups):
            st = stack_species([jax.tree.map(lambda a: a[0],
                                             state.pic.species[i])
                                for i in idxs])
            pend = jax.tree.map(lambda a: a[0], state.pending[g])
            eff = jax.tree.map(np.asarray, flush(st, pend))
            st, pend = jax.tree.map(np.asarray, (st, pend))
            for j, i in enumerate(idxs):
                ctx = (tag, it, cfg.species[i].name)
                cap = st.alive.shape[1]
                ok = pend.alive[j] & (pend.dest[j] < cap)
                dest = pend.dest[j][ok]
                assert len(np.unique(dest)) == dest.size, ctx
                assert not st.alive[j][dest].any(), ctx
                free = np.flatnonzero(~st.alive[j])[:dest.size]
                assert free.size == dest.size, ctx
                x, v, w, alive = (a[j].copy() for a in
                                  (st.x, st.v, st.w, st.alive))
                x[free], v[free], w[free] = (pend.x[j][ok], pend.v[j][ok],
                                             pend.w[j][ok])
                alive[free] = True
                np.testing.assert_array_equal(
                    _live_rows(eff.x[j], eff.v[j], eff.w[j], eff.alive[j]),
                    _live_rows(x, v, w, alive), err_msg=str(ctx))
                assert int(np.asarray(
                    diag[f"{cfg.species[i].name}/count"])) == alive.sum(), ctx
                landed += dest.size
    assert landed > 0, (tag, "no pending row landed — test underpowered")
    diag = _host_diag(diag)
    if cfg.ionization is not None:
        _assert_ionization_conserved(diag, sums, tag)
        return
    absorbed, emitted = sums["e/wall_absorbed"], sums["e/emitted"]
    assert absorbed > 0 and emitted > 0, tag
    assert int(diag["e/count"]) == N0 - absorbed + emitted, tag
    assert int(diag["D+/count"]) == N0 - sums["D+/wall_absorbed"], tag
    assert diag["e/charge"] == -float(N0 - absorbed + emitted), tag
    assert sums["e/merge_dropped"] == 0, tag


def test_single_domain_ionize_overflow_keeps_neutrals():
    """Core-path regression (pre-fix, a full electron buffer silently
    dropped the birth but still killed the neutral): a refused birth now
    leaves the neutral alive, reported via birth_overflow."""
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, N0 + 64, N0, vth=1.0),
          pic.SpeciesConfig("D+", 1.0, 3672.0, N0 + 64, N0, vth=0.02),
          pic.SpeciesConfig("D", 0.0, 3672.0, 2 * N0, N0, vth=0.02))
    cfg = pic.PICConfig(nc=64, dx=1.0, dt=0.5, species=sp, field_solve=False,
                        ionization=(2, 0, 1), ionization_rate=5e-3,
                        ionization_vth_e=1.0)
    final, diags = pic.run(cfg, 20, seed=0)
    ion = int(np.asarray(diags["n_ionized"]).sum())
    over = int(np.asarray(diags["birth_overflow"]).sum())
    assert int(np.asarray(diags["ionize_dropped"]).sum()) == 0
    assert over > 0                       # the clamp actually engaged
    counts = [int(b.count()) for b in final.species]
    assert counts[0] == N0 + ion and counts[0] <= N0 + 64
    assert counts[1] == N0 + ion
    assert counts[2] == N0 - ion          # refused neutrals survived


def test_carried_rho_matches_recompute_with_mc_sources():
    """strategy='fused' + field solve + MC sources: the carried rho (in-pass
    deposit + birth corrections) must track a from-scratch deposit."""
    for cfg in (_ion_cfg(field_solve=True),
                dataclasses.replace(_see_cfg(), strategy="fused",
                                    field_solve=True, dt=0.1)):
        assert pic._carries_rho(cfg)
        final, _ = pic.run(cfg, 8, seed=1)
        assert final.rho is not None
        rho_ref = pic.compute_rho(cfg, final.species)
        np.testing.assert_allclose(np.asarray(final.rho),
                                   np.asarray(rho_ref),
                                   rtol=1e-4, atol=1e-5)


# ------------------------------------------------- 4-device checks (impl)


def check_ionization_conservation_multidomain():
    """D in {2, 4} x async_n in {1, 2, 4} x {rebalance off, periodic, skew},
    with and without the field solve: exact pair/charge accounting under
    real migration between domains."""
    cases = [(2, 2, 0, 0, False), (4, 1, 0, 0, False), (4, 4, 3, 0, False),
             (2, 4, 0, 8, False), (4, 2, 3, 0, True)]
    for d, an, reb, skew, fs in cases:
        cfg = _ion_cfg(field_solve=fs)
        diag, sums = _run(cfg, d, an, 12, rebalance_every=reb,
                          rebalance_skew=skew)
        _assert_ionization_conserved(diag, sums, (d, an, reb, skew, fs))
        assert sums["birth_overflow"] == 0
        # the decomposition is real: particles actually crossed domains
        assert sums["e/migrated_left"] + sums["e/migrated_right"] > 0


def check_see_conservation_multidomain():
    """SEE across domains: every electron is alive, absorbed, or was
    emitted — exact, with the emission ring-claimed off the packed
    absorbed rows of the edge domains."""
    for d, an, reb in [(2, 2, 0), (4, 4, 3), (4, 1, 0)]:
        diag, sums = _run(_see_cfg(), d, an, 15, rebalance_every=reb)
        absorbed_e = sums["e/wall_absorbed"]
        emitted = sums["e/emitted"]
        assert absorbed_e > 0 and emitted > 0, (d, an, reb)
        assert int(diag["e/count"]) == N0 - absorbed_e + emitted, (d, an, reb)
        assert int(diag["D+/count"]) == N0 - sums["D+/wall_absorbed"]
        assert diag["e/charge"] == -float(N0 - absorbed_e + emitted)
        assert sums["e/emission_overflow"] == 0
        assert sums["e/merge_dropped"] == 0


def check_combined_sources_multidomain():
    """Ionization + SEE + absorbing walls together on D=4: all three
    sources feed the same rings in one step; accounting stays exact."""
    cfg = _ion_cfg(see=True)
    diag, sums = _run(cfg, 4, 2, 12, rebalance_skew=16)
    _assert_ionization_conserved(diag, sums, "combined")
    assert sums["e/emitted"] > 0 and sums["e/wall_absorbed"] > 0


def check_no_full_rho_allgather_ionization():
    """The ionization engine step (field solve on, carried rho) must keep
    the halo-field guarantee: no all_gather payload beyond a scalar."""
    from test_async_engine import _collect_collectives

    cfg = _ion_cfg(field_solve=True)
    mesh = make_debug_mesh(data=4, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=512, max_births=512)
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh, donate=False)
    colls = _collect_collectives(jax.make_jaxpr(step)(state).jaxpr, [])
    gathers = [shapes for name, shapes in colls if "all_gather" in name]
    assert gathers, "expected scalar prefix-carry gathers"
    for shapes in gathers:
        for shape in shapes:
            assert int(np.prod(shape, dtype=int)) <= 1, (
                f"non-scalar all_gather operand {shape} in the ionization "
                f"step — the full-rho assembly is back")
    assert any(name == "ppermute" for name, _ in colls)


def check_ne_closes_periodic_ring(d: int):
    """The engine's halo-summed n_e (what the ionization draw reads), at
    every node of every domain, nodes 0 and ncl included, equals a global
    periodic CIC deposit of the same electrons: the node that domain D-1
    shares with domain 0 across the wrap (with one domain, its own two
    ends) holds the particles of both sides."""
    from repro.distributed import halo

    cfg = _ion_cfg()
    mesh = make_debug_mesh(data=d, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=512, max_births=512)
    state = engine.init_engine_state(ecfg, mesh, 5)
    # the first step's ingest lands nothing (no pending rows, no
    # rebalance), so its n_e deposit reads the initial electrons
    e = state.pic.species[0]
    x, w, alive = (np.asarray(a, np.float64) for a in (e.x, e.w, e.alive))
    ncl = cfg.nc // d
    xg = x + (np.arange(d) * ncl * cfg.dx)[:, None]
    s = (xg / cfg.dx).ravel()
    i = np.clip(np.floor(s).astype(np.int64), 0, cfg.nc - 1)
    f = s - i
    q = (w * alive).ravel()
    want = np.zeros(cfg.nc)
    np.add.at(want, i, q * (1.0 - f))
    np.add.at(want, (i + 1) % cfg.nc, q * f)
    want /= cfg.dx

    seen = {}
    real = halo.halo_sum

    def spy(rho, axis_names, *args, **kw):
        out = real(rho, axis_names, *args, **kw)
        jax.debug.callback(
            lambda r, v: seen.__setitem__(int(r), np.asarray(v)),
            halo.rank(axis_names), out)
        return out

    halo.halo_sum = spy
    try:
        step = engine.make_engine_step(ecfg, mesh, donate=False)
        jax.block_until_ready(step(state))
    finally:
        halo.halo_sum = real
    assert sorted(seen) == list(range(d))
    for r in range(d):
        nodes = (r * ncl + np.arange(ncl + 1)) % cfg.nc
        np.testing.assert_allclose(seen[r], want[nodes], rtol=1e-5,
                                   atol=1e-4, err_msg=f"domain {r}")


# ------------------------------------------------------------- 4-device tests


@pytest.mark.parametrize("d", [1, 4])
def test_ne_closes_periodic_ring(d):
    _dispatch("check_ne_closes_periodic_ring", d)



def test_ionization_conservation_multidomain():
    _dispatch("check_ionization_conservation_multidomain")


def test_see_conservation_multidomain():
    _dispatch("check_see_conservation_multidomain")


def test_combined_sources_multidomain():
    _dispatch("check_combined_sources_multidomain")


def test_no_full_rho_allgather_ionization():
    _dispatch("check_no_full_rho_allgather_ionization")
