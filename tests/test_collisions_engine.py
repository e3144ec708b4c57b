"""The collide phase on the async engine: parity, conservation, jaxpr pins.

The binary-collision menu runs per queue between push and migration — it
touches only velocities, so the engine's count/charge accounting must stay
bitwise-identical to the single-domain cycle on identical seeds, and the
collision invariants (electron KE under elastic + e-e Coulomb; joint D+/D
KE under charge exchange) must hold on both paths. These tests pin

* single-domain vs engine parity of moments across D in {1, 2, 4} x
  async_n in {1, 2, 4} x {cell_order on, off}: counts and charges bitwise
  (exact small integers in float32), the collision KE invariants to float
  tolerance, with the collision counters proven active;
* the jaxpr contract of the collide phase: only queue-sized sorts and
  gathers — no sort and no cumsum over a full-capacity axis (the
  ``test_slot_ring`` assertion style), and no non-scalar all_gather when
  the field solve is on;
* cell_order=True: the rebalance really is a counting sort by cell (the
  rebalance's sort applied to the buffers the next ingest sees), the
  free-slot-ring invariant survives it, and
  conservation holds with collisions + ionization + SEE all active;
* the ``EmissionParams.weight`` config satellite: mixed-weight SEE
  conserves charge exactly on both paths.

Multi-device checks follow the ``test_mc_sources_engine`` pattern:
in-process when 4 devices exist, else a subprocess with emulated devices.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import pic
from repro.core.collisions import CollisionConfig
from repro.core.particles import stack_species
from repro.distributed import engine
from repro.launch.mesh import make_debug_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
HERE = os.path.dirname(__file__)

N0 = 2048
CAP = 8192

MENU = (CollisionConfig("elastic", 0, 2, 2e-2),
        CollisionConfig("charge_exchange", 1, 2, 2e-2),
        CollisionConfig("coulomb", 0, None, 2e-3))

COLL_KEYS = ("coll_elastic", "coll_cx", "coll_coulomb")


def _dispatch(func_name: str) -> None:
    if jax.device_count() >= 4:
        globals()[func_name]()
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + HERE
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    prog = f"from test_collisions_engine import {func_name}; {func_name}()"
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]


def _coll_cfg(*, menu=MENU, dt=0.4, field_solve=False, kernel=False):
    """(e-, D+, D) with the full collision menu, weight 1.0 — every charge
    total is an exact small integer in float32."""
    sp = (
        pic.SpeciesConfig("e", -1.0, 1.0, CAP, N0, vth=1.0),
        pic.SpeciesConfig("D+", 1.0, 3672.0, CAP, N0, vth=0.02),
        pic.SpeciesConfig("D", 0.0, 3672.0, CAP, N0, vth=0.05),
    )
    return pic.PICConfig(
        nc=256, dx=1.0, dt=dt if not field_solve else 0.1, species=sp,
        field_solve=field_solve, boundary="periodic", strategy="fused",
        collisions=menu, collide_kernel=kernel)


def _run_engine(cfg, d, an, steps, *, cell_order=False, rebalance_every=0,
                rebalance_skew=0, seed=3):
    """Returns (first-step diag, last-step diag, accumulated sums): the
    engine draws its OWN per-domain initial particles, so KE invariants are
    checked across its steps (step 1 vs step N), not against the
    single-domain initial state."""
    mesh = make_debug_mesh(data=d, model=1)
    ecfg = engine.EngineConfig(
        pic=cfg, axis_names=("data",), async_n=an, max_migration=512,
        max_births=512, rebalance_every=rebalance_every,
        rebalance_skew=rebalance_skew, cell_order=cell_order)
    state = engine.init_engine_state(ecfg, mesh, seed)
    step = engine.make_engine_step(ecfg, mesh)
    sums: dict = {}
    first = None
    for _ in range(steps):
        state, diag = step(state)
        if first is None:
            first = {k: (float(np.asarray(v)) if np.asarray(v).ndim == 0
                         else np.asarray(v)) for k, v in diag.items()}
        for k in COLL_KEYS + ("e/migrated_left", "e/migrated_right"):
            if k in diag:
                sums[k] = sums.get(k, 0) + int(np.asarray(diag[k]))
    out = {k: (float(np.asarray(v)) if np.asarray(v).ndim == 0
               else np.asarray(v)) for k, v in diag.items()}
    return first, out, sums


def _run_single(cfg, steps, seed=3):
    final, diags = pic.run(cfg, steps, seed=seed)
    out = {}
    for sc, buf in zip(cfg.species, final.species):
        out[f"{sc.name}/count"] = int(buf.count())
        out[f"{sc.name}/charge"] = float(jnp.sum(
            buf.w * buf.alive * sc.charge))
        out[f"{sc.name}/ke"] = float(
            0.5 * sc.mass * jnp.sum(buf.w * buf.alive
                                    * jnp.sum(buf.v * buf.v, axis=-1)))
    sums = {k: int(np.asarray(v).sum()) for k, v in diags.items()
            if k in COLL_KEYS}
    return out, sums


def _initial_kes(cfg, seed=3):
    state = pic.init_state(cfg, seed)
    kes = {}
    for sc, buf in zip(cfg.species, state.species):
        kes[sc.name] = float(
            0.5 * sc.mass * jnp.sum(buf.w * buf.alive
                                    * jnp.sum(buf.v * buf.v, axis=-1)))
    return kes


def _assert_parity(ediag, esums, sdiag, ssums, tag):
    """Moments parity: counts/charges bitwise; collisions active on both."""
    for k in COLL_KEYS:
        assert esums.get(k, 0) > 0, (tag, k, "engine menu inactive")
        assert ssums.get(k, 0) > 0, (tag, k, "single menu inactive")
    for n in ("e", "D+", "D"):
        assert int(ediag[f"{n}/count"]) == sdiag[f"{n}/count"] == N0, (tag, n)
        assert ediag[f"{n}/charge"] == sdiag[f"{n}/charge"], (tag, n)
    assert ediag["e/charge"] == -float(N0), tag
    assert ediag["D+/charge"] == float(N0), tag


def _assert_ke_invariants(diag, ref_kes, tag, rtol=2e-4):
    """Collision KE invariants against a reference snapshot of the SAME
    trajectory: elastic and e-e Coulomb preserve electron KE; charge
    exchange moves KE between D+ and D but conserves their (equal-mass)
    sum."""
    def ke(d, n):
        return float(d[f"{n}/ke"] if f"{n}/ke" in d else d[n])
    np.testing.assert_allclose(ke(diag, "e"), ke(ref_kes, "e"), rtol=rtol,
                               err_msg=str(tag))
    np.testing.assert_allclose(ke(diag, "D+") + ke(diag, "D"),
                               ke(ref_kes, "D+") + ke(ref_kes, "D"),
                               rtol=rtol, err_msg=str(tag))


# ---------------------------------------------------------------- in-process


def test_collision_parity_single_domain():
    """D=1 across async_n in {1, 2, 4} x {cell_order on, off}: engine vs
    single-domain moments bitwise, KE invariants on both paths."""
    cfg = _coll_cfg()
    sdiag, ssums = _run_single(cfg, 10)
    _assert_ke_invariants(sdiag, _initial_kes(cfg), "single")
    for an in (1, 2, 4):
        for cell in (False, True):
            reb = 3 if cell else 0
            efirst, ediag, esums = _run_engine(cfg, 1, an, 10,
                                               cell_order=cell,
                                               rebalance_every=reb)
            _assert_parity(ediag, esums, sdiag, ssums, (1, an, cell))
            _assert_ke_invariants(ediag, efirst, (1, an, cell))


def test_collision_kernel_path_engine_parity():
    """collide_kernel=True (the Pallas T-A deflection) keeps the same
    moments and invariants on the engine."""
    cfg = _coll_cfg(kernel=True)
    efirst, ediag, esums = _run_engine(cfg, 1, 2, 6)
    for k in COLL_KEYS:
        assert esums[k] > 0
    for n in ("e", "D+", "D"):
        assert int(ediag[f"{n}/count"]) == N0
    _assert_ke_invariants(ediag, efirst, "kernel")


@pytest.mark.parametrize("async_n", [1, 2, 4])
def test_cell_order_rebalance_counting_sorts(async_n):
    """With cell_order=True the rebalance orders every species buffer by
    cell (live rows grouped, nondecreasing, dead at the tail): after one
    real step, each capacity group as the next ingest sees it (pending
    rows landed) goes through the rebalance's sort,
    ``engine._cellsort_group``, and keeps every particle."""
    cfg = _coll_cfg()
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",),
                               async_n=async_n, max_migration=512,
                               max_births=512, rebalance_every=1,
                               cell_order=True)
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh)
    state, diag = step(state)               # step -> 1: next ingest sorts
    sort = jax.jit(lambda st: engine._cellsort_group(
        st, cfg.dx, ecfg.local_nc(mesh)))
    for g, idxs in enumerate(engine._capacity_groups(ecfg, mesh)):
        st = engine._flush_pending(
            stack_species([jax.tree.map(lambda a: a[0],
                                        state.pic.species[i])
                           for i in idxs]),
            jax.tree.map(lambda a: a[0], state.pending[g]))
        out, counts = jax.tree.map(np.asarray, sort(st))
        for j, i in enumerate(idxs):
            name = cfg.species[i].name
            n_live = int(out.alive[j].sum())
            assert n_live == int(counts[j]) > 0, name
            assert n_live == int(np.asarray(diag[f"{name}/count"])), name
            assert not out.alive[j][n_live:].any(), name   # dead tail
            cells = np.floor(out.x[j][:n_live] / cfg.dx).astype(int)
            assert (np.diff(cells) >= 0).all(), name       # cell-grouped


def test_cell_order_keeps_ring_invariant_with_all_sources():
    """Ring ∪ pending-dest must stay EXACTLY the dead-slot set when the
    cell-order rebalance reshuffles buffers under collisions + ionization
    + SEE churn (the free-set invariant of test_slot_ring, under the new
    reorder mode)."""
    from test_slot_ring import _ring_sets

    sp = (pic.SpeciesConfig("e", -1.0, 1.0, 2048, 1024, vth=1.0),
          pic.SpeciesConfig("D+", 1.0, 3672.0, 2048, 1024, vth=0.02),
          pic.SpeciesConfig("D", 0.0, 3672.0, 2048, 1024, vth=0.05))
    cfg = pic.PICConfig(
        nc=64, dx=1.0, dt=0.5, species=sp, field_solve=False,
        boundary="absorb", strategy="fused", collisions=MENU,
        ionization=(2, 0, 1), ionization_rate=5e-3, ionization_vth_e=1.0,
        wall_emission=((0, 0),), emission_yield=0.7, emission_vth=0.5)
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=256, max_births=256,
                               rebalance_every=2, cell_order=True)
    state = engine.init_engine_state(ecfg, mesh, 1)
    step = engine.make_engine_step(ecfg, mesh)
    active = 0
    for it in range(8):
        state, diag = step(state)
        active += int(np.asarray(diag["n_ionized"]))
        for (g, i), (live, dests) in _ring_sets(state, ecfg, mesh).items():
            alive = np.asarray(state.pic.species[i].alive)[0]
            dead = set(int(s) for s in np.nonzero(~alive)[0])
            assert len(live) == len(set(live)), (it, i, "ring dup")
            assert set(live).isdisjoint(dests), (it, i, "claimed twice")
            assert set(live) | set(dests) == dead, (it, i, "free-set drift")
    assert active > 0


def test_mixed_weight_see_conserves_charge_exactly():
    """EmissionParams.weight through PICConfig (config satellite):
    half-weight secondaries — total electron charge must equal
    -(N0 - absorbed + 0.5 * emitted) EXACTLY (halves are exact in f32),
    counts stay integer-accounted, on the single-domain path AND the
    engine."""
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, CAP, N0, vth=1.5),
          pic.SpeciesConfig("D+", 1.0, 3672.0, CAP, N0, vth=0.02))
    cfg = pic.PICConfig(
        nc=256, dx=1.0, dt=0.4, species=sp, field_solve=False,
        boundary="absorb", strategy="unified", wall_emission=((0, 0),),
        emission_yield=0.8, emission_vth=0.5, emission_weight=0.5)

    # single-domain
    final, diags = pic.run(cfg, 12, seed=3)
    emitted = int(np.asarray(diags["e/emitted"]).sum())
    absorbed = int(np.asarray(diags["e/absorbed_left"]).sum()
                   + np.asarray(diags["e/absorbed_right"]).sum())
    assert emitted > 0 and absorbed > 0
    e = final.species[0]
    assert int(e.count()) == N0 - absorbed + emitted
    charge = float(jnp.sum(e.w * e.alive * -1.0))
    assert charge == -(N0 - absorbed + 0.5 * emitted)

    # engine (ring-claimed emission off the packed absorbed rows)
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=512, max_births=512)
    state = engine.init_engine_state(ecfg, mesh, 3)
    step = engine.make_engine_step(ecfg, mesh)
    em = ab = 0
    for _ in range(12):
        state, diag = step(state)
        em += int(np.asarray(diag["e/emitted"]))
        ab += int(np.asarray(diag["e/wall_absorbed"]))
    assert em > 0 and ab > 0
    assert int(np.asarray(diag["e/count"])) == N0 - ab + em
    assert float(np.asarray(diag["e/charge"])) == -(N0 - ab + 0.5 * em)


# --------------------------------------------------------------- jaxpr pins


def _collect_primitive_shapes(jxp, name, out):
    for eqn in jxp.eqns:
        if eqn.primitive.name == name:
            out.extend(tuple(v.aval.shape) for v in eqn.invars)
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(x, "jaxpr"):
                    _collect_primitive_shapes(x.jaxpr, name, out)
                elif hasattr(x, "eqns"):
                    _collect_primitive_shapes(x, name, out)
    return out


def test_collide_phase_is_queue_sized_only():
    """The jaxpr contract of the collide phase: every sort the step issues
    is queue-sized (cap / async_n — the cell-shuffled pairing), NEVER a
    full-capacity one, and no cumsum regresses to the full-capacity axis
    either. Checked with rebalance off so the only sorts present are the
    collide phase's own."""
    from test_slot_ring import _collect_cumsum_shapes

    cap = CAP
    mesh = make_debug_mesh(data=1, model=1)
    for tag, cfg in {
        "collisions": _coll_cfg(),
        "collisions+field": _coll_cfg(field_solve=True),
        "collisions+mc": dataclasses.replace(
            _coll_cfg(), ionization=(2, 0, 1), ionization_rate=1e-3,
            ionization_vth_e=1.0),
    }.items():
        ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                                   max_migration=512, max_births=512)
        state = engine.init_engine_state(ecfg, mesh, 0)
        step = engine.make_engine_step(ecfg, mesh, donate=False)
        jxp = jax.make_jaxpr(step)(state).jaxpr
        sorts = _collect_primitive_shapes(jxp, "sort", [])
        capq = cap // ecfg.async_n
        assert sorts, (tag, "expected the collide phase's pairing sorts")
        assert all(s[-1] <= capq for s in sorts if s), (tag, sorts)
        cumsums = _collect_cumsum_shapes(jxp, [])
        full = [s for s in cumsums if s and s[-1] >= cap]
        assert not full, (
            f"[{tag}] the collide phase issued a full-capacity scan "
            f"(shapes={full}) — per-cell pairing must stay queue-sized")


def test_collide_rebalance_sort_is_conditional_only():
    """With cell_order + rebalance ON, full-capacity sorts may exist ONLY
    under the rebalance cond branch — the steady-state step body stays
    queue-sized. (The cond branches are inspected separately: the branch
    jaxprs contain the (S, cap) counting sort, the top level only
    queue-sized pairing sorts.)"""
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=_coll_cfg(), axis_names=("data",),
                               async_n=2, max_migration=512, max_births=512,
                               rebalance_every=4, cell_order=True)
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh, donate=False)
    jxp = jax.make_jaxpr(step)(state).jaxpr

    def outside_cond(j, out):
        """Sorts reachable without entering a cond branch, at any depth."""
        for eqn in j.eqns:
            if eqn.primitive.name == "cond":
                continue
            if eqn.primitive.name == "sort":
                out.extend(tuple(v.aval.shape) for v in eqn.invars)
            for v in eqn.params.values():
                for x in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(x, "jaxpr"):
                        outside_cond(x.jaxpr, out)
                    elif hasattr(x, "eqns"):
                        outside_cond(x, out)
        return out

    top = outside_cond(jxp, [])
    capq = CAP // ecfg.async_n
    assert top and all(s[-1] <= capq for s in top if s), top
    # and the rebalance branch really does carry the full counting sort
    all_sorts = _collect_primitive_shapes(jxp, "sort", [])
    assert any(s and s[-1] == CAP for s in all_sorts), all_sorts


def test_no_full_rho_allgather_with_collisions():
    """Collisions + field solve keep the halo-field guarantee: no
    all_gather payload beyond a scalar in the step."""
    from test_async_engine import _collect_collectives

    cfg = _coll_cfg(field_solve=True)
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=512, max_births=512)
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh, donate=False)
    colls = _collect_collectives(jax.make_jaxpr(step)(state).jaxpr, [])
    for name, shapes in colls:
        if "all_gather" in name:
            for shape in shapes:
                assert int(np.prod(shape, dtype=int)) <= 1, (name, shape)


def test_engine_rejects_cross_group_collision_partners():
    """Binary partners must share a capacity group on the engine (a queue
    is one group's slice)."""
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, CAP, N0, vth=1.0),
          pic.SpeciesConfig("D+", 1.0, 3672.0, CAP, N0, vth=0.02),
          pic.SpeciesConfig("D", 0.0, 3672.0, 2 * CAP, N0, vth=0.05))
    cfg = pic.PICConfig(nc=256, dx=1.0, dt=0.2, species=sp,
                        field_solve=False, strategy="fused",
                        collisions=(CollisionConfig("elastic", 0, 2, 1e-3),))
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=512)
    try:
        engine.make_engine_step(ecfg, mesh)
    except ValueError as e:
        assert "capacity group" in str(e)
    else:
        raise AssertionError("cross-group collision partners accepted")


# ------------------------------------------------- 4-device checks (impl)


def check_collision_parity_multidomain():
    """D in {2, 4} x async_n in {1, 2, 4} x {cell_order on, off}: moments
    bitwise vs the single-domain run, KE invariants, real migration."""
    cfg = _coll_cfg()
    sdiag, ssums = _run_single(cfg, 10)
    cases = [(2, 1, True), (2, 2, False), (2, 4, True),
             (4, 1, False), (4, 2, True), (4, 4, False)]
    for d, an, cell in cases:
        reb = 3 if cell else 0
        efirst, ediag, esums = _run_engine(cfg, d, an, 10, cell_order=cell,
                                           rebalance_every=reb)
        _assert_parity(ediag, esums, sdiag, ssums, (d, an, cell))
        _assert_ke_invariants(ediag, efirst, (d, an, cell))
        assert esums["e/migrated_left"] + esums["e/migrated_right"] > 0, (
            d, an, cell, "decomposition not exercised")


def check_collisions_with_all_sources_multidomain():
    """Collisions + ionization + SEE + absorbing walls on D=4 with the
    cell-order rebalance: the full MC menu on one queue pipeline, exact
    pair/charge accounting throughout."""
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, CAP, N0, vth=1.0),
          pic.SpeciesConfig("D+", 1.0, 3672.0, CAP, N0, vth=0.02),
          pic.SpeciesConfig("D", 0.0, 3672.0, CAP, N0, vth=0.05))
    cfg = pic.PICConfig(
        nc=256, dx=1.0, dt=0.4, species=sp, field_solve=False,
        boundary="absorb", strategy="fused", collisions=MENU,
        ionization=(2, 0, 1), ionization_rate=3e-3, ionization_vth_e=1.0,
        wall_emission=((0, 0),), emission_yield=0.7, emission_vth=0.5)
    mesh = make_debug_mesh(data=4, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=512, max_births=512,
                               rebalance_every=3, cell_order=True)
    state = engine.init_engine_state(ecfg, mesh, 3)
    step = engine.make_engine_step(ecfg, mesh)
    sums: dict = {}
    for _ in range(12):
        state, diag = step(state)
        for k, v in diag.items():
            if (k in ("n_ionized", "birth_overflow") + COLL_KEYS
                    or k.endswith(("wall_absorbed", "emitted",
                                   "merge_dropped"))):
                sums[k] = sums.get(k, 0) + int(np.asarray(v))
    ion = sums["n_ionized"]
    assert ion > 0 and sums["coll_cx"] > 0 and sums["coll_elastic"] > 0
    absorbed = {s: sums.get(f"{s}/wall_absorbed", 0)
                for s in ("e", "D+", "D")}
    emitted = sums.get("e/emitted", 0)
    assert int(np.asarray(diag["e/count"])) == (
        N0 + ion + emitted - absorbed["e"])
    assert int(np.asarray(diag["D+/count"])) == N0 + ion - absorbed["D+"]
    assert int(np.asarray(diag["D/count"])) == N0 - ion - absorbed["D"]
    assert float(np.asarray(diag["D/charge"])) == 0.0
    assert sums.get("e/merge_dropped", 0) == 0


# ------------------------------------------------------------- 4-device tests


def test_collision_parity_multidomain():
    _dispatch("check_collision_parity_multidomain")


def test_collisions_with_all_sources_multidomain():
    _dispatch("check_collisions_with_all_sources_multidomain")
