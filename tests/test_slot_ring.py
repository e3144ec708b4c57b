"""Persistent free-slot ring: unit semantics, engine-state invariant, queue
rebalance, and the merge-scaling regression.

The ring (``core/particles.FreeSlotRing``) replaces the merge phase's
full-capacity ``free_slots`` scan in the distributed engine; these tests pin

* the FIFO semantics (push/claim/wraparound/exhaustion) against a plain
  Python model,
* the engine invariant: at every step boundary the ring's live entries plus
  the in-flight pending destinations are EXACTLY the dead slots,
* that ``rebalance_every`` re-evens a skewed queue split, and
* the capacity-scaling regression: no full-capacity cumsum survives in the
  step (the old merge's ``free_slots`` scan was one per species per step).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import pic
from repro.core.particles import (FreeSlotRing, SpeciesBuffer, inject_at,
                                  inject_masked, make_species, ring_claim,
                                  ring_from_counts, ring_init, ring_push)
from repro.distributed import engine
from repro.launch.mesh import make_debug_mesh

try:                                   # gated like the other property suites
    from hypothesis import given, settings, strategies as hyp_st
    HAVE_HYPOTHESIS = True
except ImportError:                    # pragma: no cover - optional dep
    HAVE_HYPOTHESIS = False

    def given(*a, **k):                # no-op decorators keep collection sane
        return lambda f: f

    settings = given

    class hyp_st:                      # type: ignore[no-redef]
        @staticmethod
        def integers(*a, **k):
            return None

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed")


# ------------------------------------------------------------------ unit


def test_ring_fifo_model_with_wraparound():
    """Random push/claim traffic vs a Python FIFO model; the ring is small
    enough that the cursors wrap several times."""
    cap = 24
    rng = np.random.RandomState(3)
    alive0 = rng.rand(cap) < 0.5
    ring = ring_init(jnp.asarray(alive0))
    model = [int(i) for i in np.nonzero(~alive0)[0]]
    free = set(model)
    alive = alive0.copy()
    pushed_total = 0
    for _ in range(40):
        # free a few random alive slots (a kill), push their indices
        kill_idx = np.asarray([i for i in np.nonzero(alive)[0][:3]])
        m = 4
        idx = np.full((m,), cap)
        ok = np.zeros((m,), bool)
        idx[: len(kill_idx)] = kill_idx
        ok[: len(kill_idx)] = True
        alive[kill_idx] = False
        ring = ring_push(ring, jnp.asarray(idx), jnp.asarray(ok))
        model.extend(int(i) for i in kill_idx)
        free.update(int(i) for i in kill_idx)
        pushed_total += len(kill_idx)
        # claim a few slots back (an inject)
        want = jnp.asarray(rng.rand(5) < 0.7)
        ring, dest, got = ring_claim(ring, want, cap)
        dest, got = np.asarray(dest), np.asarray(got)
        for j in range(5):
            if got[j]:
                expect = model.pop(0)
                assert int(dest[j]) == expect
                alive[expect] = True
                free.discard(expect)
            else:
                assert int(dest[j]) == cap
        assert int(ring.count) == len(model)
        # live window of the ring matches the model, in order
        r = ring.slots.shape[0]
        live = [int(ring.slots[(int(ring.head) + i) % r])
                for i in range(int(ring.count))]
        assert live == model
    assert pushed_total > cap          # cursors wrapped at least once


def test_ring_claim_exhaustion_is_ordered():
    """When the ring runs dry mid-claim, the FIRST candidates win and the
    tail is refused with the sentinel."""
    alive = jnp.ones((8,), bool).at[jnp.asarray([2, 5])].set(False)
    ring = ring_init(alive)
    ring, dest, ok = ring_claim(ring, jnp.ones((4,), bool), 8)
    np.testing.assert_array_equal(np.asarray(dest), [2, 5, 8, 8])
    np.testing.assert_array_equal(np.asarray(ok), [True, True, False, False])
    assert int(ring.count) == 0
    # pushing one slot revives exactly one claim
    ring = ring_push(ring, jnp.asarray([5]), jnp.asarray([True]))
    ring, dest, ok = ring_claim(ring, jnp.ones((2,), bool), 8)
    np.testing.assert_array_equal(np.asarray(dest), [5, 8])


def test_ring_from_counts_matches_ring_init_on_compacted():
    """After a compaction (alive-first), the closed-form ring equals the
    scanned one."""
    for n_alive in (0, 3, 8):
        alive = jnp.arange(8) < n_alive
        a = ring_init(alive)
        b = ring_from_counts(jnp.asarray(n_alive, jnp.int32), 8)
        assert int(a.count) == int(b.count) == 8 - n_alive
        np.testing.assert_array_equal(
            np.asarray(a.slots)[: 8 - n_alive],
            np.asarray(b.slots)[: 8 - n_alive])


def test_inject_at_is_the_inject_masked_scatter():
    """inject_masked == free_slots scan + inject_at: the two injection paths
    share one scatter and cannot diverge."""
    buf = make_species(16)
    buf = SpeciesBuffer(x=buf.x, v=buf.v, w=buf.w,
                        alive=jnp.arange(16) < 12)
    x = jnp.arange(6, dtype=jnp.float32)
    v = jnp.ones((6, 3), jnp.float32)
    w = jnp.full((6,), 2.0)
    mask = jnp.asarray([True, True, False, True, True, True])
    out, dropped, ok = inject_masked(buf, x, v, w, mask)
    # 4 free slots, 5 wanted: one drop
    assert int(dropped) == 1
    assert int(out.count()) == 16
    ring = ring_init(buf.alive)
    ring, dest, ok2 = ring_claim(ring, mask, 16)
    out2 = inject_at(buf, dest, x, v, w, ok2)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(out2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- hypothesis properties


def _ring_window(ring) -> list[int]:
    """The live FIFO window of a ring, in claim order."""
    r = ring.slots.shape[0]
    return [int(ring.slots[(int(ring.head) + i) % r])
            for i in range(int(ring.count))]


@needs_hypothesis
@settings(max_examples=25, deadline=None)
@given(cap=hyp_st.integers(4, 48), seed=hyp_st.integers(0, 2 ** 16),
       rounds=hyp_st.integers(1, 24))
def test_ring_property_interleaved_leaver_birth_traffic(cap, seed, rounds):
    """Random interleaved push (leavers/kills) and claim (births/arrivals)
    traffic against a Python FIFO model: the live window is the model
    exactly, through wraparound, ring-full and ring-empty edges."""
    rng = np.random.RandomState(seed)
    alive = rng.rand(cap) < rng.rand()
    ring = ring_init(jnp.asarray(alive))
    model = [int(i) for i in np.nonzero(~alive)[0]]
    for _ in range(rounds):
        # a leaver burst: kill up to 3 alive slots, push their indices
        kill_idx = np.nonzero(alive)[0][: rng.randint(0, 4)]
        m = 4
        idx = np.full((m,), cap)
        ok = np.zeros((m,), bool)
        idx[: len(kill_idx)] = kill_idx
        ok[: len(kill_idx)] = True
        alive[kill_idx] = False
        ring = ring_push(ring, jnp.asarray(idx), jnp.asarray(ok))
        model.extend(int(i) for i in kill_idx)
        # a birth burst: claim up to 5 slots back, optionally budget-capped
        want = rng.rand(5) < rng.rand()
        budget = rng.randint(0, 6) if rng.rand() < 0.5 else None
        ring, dest, got = ring_claim(
            ring, jnp.asarray(want), cap,
            None if budget is None else jnp.asarray(budget, jnp.int32))
        grants = 0
        for j in range(5):
            if bool(got[j]):
                expect = model.pop(0)
                assert int(dest[j]) == expect
                alive[expect] = True
                grants += 1
            else:
                assert int(dest[j]) == cap
        if budget is not None:
            assert grants <= budget
        assert int(ring.count) == len(model)
        assert _ring_window(ring) == model
    # ring-empty edge: drain everything, then over-claim
    ring, dest, got = ring_claim(ring, jnp.ones((cap + 1,), bool), cap)
    assert int(np.asarray(got).sum()) == len(model)
    assert int(ring.count) == 0
    for j in range(cap + 1):
        if bool(got[j]):
            alive[int(dest[j])] = True   # drained slots are occupied now
    # ring-full edge: kill every live slot -> the window is the capacity
    to_kill = np.nonzero(alive)[0]
    full_ring = ring
    for start in range(0, len(to_kill), 4):
        chunk = to_kill[start: start + 4]
        idx = np.full((4,), cap)
        ok = np.zeros((4,), bool)
        idx[: len(chunk)] = chunk
        ok[: len(chunk)] = True
        full_ring = ring_push(full_ring, jnp.asarray(idx), jnp.asarray(ok))
    assert int(full_ring.count) == cap
    assert sorted(_ring_window(full_ring)) == list(range(cap))


@needs_hypothesis
@settings(max_examples=25, deadline=None)
@given(cap=hyp_st.integers(1, 64), n_alive=hyp_st.integers(0, 64))
def test_ring_from_counts_property(cap, n_alive):
    """The closed-form post-compaction ring equals the scanned one for any
    (capacity, alive-count) pair."""
    n_alive = min(n_alive, cap)
    alive = jnp.arange(cap) < n_alive
    a, b = ring_init(alive), ring_from_counts(
        jnp.asarray(n_alive, jnp.int32), cap)
    assert int(a.count) == int(b.count) == cap - n_alive
    assert _ring_window(a) == _ring_window(b)


@needs_hypothesis
@settings(max_examples=25, deadline=None)
@given(seed=hyp_st.integers(0, 2 ** 16), budget=hyp_st.integers(0, 10))
def test_ring_claim_budget_equals_external_clamp(seed, budget):
    """claim(want, budget=B) == claim(want clamped to its first B winners):
    the paired-birth budget path cannot diverge from explicit masking."""
    cap = 24
    rng = np.random.RandomState(seed)
    alive = rng.rand(cap) < 0.5
    want = jnp.asarray(rng.rand(8) < 0.7)
    ring = ring_init(jnp.asarray(alive))
    r1, d1, o1 = ring_claim(ring, want, cap,
                            jnp.asarray(budget, jnp.int32))
    rank = np.cumsum(np.asarray(want).astype(int)) - 1
    clamped = jnp.asarray(np.asarray(want) & (rank < budget))
    r2, d2, o2 = ring_claim(ring, clamped, cap)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert int(r1.count) == int(r2.count) and int(r1.head) == int(r2.head)


# ------------------------------------------------- engine-state invariant


def _engine_cfg(cap=2048, n=1024, nc=64, **kw):
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, cap, n, vth=1.0, weight=0.02),
          pic.SpeciesConfig("D+", 1.0, 3672.0, cap, n, vth=0.02,
                            weight=0.02))
    kw.setdefault("field_solve", True)
    kw.setdefault("boundary", "periodic")
    kw.setdefault("strategy", "fused")
    kw.setdefault("dt", 0.5)
    return pic.PICConfig(nc=nc, dx=1.0, species=sp, **kw)


def _ring_sets(estate, ecfg, mesh):
    """{(group, species): (ring slots in FIFO order, pending dests)}."""
    out = {}
    groups = engine._capacity_groups(ecfg, mesh)
    for g, idxs in enumerate(groups):
        ring = jax.tree.map(lambda a: np.asarray(a)[0], estate.rings[g])
        pend = jax.tree.map(lambda a: np.asarray(a)[0], estate.pending[g])
        r = ring.slots.shape[-1]
        for j, i in enumerate(idxs):
            cnt, head = int(ring.count[j]), int(ring.head[j])
            live = [int(ring.slots[j][(head + t) % r]) for t in range(cnt)]
            dests = [int(d) for d, a in zip(pend.dest[j], pend.alive[j])
                     if a]
            out[(g, i)] = (live, dests)
    return out


def test_engine_ring_invariant_after_kill_inject_migrate():
    """After any number of steps, ring ∪ pending-dest is EXACTLY the dead
    slot set of each species buffer — listed once each (no leaks, no
    double-frees, no claims of live slots)."""
    cfg = _engine_cfg(dt=1.5)           # hot: plenty of migration churn
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=256, rebalance_every=3)
    state = engine.init_engine_state(ecfg, mesh, 1)
    step = engine.make_engine_step(ecfg, mesh)
    for it in range(8):
        state, diag = step(state)
        sets = _ring_sets(state, ecfg, mesh)
        for (g, i), (live, dests) in sets.items():
            alive = np.asarray(state.pic.species[i].alive)[0]
            dead = set(int(s) for s in np.nonzero(~alive)[0])
            assert len(live) == len(set(live)), (it, i, "ring dup")
            assert len(dests) == len(set(dests)), (it, i, "dest dup")
            assert set(live).isdisjoint(dests), (it, i, "claimed twice")
            assert set(live) | set(dests) == dead, (it, i, "free-set drift")
        # the churn is real: arrivals are actually in flight
    assert int(np.asarray(diag["e/count"])) == 1024
    assert sum(int(np.asarray(diag[f"{s}/count"]))
               for s in ("e", "D+")) == 2048


def test_engine_ring_invariant_with_mc_sources():
    """The free-set invariant must survive the MC sources too: ionization
    kills push neutral slots, pair births and SEE secondaries hold eager
    pre-claims in pending — ring ∪ pending-dest stays EXACTLY the dead
    set (a half-claimed pair or a leaked emission slot would drift it)."""
    cfg = _mc_cfg(2048, ionization=True, see=True)
    cfg = dataclasses.replace(cfg, dt=0.5,
                              ionization_rate=5e-3)   # hot MC churn
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=256, max_births=256,
                               rebalance_every=3)
    state = engine.init_engine_state(ecfg, mesh, 1)
    step = engine.make_engine_step(ecfg, mesh)
    born = 0
    for it in range(8):
        state, diag = step(state)
        born += int(np.asarray(diag["n_ionized"]))
        sets = _ring_sets(state, ecfg, mesh)
        for (g, i), (live, dests) in sets.items():
            alive = np.asarray(state.pic.species[i].alive)[0]
            dead = set(int(s) for s in np.nonzero(~alive)[0])
            assert len(live) == len(set(live)), (it, i, "ring dup")
            assert len(dests) == len(set(dests)), (it, i, "dest dup")
            assert set(live).isdisjoint(dests), (it, i, "claimed twice")
            assert set(live) | set(dests) == dead, (it, i, "free-set drift")
    assert born > 0                       # the churn is real


def test_rebalance_resplits_skewed_occupancy():
    """A maximally skewed split (all live slots in even positions == queue 0)
    must come back even after one rebalance boundary, and stay conserved.

    The rebalance runs at ingest and splits the live rows exactly; the same
    step's migration then moves each crosser out of its queue and into a
    ring-claimed slot that may sit in the other queue, shifting the skew by
    at most 2 per crosser."""
    cap, n = 1024, 256
    cfg = _engine_cfg(cap=cap, n=n, dt=0.1)
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=256, rebalance_every=1)
    # hand-build a state whose live slots all sit in queue 0 (even slots)
    key = jax.random.PRNGKey(0)
    bufs = []
    for sc in cfg.species:
        key, k1, k2 = jax.random.split(key, 3)
        alive = (jnp.arange(cap) % 2 == 0) & (jnp.arange(cap) < 2 * n)
        x = jax.random.uniform(k1, (cap,), jnp.float32, 0.0, cfg.length)
        v = sc.vth * jax.random.normal(k2, (cap, 3), jnp.float32)
        w = jnp.where(alive, sc.weight, 0.0)
        bufs.append(SpeciesBuffer(x=x, v=v, w=w, alive=alive))
    rho = pic.compute_rho(cfg, tuple(bufs))
    pstate = pic.PICState(
        species=tuple(jax.tree.map(lambda a: a[None], b) for b in bufs),
        key=jax.random.PRNGKey(9)[None], step=jnp.ones((), jnp.int32),
        rho=rho[None])
    estate = engine.attach_engine_state(ecfg, mesh, pstate)
    step = engine.make_engine_step(ecfg, mesh)
    estate, diag = step(estate)          # step % 1 == 0 -> rebalances
    for sc in cfg.species:
        occ = np.asarray(diag[f"{sc.name}/queue_occ"])
        assert int(np.asarray(diag[f"{sc.name}/count"])) == n, sc.name
        assert occ.sum() <= n            # pending rows are not resident yet
        crossers = sum(int(np.asarray(diag[f"{sc.name}/{k}"]))
                       for k in ("migrated_left", "migrated_right"))
        skew = int(np.asarray(diag[f"{sc.name}/queue_skew"]))
        assert skew <= 1 + 2 * crossers, (occ, crossers)
        assert skew < n // 4, occ       # 256 before the rebalance


# ------------------------------------------------- merge-scaling regression


def _collect_cumsum_shapes(jxp, out):
    for eqn in jxp.eqns:
        if eqn.primitive.name == "cumsum":
            out.extend(tuple(v.aval.shape) for v in eqn.invars)
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(x, "jaxpr"):
                    _collect_cumsum_shapes(x.jaxpr, out)
                elif hasattr(x, "eqns"):
                    _collect_cumsum_shapes(x, out)
    return out


@pytest.mark.parametrize("async_n", [1, 2, 4])
def test_merge_does_no_full_capacity_scan(async_n):
    """Regression for the merge-phase bottleneck: the step's only cumsums
    over a queue's slots (cap / async_n) are the migration packs', one per
    queue, and none runs over more. A merge that re-discovers dead slots
    with ``free_slots`` scans the whole capacity per species per step —
    what the persistent ring eliminated; at async_n = 1, where a queue is
    the whole capacity, it would show as a second scan of that length."""
    cap = 8192
    cfg = _engine_cfg(cap=cap, n=4096, nc=64)
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",),
                               async_n=async_n, max_migration=512)
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh, donate=False)
    shapes = _collect_cumsum_shapes(jax.make_jaxpr(step)(state).jaxpr, [])
    capq = cap // async_n
    n_groups = len(engine._capacity_groups(ecfg, mesh))
    queue_scans = [s for s in shapes if s and s[-1] == capq]
    assert len(queue_scans) == async_n * n_groups, shapes
    longer = [s for s in shapes if s and s[-1] > capq]
    assert not longer, (
        f"cumsum over more than a queue is back (shapes={longer}): the "
        f"merge phase scales with total capacity again")


def _mc_cfg(cap, *, ionization=False, see=False, field_solve=False):
    """3-species config with the MC sources the engine now ring-routes."""
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, cap, cap // 2, vth=1.0),
          pic.SpeciesConfig("D+", 1.0, 3672.0, cap, cap // 2, vth=0.02),
          pic.SpeciesConfig("D", 0.0, 3672.0, cap, cap // 2, vth=0.05))
    kw: dict = {}
    if ionization:
        kw.update(ionization=(2, 0, 1), ionization_rate=1e-3,
                  ionization_vth_e=1.0)
    if see:
        kw.update(boundary="absorb", wall_emission=((0, 0),),
                  emission_yield=0.5, emission_vth=0.5)
    return pic.PICConfig(nc=64, dx=1.0, dt=0.2, species=sp,
                         field_solve=field_solve, strategy="fused", **kw)


_MC_CASES = {
    "ionization": dict(ionization=True),
    "ionization+field": dict(ionization=True, field_solve=True),
    "see": dict(see=True),
    "ionization+see": dict(ionization=True, see=True),
}


@pytest.mark.parametrize("tag", list(_MC_CASES))
def test_mc_source_steps_do_no_full_capacity_scan(tag):
    """Ionization and SEE engine configs must compile with no
    full-capacity free-slot scan either: ionization packs its events per
    queue and its births pop pre-claimed ring slots; SEE claims off the
    already-packed absorbed rows. The assertion bites: the full-scan
    injection ``particles.inject_masked`` (which the single-domain step
    still runs, through ``collisions.ionize`` and ``boundaries``) shows a
    cumsum over the whole capacity."""
    cap = 8192
    mesh = make_debug_mesh(data=1, model=1)
    cfg = _mc_cfg(cap, **_MC_CASES[tag])
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=512, max_births=512)
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh, donate=False)
    shapes = _collect_cumsum_shapes(jax.make_jaxpr(step)(state).jaxpr, [])
    full = [s for s in shapes if s and s[-1] >= cap]
    assert not full, (
        f"[{tag}] full-capacity cumsum is back (shapes={full}): an MC "
        f"source re-introduced a capacity-scaling scan")
    m = 512
    buf = make_species(cap)
    scan = jax.make_jaxpr(inject_masked)(
        buf, jnp.zeros((m,)), jnp.zeros((m, 3)), jnp.zeros((m,)),
        jnp.ones((m,), bool))
    assert any(s and s[-1] >= cap
               for s in _collect_cumsum_shapes(scan.jaxpr, [])), (
        f"[{tag}] expected the full-scan injection to scan the capacity")
