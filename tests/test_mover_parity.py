"""Mover-strategy parity + fused-cycle / donation regressions.

Every data-movement strategy must implement the SAME physics: identical
positions, velocities and wall-hit masks from identical inputs. The fused
strategy additionally returns the post-push charge density, which must match
a separate deposit over its output. The wall-emission cycle must invoke
exactly one push per species per step (the seed pushed emitting species
twice), and ``make_step`` must donate the particle buffers.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mover, pic
from repro.core.grid import Grid1D, deposit
from repro.core.particles import init_uniform, stack_species, unstack_species

ALL_STRATEGIES = ["unified", "explicit", "async_batched", "fused"]


def _population(n=4096, nc=128, vth=2.0, seed=11):
    g = Grid1D(nc=nc, dx=1.0)
    buf = init_uniform(jax.random.PRNGKey(seed), n, n - 64, g.length, vth)
    e = jax.random.normal(jax.random.PRNGKey(seed + 1), (g.ng,))
    return g, buf, e


@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
@pytest.mark.parametrize("boundary", ["periodic", "absorb", "open"])
def test_strategies_agree_on_state_and_wall_masks(strategy, boundary):
    g, buf, e = _population(vth=4.0)        # hot: plenty of wall crossers
    ref = mover.push(buf, e, g, -1.0, 0.2, strategy="unified",
                     boundary=boundary)
    res = mover.push(buf, e, g, -1.0, 0.2, strategy=strategy,
                     boundary=boundary)
    tol = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(res.buf.x), np.asarray(ref.buf.x),
                               **tol)
    np.testing.assert_allclose(np.asarray(res.buf.v), np.asarray(ref.buf.v),
                               **tol)
    assert (np.asarray(res.buf.alive) == np.asarray(ref.buf.alive)).all()
    assert (np.asarray(res.hit_left) == np.asarray(ref.hit_left)).all()
    assert (np.asarray(res.hit_right) == np.asarray(ref.hit_right)).all()
    if boundary == "absorb":
        assert int(jnp.sum(ref.hit_left | ref.hit_right)) > 0, \
            "test population should actually hit the walls"


def test_fused_rho_matches_separate_deposit():
    g, buf, e = _population()
    res = mover.push_fused(buf, e, g, -1.0, 0.1, boundary="periodic",
                           deposit_charge=-1.0)
    assert res.rho is not None
    want = deposit(g, res.buf, -1.0)
    np.testing.assert_allclose(np.asarray(res.rho), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_fused_without_deposit_returns_no_rho():
    g, buf, e = _population()
    res = mover.push_fused(buf, e, g, -1.0, 0.1, boundary="periodic")
    assert res.rho is None


def test_stacked_push_matches_per_species_loop():
    g, _, e = _population()
    bufs = [init_uniform(jax.random.PRNGKey(s), 2048, 2000, g.length, 1.0)
            for s in (0, 1, 2)]
    qm = jnp.asarray([-1.0, 0.5, 0.0])
    dt = jnp.asarray([0.1, 0.2, 0.1])
    st, hl, hr, diag, rho = mover.push_stacked(
        stack_species(bufs), e, g, qm, dt, boundary="absorb",
        charges=jnp.asarray([-1.0, 1.0, 0.0]))
    outs = unstack_species(st)
    rho_ref = jnp.zeros_like(rho)
    for s, buf in enumerate(bufs):
        ref = mover.push(buf, e, g, float(qm[s]), float(dt[s]),
                         strategy="unified", boundary="absorb")
        np.testing.assert_allclose(np.asarray(outs[s].x),
                                   np.asarray(ref.buf.x), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(outs[s].v),
                                   np.asarray(ref.buf.v), rtol=2e-5,
                                   atol=2e-5)
        assert (np.asarray(hl[s]) == np.asarray(ref.hit_left)).all()
        assert (np.asarray(hr[s]) == np.asarray(ref.hit_right)).all()
        for k in ("absorbed_left", "absorbed_right"):
            assert int(diag[k][s]) == int(ref.diag[k])
        rho_ref = rho_ref + deposit(g, ref.buf, float([-1.0, 1.0, 0.0][s]))
    np.testing.assert_allclose(np.asarray(rho), np.asarray(rho_ref),
                               rtol=1e-4, atol=1e-4)


def _stacked_population(qm):
    g, _, _ = _population()
    bufs = [init_uniform(jax.random.PRNGKey(s), 2048, 2000, g.length, 3.0)
            for s in range(len(qm))]
    return g, stack_species(bufs), jnp.asarray(qm), jnp.asarray(
        [0.1, 0.2, 0.1][:len(qm)])


def _assert_push_outputs(got, want, eq):
    """``eq(a, b)`` on every output of ``push_stacked``: the stacked
    species' leaves, both wall masks, each diag value and rho."""
    (st, hl, hr, diag, rho), (st_w, hl_w, hr_w, diag_w, rho_w) = got, want
    for name in ("x", "v", "alive", "w"):
        assert eq(getattr(st, name), getattr(st_w, name)), name
    assert eq(hl, hl_w) and eq(hr, hr_w)
    assert diag.keys() == diag_w.keys()
    for k in diag:
        assert eq(diag[k], diag_w[k]), k
    assert (rho is None) == (rho_w is None)
    if rho is not None:
        assert eq(rho, rho_w)


def _by_value(a, b):
    return bool((np.asarray(a) == np.asarray(b)).all())


@pytest.mark.parametrize("b", [(0.0, 0.0, 0.0), (0.3, 0.0, 0.5)],
                         ids=["b0", "b"])
@pytest.mark.parametrize("boundary", ["periodic", "absorb", "open"])
def test_stacked_push_without_field_equals_zero_field(boundary, b):
    """``e=None`` (no field solve: no gather, no kick) moves every row
    exactly as a gathered E of zeros does. Compared by value: the zero
    kick's ``v + 0.0`` may turn a -0.0 into +0.0, skipping it may not."""
    g, st, qm, dt = _stacked_population([-1.0, 0.5, 0.0])
    kw = dict(b=b, boundary=boundary, charges=jnp.asarray([-1.0, 1.0, 0.0]))
    got = mover.push_stacked(st, None, g, qm, dt, **kw)
    want = mover.push_stacked(st, jnp.zeros((g.ng,)), g, qm, dt, **kw)
    _assert_push_outputs(got, want, _by_value)
    if boundary == "absorb":
        assert int(jnp.sum(got[1] | got[2])) > 0


@pytest.mark.parametrize("qm", [[-1.0, 0.5, 0.0], [-1.0, 0.0, 0.5]],
                         ids=["neutral_last", "neutral_between"])
@pytest.mark.parametrize("gather_mode", ["take", "onehot"])
def test_stacked_push_gathers_charged_rows_only(gather_mode, qm):
    """With a nonzero E, gathering only the charged rows moves them
    bitwise as the gather over every row does, and the neutral row (q/m =
    0, kicked by a folded zero) by value."""
    g, st, qm_a, dt = _stacked_population(qm)
    e = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (g.ng,))
    charged = tuple(j for j, q in enumerate(qm) if q != 0.0)
    kw = dict(boundary="absorb", gather_mode=gather_mode,
              charges=jnp.asarray(qm))
    got = mover.push_stacked(st, e, g, qm_a, dt, gather_rows=charged, **kw)
    want = mover.push_stacked(st, e, g, qm_a, dt, **kw)
    for j in range(len(qm)):
        eq = ((lambda a, b: np.asarray(a).tobytes()
               == np.asarray(b).tobytes()) if j in charged else _by_value)
        row = lambda out: (jax.tree.map(lambda a: a[j], out[0]), out[1][j],
                           out[2][j], {k: v[j] for k, v in out[3].items()},
                           None)
        _assert_push_outputs(row(got), row(want), eq)
    assert _by_value(got[4], want[4])           # the deposit of all rows
    assert not _by_value(got[0].v[charged[0]], st.v[charged[0]])  # kicked


@pytest.mark.parametrize("cfg, rows", [
    ("bench", 0), ("resilience", 2), ("two_neutrals", 1)])
def test_engine_field_gather_rows(cfg, rows):
    """The built step gathers E for no row with the field off, and with
    it on for the charged species alone."""
    from repro.configs.pic_bit1 import (make_bench_config,
                                        make_engine_config,
                                        make_resilience_config)

    if cfg == "bench":
        pcfg = make_bench_config(nc=64, n=1024)
    else:
        pcfg = make_resilience_config(nc=64, n=1024)
    if cfg == "two_neutrals":
        pcfg = pic.PICConfig(nc=64, dx=1.0, dt=0.2, species=(
            pic.SpeciesConfig("D", 0.0, 3672.0, 2048, 1024, vth=0.05),
            pic.SpeciesConfig("e", -1.0, 1.0, 2048, 1024, vth=1.0),
            pic.SpeciesConfig("H", 0.0, 1836.0, 2048, 1024, vth=0.05)))
    assert make_engine_config(pcfg).field_gather_rows == rows


def _wall_cfg(cap_primary=4096, cap_target=4096, strategy="unified"):
    sp = (
        pic.SpeciesConfig("e", -1.0, 1.0, cap_primary, cap_primary // 2,
                          vth=1.5),
        pic.SpeciesConfig("i", 1.0, 1836.0, cap_target, cap_target // 2,
                          vth=0.02),
    )
    return pic.PICConfig(
        nc=64, dx=1.0, dt=0.2, species=sp, field_solve=False,
        boundary="absorb", strategy=strategy,
        wall_emission=((0, 0),), emission_yield=0.7, emission_vth=0.5)


@pytest.mark.parametrize("stacked", [True, False])
def test_wall_emission_invokes_exactly_one_push_per_species(
        stacked, monkeypatch):
    """Regression: the seed pushed wall-emitting species twice per step (an
    extra open-boundary push just to learn the wall masks)."""
    # equal capacities -> stacked vmap path; unequal -> per-species loop
    cfg = _wall_cfg(cap_target=4096 if stacked else 2048)
    state = pic.init_state(cfg, 0)

    pushes = {"n": 0}
    real_push, real_stacked = mover.push, mover.push_stacked

    def counting_push(buf, *a, **kw):
        pushes["n"] += 1
        return real_push(buf, *a, **kw)

    def counting_stacked(st, *a, **kw):
        pushes["n"] += st.num_species
        return real_stacked(st, *a, **kw)

    monkeypatch.setattr(pic.mover, "push", counting_push)
    monkeypatch.setattr(pic.mover, "push_stacked", counting_stacked)
    _, diag = pic.step_fn(state, cfg)
    assert pushes["n"] == len(cfg.species), \
        f"expected one push per species, counted {pushes['n']}"
    # and the emission source actually fired off those single pushes
    assert int(diag["e/absorbed_left"]) + int(diag["e/absorbed_right"]) > 0
    assert int(diag["e/emitted"]) > 0


def test_wall_emission_cycle_matches_seed_semantics():
    """The mask-driven SEE path must reproduce the double-push seed numbers:
    same absorbed counts and an emission stream tracking the yield."""
    cfg = _wall_cfg()
    state = pic.init_state(cfg, 3)
    step = pic.make_step(cfg)
    absorbed = emitted = 0
    for _ in range(20):
        state, diag = step(state)
        absorbed += int(diag["e/absorbed_left"]) + int(
            diag["e/absorbed_right"])
        emitted += int(diag["e/emitted"])
    assert absorbed > 100
    assert 0.5 * absorbed < emitted < 0.9 * absorbed


def test_make_step_donates_particle_buffers():
    cfg = pic.PICConfig(
        nc=64, dx=1.0, dt=0.1, field_solve=False,
        species=(pic.SpeciesConfig("e", -1.0, 1.0, 1024, 1024, vth=1.0),))
    state = pic.init_state(cfg, 0)
    old_x = state.species[0].x
    step = pic.make_step(cfg)
    state, _ = step(state)
    assert np.isfinite(np.asarray(state.species[0].x)).all()
    # the input buffers were donated to the step: the old state is dead
    with pytest.raises(RuntimeError):
        np.asarray(old_x)


def test_fused_carried_rho_matches_unified_field_cycle():
    sp = (
        pic.SpeciesConfig("e", -1.0, 1.0, 2048, 2048, vth=0.5,
                          weight=64 / 2048.0),
        pic.SpeciesConfig("i", 1.0, 1836.0, 2048, 2048, vth=0.01,
                          weight=64 / 2048.0),
    )
    base = pic.PICConfig(nc=64, dx=1.0, dt=0.1, species=sp, field_solve=True)
    fused = dataclasses.replace(base, strategy="fused")
    su, _ = pic.run(base, 5, seed=0)
    sf, _ = pic.run(fused, 5, seed=0)
    assert sf.rho is not None            # the fused cycle carries its deposit
    for bu, bf in zip(su.species, sf.species):
        np.testing.assert_allclose(np.asarray(bf.x), np.asarray(bu.x),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(bf.v), np.asarray(bu.v),
                                   rtol=1e-3, atol=1e-3)


def test_fused_run_warm_starts_from_non_fused_state():
    """run() must backfill the carried rho when handed a state produced
    under a different strategy (lax.scan needs one carry structure)."""
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, 1024, 1024, vth=0.5,
                            weight=0.05),)
    base = pic.PICConfig(nc=64, dx=1.0, dt=0.1, species=sp, field_solve=True)
    state = pic.init_state(base, 0)          # rho is None here
    fused = dataclasses.replace(base, strategy="fused")
    final, _ = pic.run(fused, 3, state=state)
    assert final.rho is not None
    assert np.isfinite(np.asarray(final.species[0].x)).all()


def test_config_accepts_list_species_and_stays_hashable():
    sp = [pic.SpeciesConfig("e", -1.0, 1.0, 256, 256, vth=1.0)]
    cfg = pic.PICConfig(nc=32, dx=1.0, dt=0.1, species=sp, field_solve=False,
                        wall_emission=[(0, 0)])
    assert isinstance(cfg.species, tuple)
    hash(cfg)                                # static jit argument contract
    final, _ = pic.run(cfg, 2, seed=0)       # cfg rides through static jit
    assert int(final.species[0].count()) == 256


def test_diag_every_rate_limits_reductions():
    cfg = pic.PICConfig(
        nc=64, dx=1.0, dt=0.1, field_solve=False, diag_every=2,
        species=(pic.SpeciesConfig("e", -1.0, 1.0, 512, 512, vth=1.0),))
    state = pic.init_state(cfg, 0)
    step = pic.make_step(cfg)
    state, d0 = step(state)              # step 0: diag computed
    state, d1 = step(state)              # step 1: skipped -> zeros
    state, d2 = step(state)              # step 2: computed again
    assert int(d0["e/count"]) == 512 and int(d2["e/count"]) == 512
    assert int(d1["e/count"]) == 0
    assert float(d1["e/ke"]) == 0.0
    assert float(d0["e/ke"]) > 0.0


def test_config_validation_messages():
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, 100, 100, vth=1.0),)
    with pytest.raises(ValueError, match="unknown mover strategy"):
        pic.PICConfig(species=sp, strategy="warp")
    with pytest.raises(ValueError, match="unknown boundary"):
        pic.PICConfig(species=sp, boundary="reflect")
    with pytest.raises(ValueError, match="diag_every"):
        pic.PICConfig(species=sp, diag_every=0)
    with pytest.raises(ValueError, match="async_batched"):
        pic.PICConfig(species=sp, strategy="async_batched", num_batches=3)
    with pytest.raises(ValueError, match="divisible by num_batches"):
        g = Grid1D(nc=16, dx=1.0)
        buf = init_uniform(jax.random.PRNGKey(0), 100, 100, g.length, 1.0)
        mover.push_async_batched(buf, jnp.zeros(g.ng), g, -1.0, 0.1,
                                 num_batches=3)
