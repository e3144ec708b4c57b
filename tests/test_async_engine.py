"""Asynchronous multi-device engine: D/async_n parity, migration-overflow
retention, halo field correctness, and the no-full-rho-all_gather guarantee.

Multi-device checks need 4 devices: when the process already exposes them
(the CI multi-device lane sets XLA_FLAGS) they run in-process; otherwise
each check re-runs itself in a subprocess with 4 emulated host devices.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fields, pic
from repro.distributed import engine, halo
from repro.launch.mesh import make_debug_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
HERE = os.path.dirname(__file__)


def _dispatch(func_name: str) -> None:
    """Run a check in-process when 4 devices exist, else in a subprocess."""
    if jax.device_count() >= 4:
        globals()[func_name]()
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + HERE
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    prog = f"from test_async_engine import {func_name}; {func_name}()"
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]


def _cfg(nc=256, *, field_solve=True, boundary="periodic", strategy="fused",
         n=4096, cap=8192, dt=0.2):
    sp = (
        pic.SpeciesConfig("e", -1.0, 1.0, cap, n, vth=1.0, weight=0.02),
        pic.SpeciesConfig("D+", 1.0, 3672.0, cap, n, vth=0.02, weight=0.02),
    )
    return pic.PICConfig(nc=nc, dx=1.0, dt=dt, species=sp,
                         field_solve=field_solve, boundary=boundary,
                         strategy=strategy)


def _run(cfg, d, async_n, steps, *, max_migration=1024, seed=0,
         rebalance_every=0):
    """Run the engine; returns (final diag, accumulated sums)."""
    mesh = make_debug_mesh(data=d, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",),
                               async_n=async_n, max_migration=max_migration,
                               rebalance_every=rebalance_every)
    state = engine.init_engine_state(ecfg, mesh, seed)
    step = engine.make_engine_step(ecfg, mesh)
    sums = {}
    for _ in range(steps):
        state, diag = step(state)
        for k in diag:
            if k.endswith(("migration_overflow", "merge_dropped",
                           "migrated_left", "migrated_right",
                           "wall_absorbed")):
                sums[k] = sums.get(k, 0) + int(np.asarray(diag[k]))
    out = {k: (float(np.asarray(v)) if np.asarray(v).ndim == 0
               else np.asarray(v)) for k, v in diag.items()}
    return out, sums


# ---------------------------------------------------------------- in-process


def test_overflow_keeps_particles():
    """Seed regression: crossers beyond the migration pack used to vanish.

    A hot plasma with a tiny send budget must now conserve the population
    exactly, reporting the unpacked crossers via ``migration_overflow``."""
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, 2048, 1024, vth=3.0),)
    cfg = pic.PICConfig(nc=32, dx=1.0, dt=2.0, species=sp, field_solve=False,
                        boundary="periodic")
    diag, sums = _run(cfg, 1, 1, 10, max_migration=8)
    assert int(diag["e/count"]) == 1024          # nothing lost
    assert sums["e/migration_overflow"] > 0      # ...and the overflow is real
    assert sums["e/merge_dropped"] == 0


@pytest.mark.parametrize("async_n", [1, 2, 4])
def test_engine_matches_single_domain_reference(async_n):
    """D=1 engine vs the plain fused hot loop, from the SAME initial state:
    population and charge exact, energy equal to float tolerance, for
    every queue count."""
    cfg = _cfg(nc=128, n=2048, cap=4096)
    state0 = pic.init_state(cfg, 7)
    ref_state, _ = jax.block_until_ready(pic.run(cfg, 15, state=state0))
    ref_counts = [int(b.count()) for b in ref_state.species]
    ref_ke = [float(np.asarray(
        jnp.sum(jnp.where(b.alive, 0.5 * sc.mass * jnp.sum(b.v * b.v, -1)
                          * b.w, 0.0))))
        for sc, b in zip(cfg.species, ref_state.species)]

    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",),
                               async_n=async_n, max_migration=512)
    state0 = pic.init_state(cfg, 7)              # rebuild: ref run donated it
    est = pic.PICState(
        species=tuple(jax.tree.map(lambda a: a[None], b)
                      for b in state0.species),
        key=state0.key[None], step=state0.step, rho=state0.rho[None])
    # externally built PICState: the engine wraps it (free-slot rings from
    # the alive masks, no in-flight arrivals)
    est = engine.attach_engine_state(ecfg, mesh, est)
    step = engine.make_engine_step(ecfg, mesh)
    for _ in range(15):
        est, diag = step(est)
    for i, sc in enumerate(cfg.species):
        assert int(np.asarray(diag[f"{sc.name}/count"])) == ref_counts[i]
        np.testing.assert_allclose(
            float(np.asarray(diag[f"{sc.name}/ke"])), ref_ke[i], rtol=2e-4)


def test_async_n_must_divide_budget_and_capacity():
    with pytest.raises(ValueError):
        engine.EngineConfig(pic=_cfg(), async_n=3, max_migration=1024)
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=_cfg(cap=8192, n=4096), async_n=5,
                               max_migration=1000)
    with pytest.raises(ValueError):
        engine.make_engine_step(ecfg, mesh)


# ------------------------------------------------- 4-device checks (impl)


def check_domain_parity():
    """D in {1, 2, 4} x async_n in {1, 2, 4}, with and without queue
    rebalancing: particle count must match the synchronous D=1 reference
    EXACTLY (conservation — including across rebalance_every boundaries).
    Total charge is an f32 sum whose last bits depend on the reduction
    order, which differs with the layout: it must agree within the pairwise
    summation bound log2(count) * count * weight * eps — far below the
    charge of one lost particle. Kinetic energy agrees statistically
    (domains draw independent samples)."""
    cfg = _cfg()
    ref, ref_sums = _run(cfg, 1, 1, 20)
    eps = float(np.finfo(np.float32).eps)
    for d, an, reb in [(2, 1, 0), (2, 2, 0), (4, 1, 0), (4, 4, 0),
                       (1, 2, 3), (2, 2, 3), (4, 4, 3)]:
        diag, sums = _run(cfg, d, an, 20, rebalance_every=reb)
        for sc in cfg.species:
            count = diag[f"{sc.name}/count"]
            assert count == ref[f"{sc.name}/count"], (d, an, reb, sc.name)
            tol = np.ceil(np.log2(count)) * count * sc.weight * eps
            assert sc.weight > tol
            np.testing.assert_allclose(
                diag[f"{sc.name}/charge"], ref[f"{sc.name}/charge"], rtol=0,
                atol=tol, err_msg=str((d, an, reb, sc.name)))
            np.testing.assert_allclose(
                diag[f"{sc.name}/ke"], ref[f"{sc.name}/ke"], rtol=0.15)
            assert sums[f"{sc.name}/migration_overflow"] == 0
            assert sums[f"{sc.name}/merge_dropped"] == 0
            assert diag[f"{sc.name}/queue_occ"].shape == (an,)
        assert sums["e/migrated_left"] + sums["e/migrated_right"] > 0


def check_async_queue_parity():
    """At fixed D=4 the queue split is pure scheduling: async_n=1 and 4 see
    identical particles, so counts AND energies must agree tightly."""
    cfg = _cfg()
    a1, s1 = _run(cfg, 4, 1, 20)
    a4, s4 = _run(cfg, 4, 4, 20)
    for sc in cfg.species:
        assert a1[f"{sc.name}/count"] == a4[f"{sc.name}/count"]
        assert a1[f"{sc.name}/charge"] == a4[f"{sc.name}/charge"]
        np.testing.assert_allclose(a1[f"{sc.name}/ke"], a4[f"{sc.name}/ke"],
                                   rtol=1e-5)
    assert (s1["e/migrated_left"] + s1["e/migrated_right"]
            == s4["e/migrated_left"] + s4["e/migrated_right"])


def check_absorb_conservation():
    """Global absorbing walls: every particle is either still alive or was
    absorbed at a wall — the engine loses nothing in between. Absorption is
    the heaviest free-slot churn the ring sees, so run it both with and
    without periodic queue rebalancing."""
    cfg = _cfg(boundary="absorb", field_solve=False, strategy="unified")
    for reb in (0, 4):
        diag, sums = _run(cfg, 4, 2, 25, rebalance_every=reb)
        for sc in cfg.species:
            n0 = sc.n_init
            assert (int(diag[f"{sc.name}/count"])
                    + sums[f"{sc.name}/wall_absorbed"] == n0), (reb, sc.name)
            assert sums[f"{sc.name}/merge_dropped"] == 0
        assert sums["e/wall_absorbed"] > 0       # walls actually active


def _collect_collectives(jxp, out):
    for eqn in jxp.eqns:
        name = eqn.primitive.name
        if "all_gather" in name or name == "ppermute":
            out.append((name, [tuple(v.aval.shape) for v in eqn.invars]))
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(x, "jaxpr"):
                    _collect_collectives(x.jaxpr, out)
                elif hasattr(x, "eqns"):
                    _collect_collectives(x, out)
    return out


def check_no_full_rho_allgather():
    """The halo field phase must never all_gather an ng_local-sized array:
    the only gathers are the scalar prefix carries of the Poisson solve."""
    cfg = _cfg(nc=256)
    mesh = make_debug_mesh(data=4, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=512)
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh, donate=False)
    colls = _collect_collectives(jax.make_jaxpr(step)(state).jaxpr, [])
    gathers = [shapes for name, shapes in colls if "all_gather" in name]
    permutes = [shapes for name, shapes in colls if name == "ppermute"]
    assert gathers, "expected scalar prefix-carry gathers"
    for shapes in gathers:
        for shape in shapes:
            assert int(np.prod(shape, dtype=int)) <= 1, (
                f"non-scalar all_gather operand {shape} — the redundant "
                f"global field assembly is back")
    assert len(permutes) > 0                      # halo + migration rings


def check_halo_field_matches_global():
    """halo.field_phase on partial local slabs == the single-domain
    smooth->Poisson->E pipeline on the assembled global density."""
    from jax.sharding import PartitionSpec as P

    d, ncl = 4, 32
    ng = d * ncl + 1
    rng = np.random.RandomState(0)
    rho_g = rng.uniform(-1.0, 1.0, ng).astype(np.float32)
    # local slabs: interior shared nodes hold only a PARTIAL deposit on each
    # side (0.7 left copy / 0.3 right copy); halo_sum must reassemble them
    locs = np.zeros((d, ncl + 1), np.float32)
    for r in range(d):
        sl = rho_g[r * ncl: r * ncl + ncl + 1].copy()
        if r > 0:
            sl[0] *= 0.3
        if r < d - 1:
            sl[-1] *= 0.7
        locs[r] = sl

    mesh = make_debug_mesh(data=4, model=1)

    def local(rho):
        rho = rho[0]
        r = halo.rank(("data",))
        e = halo.field_phase(
            rho, dx=1.0, eps0=1.0, smoothing_passes=2, axis_names=("data",),
            mesh=mesh, is_first=r == 0, is_last=r == d - 1)
        return e[None]

    f = jax.shard_map(local, mesh=mesh, in_specs=(P("data"),),
                      out_specs=P("data"), check_vma=False)
    e_loc = np.asarray(jax.jit(f)(jnp.asarray(locs)))
    e_ref = np.asarray(fields.efield(fields.solve_poisson(
        fields.smooth_binomial(jnp.asarray(rho_g), 2), 1.0), 1.0))
    # float32 absolute error scales with |phi| ~ O(ng^2), not with |E|
    atol = 1e-4 * float(np.max(np.abs(e_ref)) + 1.0)
    for r in range(d):
        np.testing.assert_allclose(e_loc[r], e_ref[r * ncl: r * ncl + ncl + 1],
                                   rtol=1e-4, atol=atol)


# ------------------------------------------------------------- 4-device tests


def test_domain_parity():
    _dispatch("check_domain_parity")


def test_async_queue_parity():
    _dispatch("check_async_queue_parity")


def test_absorb_conservation():
    _dispatch("check_absorb_conservation")


def test_no_full_rho_allgather():
    _dispatch("check_no_full_rho_allgather")


def test_halo_field_matches_global():
    _dispatch("check_halo_field_matches_global")
