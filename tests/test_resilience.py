"""Checkpoint/restore + elastic resilience for the async engine.

The layer this file pins (``ckpt/checkpoint.py`` + ``runtime/resilience.py``
+ the ``engine`` restore hooks):

* **bitwise restart** — checkpoint at step k, inject ``SimulatedFailure``,
  restore, run to k+m: every state leaf (particle buffers, rings, pending,
  carried rho, RNG keys, step) and every diagnostic of the resumed steps is
  bitwise-identical to the uninterrupted run, across D x async_n with
  ionization + SEE + collisions enabled;
* **elastic restore** — save at D, restore at D' != D: exact count/charge
  conservation across the boundary, the PR-5-style moment invariants over
  the continued run, and a jaxpr pin that the rebuild does NO full-capacity
  free-slot scan (``ring_from_counts``, not ``ring_init``); a checkpoint
  with no ``pending/*`` leaves resumes through the same path;
* **torn writes** — a writer killed between ``arrays.npz`` and
  ``manifest.json`` leaves a checkpoint restart scans straight past;
* **serialization** — ``_flatten``/unflatten round-trips the engine pytree
  (nested dataclasses, bf16, bool masks) bitwise, property-tested under
  hypothesis when available;
* the seed-module bug fixes: strict ``restore(like=...)`` key matching and
  fire-once ``FailureInjector``.
"""

import dataclasses
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint
from repro.ckpt.checkpoint import Checkpointer
from repro.configs.pic_bit1 import (make_collision_config,
                                    make_engine_config,
                                    make_resilience_config)
from repro.core.particles import FreeSlotRing
from repro.distributed import engine
from repro.launch.mesh import make_debug_mesh
from repro.runtime import resilience
from repro.runtime.fault_tolerance import FailureInjector, SimulatedFailure

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

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
HERE = os.path.dirname(__file__)


def _dispatch(func_name: str) -> None:
    """Run a check in-process when 4 devices exist, else in a subprocess
    with emulated host devices (same idiom as ``test_async_engine``)."""
    if jax.device_count() >= 4:
        globals()[func_name]()
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + HERE
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    prog = f"from test_resilience import {func_name}; {func_name}()"
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]


def _ecfg(async_n=2, nc=32, n=256, **kw):
    cfg = make_resilience_config(nc=nc, n=n)
    return make_engine_config(cfg, async_n=async_n, max_migration=64,
                              max_births=64, **kw)


def _leaves(state):
    return jax.tree_util.tree_flatten_with_path(state)[0]


def _assert_states_bitwise(a, b, ctx=""):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb), ctx
    for (kp, x), (_, y) in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, (ctx, kp)
        assert np.array_equal(x, y), f"{ctx} leaf {jax.tree_util.keystr(kp)}"


def _assert_diags_bitwise(a, b, ctx=""):
    assert len(a) == len(b), ctx
    for i, (da, db) in enumerate(zip(a, b)):
        assert set(da) == set(db), (ctx, i)
        for k in da:
            assert np.array_equal(np.asarray(da[k]), np.asarray(db[k])), \
                f"{ctx} step+{i} diag {k}"


# ------------------------------------------------------- bitwise restart


def bitwise_restart_check(d: int, async_ns=(1, 2, 4),
                          k_ckpt=2, k_fail=4, k_end=6) -> None:
    """checkpoint-at-k -> SimulatedFailure -> restore -> run-to-k+m must be
    bitwise-identical to the uninterrupted run: state leaves AND the diag
    records of the resumed steps. Full-churn workload (ionization + SEE +
    collisions + carried rho)."""
    mesh = make_debug_mesh(data=d, model=1)
    for async_n in async_ns:
        ecfg = _ecfg(async_n=async_n)
        step = engine.make_engine_step(ecfg, mesh)
        ref, ref_diags = resilience.run_engine(
            ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0),
            num_steps=k_end, step_fn=step)
        with tempfile.TemporaryDirectory() as tmp:
            ck = Checkpointer(tmp)
            inj = FailureInjector(fail_at_step=k_fail)
            with pytest.raises(SimulatedFailure):
                resilience.run_engine(
                    ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0),
                    num_steps=k_end, ckpt=ck, ckpt_every=k_ckpt,
                    injector=inj, step_fn=step)
            step_r, state = resilience.resume_engine(ecfg, mesh, ck)
            assert step_r == k_fail  # newest complete ckpt before the fence
            fin, diags = resilience.run_engine(
                ecfg, mesh, state, num_steps=k_end, ckpt=ck,
                ckpt_every=k_ckpt, injector=inj, step_fn=step)
        ctx = f"D={d} async_n={async_n}"
        _assert_states_bitwise(ref, fin, ctx)
        _assert_diags_bitwise(ref_diags[step_r:], diags, ctx)


def test_bitwise_restart_single_domain():
    bitwise_restart_check(1)


def bitwise_restart_d2():
    bitwise_restart_check(2)


def bitwise_restart_d4():
    bitwise_restart_check(4)


def test_bitwise_restart_two_domains():
    _dispatch("bitwise_restart_d2")


def test_bitwise_restart_four_domains():
    _dispatch("bitwise_restart_d4")


# ------------------------------------------------------- elastic restore


def _totals(ecfg, mesh, state):
    """Per-species (count, charge) of everything resident: buffer rows plus
    in-flight pending rows (the engine's own diag counts them the same
    way, so conservation holds at every step boundary)."""
    out = {}
    for i, sc in enumerate(ecfg.pic.species):
        a = np.asarray(state.pic.species[i].alive)
        w = np.asarray(state.pic.species[i].w, np.float64)
        out[i] = [int(a.sum()), float((w * a).sum()) * sc.charge]
    for g, idxs in enumerate(engine._capacity_groups(ecfg, mesh)):
        for j, i in enumerate(idxs):
            pa = np.asarray(state.pending[g].alive)[:, j]
            pw = np.asarray(state.pending[g].w, np.float64)[:, j]
            out[i][0] += int(pa.sum())
            out[i][1] += float((pw * pa).sum()) * ecfg.pic.species[i].charge
    return out


def elastic_matrix_check() -> None:
    """Save at D, restore at every D' != D (all six pairs of {1, 2, 4}).

    Collisions-only workload (periodic walls, deterministic populations) so
    the PR-5-style invariants are exact across the restore boundary AND the
    continued run: particle count and charge are conserved exactly, the
    electron kinetic energy is preserved by elastic/Coulomb scattering, and
    charge exchange conserves the D+/D kinetic-energy sum."""
    cfg = make_collision_config(nc=32, n=256, strategy="fused")
    ecfg = make_engine_config(cfg, async_n=2, max_migration=64,
                              max_births=64)
    meshes = {d: make_debug_mesh(data=d, model=1) for d in (1, 2, 4)}
    steps = {d: engine.make_engine_step(ecfg, meshes[d]) for d in meshes}

    def moments(diag):
        return {k: float(np.asarray(diag[k])) for k in diag
                if k.endswith(("/count", "/ke"))}

    saved = {}
    with tempfile.TemporaryDirectory() as tmp:
        for d in meshes:
            ck = Checkpointer(os.path.join(tmp, f"d{d}"))
            state, diags = resilience.run_engine(
                ecfg, meshes[d], engine.init_engine_state(ecfg, meshes[d], 0),
                num_steps=3, step_fn=steps[d])
            resilience.save_engine(ck, ecfg, meshes[d], 3, state,
                                   blocking=True)
            saved[d] = (ck, moments(diags[-1]),
                        _totals(ecfg, meshes[d], state))
        for d_save in meshes:
            ck, m0, t0 = saved[d_save]
            for d_new in meshes:
                if d_new == d_save:
                    continue
                ctx = f"{d_save}->{d_new}"
                step_r, state = resilience.resume_engine(
                    ecfg, meshes[d_new], ck)
                assert step_r == 3, ctx
                assert int(np.asarray(state.pic.step)) == 3, ctx
                # pending starts empty, rings account for every dead slot
                for p in state.pending:
                    assert not np.asarray(p.alive).any(), ctx
                for rg, idxs in zip(state.rings,
                                    engine._capacity_groups(
                                        ecfg, meshes[d_new])):
                    dead = sum(
                        int((~np.asarray(
                            state.pic.species[i].alive)).sum())
                        for i in idxs)
                    assert int(np.asarray(rg.count).sum()) == dead, ctx
                # exact count/charge conservation across the boundary
                t1 = _totals(ecfg, meshes[d_new], state)
                for i in t0:
                    assert t1[i][0] == t0[i][0], (ctx, i, t0[i], t1[i])
                    np.testing.assert_allclose(
                        t1[i][1], t0[i][1], rtol=1e-12,
                        err_msg=f"{ctx} species {i} charge")
                state, diags = resilience.run_engine(
                    ecfg, meshes[d_new], state, num_steps=5,
                    step_fn=steps[d_new])
                m1 = moments(diags[-1])
                for k in m0:
                    if k.endswith("/count"):
                        assert m1[k] == m0[k], (ctx, k, m0[k], m1[k])
                # elastic + Coulomb preserve electron KE; CX conserves the
                # D+/D sum (identity swap) — same rtol as the PR 5 harness
                assert np.isclose(m1["e/ke"], m0["e/ke"],
                                  rtol=2e-4), (ctx, m0, m1)
                assert np.isclose(m1["D+/ke"] + m1["D/ke"],
                                  m0["D+/ke"] + m0["D/ke"],
                                  rtol=2e-4), (ctx, m0, m1)


def test_elastic_restore_matrix():
    _dispatch("elastic_matrix_check")


def elastic_churn_conservation_check() -> None:
    """Elastic restore of the full-churn MC workload (SEE + ionization +
    collisions + carried rho + nonempty pending blocks): the restored
    population and charge equal the checkpointed buffers PLUS the in-flight
    pending rows, exactly, and the carried rho matches a fresh deposit."""
    ecfg = _ecfg(async_n=2)
    charges = {i: sc.charge for i, sc in enumerate(ecfg.pic.species)}
    mesh4 = make_debug_mesh(data=4, model=1)
    state, _ = resilience.run_engine(
        ecfg, mesh4, engine.init_engine_state(ecfg, mesh4, 0), num_steps=4)
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        resilience.save_engine(ck, ecfg, mesh4, 4, state, blocking=True)
        _, flat, _ = ck.restore_flat()
        groups = engine._capacity_groups_d(ecfg, 4)
        n0, q0 = {}, {}
        for g, idxs in enumerate(groups):
            for j, i in enumerate(idxs):
                a = flat[f"pic/species/{i}/alive"]
                w = flat[f"pic/species/{i}/w"]
                pa = flat[f"pending/{g}/alive"][:, j]
                pw = flat[f"pending/{g}/w"][:, j]
                n0[i] = int(a.sum()) + int(pa.sum())
                q0[i] = (float((w * a).sum()) + float((pw * pa).sum())) \
                    * charges[i]
        assert any(flat[f"pending/{g}/alive"].any()
                   for g in range(len(groups))), \
            "churn produced no in-flight rows; the flush is untested"
        for d_new in (1, 2):
            mesh = make_debug_mesh(data=d_new, model=1)
            _, st = resilience.resume_engine(ecfg, mesh, ck)
            for i in n0:
                alive = np.asarray(st.pic.species[i].alive)
                w = np.asarray(st.pic.species[i].w)
                assert int(alive.sum()) == n0[i], (d_new, i)
                np.testing.assert_allclose(
                    float((w * alive).sum()) * charges[i], q0[i],
                    rtol=1e-6, err_msg=f"{d_new}:{i}")
            # carried rho was rebuilt from the re-split particles: its
            # total charge must match the population exactly
            rho = np.asarray(st.pic.rho, np.float64)
            np.testing.assert_allclose(
                rho.sum(), sum(q0.values()), rtol=1e-5)


def test_elastic_restore_conserves_churn_workload():
    _dispatch("elastic_churn_conservation_check")


def overfull_domain_check():
    """Re-split cannot invent headroom: when one new domain's population
    exceeds its local capacity the restore must refuse loudly."""
    ecfg = _ecfg(async_n=1)
    mesh = make_debug_mesh(data=1, model=1)
    state = engine.init_engine_state(ecfg, mesh, 0)
    flat, _ = checkpoint._flatten_with_dtypes(state)
    flat = {k: np.array(v) for k, v in flat.items()}
    # cram every electron into the left half-domain, then ask for D'=2
    # with the same *total* capacity: domain 0 receives them all
    cap = flat["pic/species/0/x"].shape[1]
    flat["pic/species/0/x"][:] = 1.0
    flat["pic/species/0/alive"][:] = True
    ecfg2 = make_engine_config(ecfg.pic, async_n=1, max_migration=64,
                               max_births=64)
    mesh2 = make_debug_mesh(data=2, model=1)
    assert engine._local_cap_d(ecfg2, ecfg.pic.species[0], 2) == cap // 2
    with pytest.raises(ValueError, match="local capacity"):
        engine.resplit_host(ecfg2, mesh2, flat, d_old=1)


def test_elastic_restore_rejects_overfull_domain():
    _dispatch("overfull_domain_check")


def test_resume_checkpoint_without_pending():
    """An engine checkpoint with no ``pending/*`` leaves (written by a tree
    whose state carried no in-flight rows) cannot restore typed, so it
    resumes through the elastic path, which reads its buffers as they are:
    counts and charges conserved exactly, pending empty, rings that account
    for every dead slot, and a state the step runs on."""
    ecfg = _ecfg(async_n=2)
    mesh = make_debug_mesh(data=1, model=1)
    state, _ = resilience.run_engine(
        ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0), num_steps=3)
    assert any(np.asarray(p.alive).any() for p in state.pending), \
        "no rows in flight; the flush below would be vacuous"
    # land the in-flight rows (a budget retune flushes them), then drop the
    # pending leaves from what is saved
    flushed = engine.retarget_state(
        ecfg, dataclasses.replace(ecfg, max_migration=128), mesh, state)
    t0 = _totals(ecfg, mesh, flushed)
    old = engine.EngineState(pic=flushed.pic, rings=flushed.rings,
                             pending=())
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        resilience.save_engine(ck, ecfg, mesh, 3, old, blocking=True)
        _, flat, _ = ck.restore_flat()
        assert flat and not any(k.startswith("pending/") for k in flat)
        assert not resilience._stored_matches(
            flat, engine.state_shape(ecfg, mesh))       # the elastic path
        step_r, st = resilience.resume_engine(ecfg, mesh, ck)
    assert step_r == 3 and int(np.asarray(st.pic.step)) == 3
    for p in st.pending:
        assert not np.asarray(p.alive).any()
    for rg, idxs in zip(st.rings, engine._capacity_groups(ecfg, mesh)):
        dead = sum(int((~np.asarray(st.pic.species[i].alive)).sum())
                   for i in idxs)
        assert int(np.asarray(rg.count).sum()) == dead
    t1 = _totals(ecfg, mesh, st)
    for i in t0:
        assert t1[i][0] == t0[i][0], (i, t0[i], t1[i])
        np.testing.assert_allclose(t1[i][1], t0[i][1], rtol=1e-12,
                                   err_msg=f"species {i} charge")
    st, diags = resilience.run_engine(ecfg, mesh, st, num_steps=5)
    assert len(diags) == 2 and int(np.asarray(st.pic.step)) == 5


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


def test_elastic_restore_does_no_full_capacity_scan():
    """The rebuild must use the closed-form ``ring_from_counts`` (free set
    = the compacted tail), never the init-only ``ring_init`` full scan:
    restore cost stays O(particles moved), not O(total capacity). The
    contrast pin: ``attach_engine_state`` (which IS allowed the init scan)
    shows the full-capacity cumsum the elastic path must not contain."""
    ecfg = _ecfg(async_n=2)
    mesh = make_debug_mesh(data=1, model=1)
    cap = ecfg.local_cap(ecfg.pic.species[0], mesh)
    species = [dict(x=np.zeros((1, cap), np.float32),
                    v=np.zeros((1, cap, 3), np.float32),
                    w=np.zeros((1, cap), np.float32),
                    alive=np.zeros((1, cap), bool))
               for _ in ecfg.pic.species]
    counts = np.zeros((1, len(species)), np.int32)
    key = np.zeros((2,), np.uint32)
    jxp = jax.make_jaxpr(
        lambda: engine.elastic_state(ecfg, mesh, species, counts, key, 0))()
    shapes = _collect_cumsum_shapes(jxp.jaxpr, [])
    full = [s for s in shapes if s and s[-1] >= cap]
    assert not full, (
        f"elastic restore cumsums over a full-capacity axis {full}: the "
        f"free-slot rebuild regressed to a scan")
    state = engine.init_engine_state(ecfg, mesh, 0)
    jxp2 = jax.make_jaxpr(
        lambda s: engine.attach_engine_state(ecfg, mesh, s.pic))(state)
    attach = _collect_cumsum_shapes(jxp2.jaxpr, [])
    assert any(s and s[-1] >= cap for s in attach), (
        "contrast pin lost its teeth: attach_engine_state no longer scans")


# ----------------------------------------------------------- torn writes


def test_restart_scans_past_torn_checkpoints():
    """A step directory without a manifest (writer died mid-write) and one
    with a corrupt manifest are both invisible to latest_step/restore."""
    tree = {"a": jnp.arange(4.0)}
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        ck.save(2, tree, blocking=True)
        torn = os.path.join(tmp, "step_00000004")
        os.makedirs(torn)
        np.savez(os.path.join(torn, "arrays.npz"), a=np.arange(4.0))
        garbled = os.path.join(tmp, "step_00000006")
        os.makedirs(garbled)
        np.savez(os.path.join(garbled, "arrays.npz"), a=np.arange(4.0))
        with open(os.path.join(garbled, "manifest.json"), "w") as fh:
            fh.write('{"step": 6, "comp')     # truncated mid-write
        assert ck.latest_step() == 2
        step, out = ck.restore(like=tree)
        assert step == 2
        assert np.array_equal(np.asarray(out["a"]), np.arange(4.0))


def test_writer_killed_between_arrays_and_manifest(monkeypatch):
    """Kill the writer between ``arrays.npz`` and ``manifest.json`` (the
    manifest-last window): the torn step must be skipped and the next save
    must land cleanly once the fault clears."""
    tree = {"a": jnp.arange(3.0), "b": jnp.ones((2,), bool)}
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        ck.save(1, tree, blocking=True)
        real_replace = os.replace

        def boom(src, dst):
            if dst.endswith("manifest.json"):
                raise OSError("simulated writer kill")
            return real_replace(src, dst)

        monkeypatch.setattr(checkpoint.os, "replace", boom)
        with pytest.raises(OSError, match="writer kill"):
            ck.save(3, tree, blocking=True)
        monkeypatch.undo()
        # arrays landed, manifest did not: the definition of torn
        assert os.path.exists(
            os.path.join(tmp, "step_00000003", "arrays.npz"))
        assert not os.path.exists(
            os.path.join(tmp, "step_00000003", "manifest.json"))
        assert ck.latest_step() == 1
        ck.save(5, tree, blocking=True)
        assert ck.latest_step() == 5
        step, out = ck.restore(like=tree)
        assert step == 5 and np.array_equal(np.asarray(out["b"]),
                                            np.ones((2,), bool))


def test_save_is_asynchronous_by_default():
    """The step loop pays the host fetch only: save() returns with the
    writer thread still attached, and wait() completes the manifest."""
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        info = ck.save(7, {"a": jnp.zeros((256, 256))})
        assert ck._thread is not None          # write still in flight
        assert info["bytes"] == 256 * 256 * 4
        assert info["fetch_us"] >= 0
        ck.wait()
        assert ck.latest_step() == 7
        assert ck.last_write_us > 0


# -------------------------------------------------- serialization roundtrip


def _random_engine_tree(seed: int, cap: int, m: int):
    """An engine-shaped pytree (registered dataclasses, tuples, dict) with
    every leaf dtype the checkpoint must round-trip: f32, bf16, bool, i32,
    u32."""
    rng = np.random.RandomState(seed)
    ring = FreeSlotRing(
        slots=jnp.asarray(rng.randint(0, cap + 1, cap), jnp.int32),
        head=jnp.asarray(rng.randint(0, cap), jnp.int32),
        count=jnp.asarray(rng.randint(0, cap), jnp.int32))
    pend = engine.PendingArrivals(
        x=jnp.asarray(rng.randn(2, m), jnp.float32),
        v=jnp.asarray(rng.randn(2, m, 3), jnp.float32),
        w=jnp.asarray(rng.rand(2, m), jnp.float32),
        alive=jnp.asarray(rng.rand(2, m) < 0.5),
        dest=jnp.asarray(rng.randint(0, cap + 1, (2, m)), jnp.int32))
    return {"rings": (ring,), "pending": (pend,),
            "key": jnp.asarray(rng.randint(0, 2**32, 2, np.int64),
                               jnp.uint32),
            "halfp": jnp.asarray(rng.randn(cap), jnp.bfloat16)}


def _assert_tree_bitwise(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype, (xa.dtype, ya.dtype)
        assert np.array_equal(xa, ya)


@needs_hypothesis
@settings(max_examples=25, deadline=None)
@given(hyp_st.integers(0, 2**31 - 1), hyp_st.integers(1, 64),
       hyp_st.integers(1, 16))
def test_flatten_roundtrips_engine_pytree(seed, cap, m):
    tree = _random_engine_tree(seed, cap, m)
    _assert_tree_bitwise(tree, checkpoint.roundtrip_bytes(tree))


def test_roundtrip_engine_pytree_fixed_seed():
    """The non-hypothesis fallback of the property test, through the real
    file-based Checkpointer (npz + manifest dtypes, not just BytesIO)."""
    tree = _random_engine_tree(1234, 32, 8)
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        ck.save(1, tree, blocking=True)
        _, out = ck.restore(like=tree)
        _assert_tree_bitwise(tree, out)


def test_roundtrip_full_engine_state():
    """A live EngineState (after churn steps, nonempty rings) restores
    bitwise through save/restore with the engine's like/shardings."""
    ecfg = _ecfg(async_n=2)
    mesh = make_debug_mesh(data=1, model=1)
    state, _ = resilience.run_engine(
        ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0), num_steps=2)
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        resilience.save_engine(ck, ecfg, mesh, 2, state, blocking=True)
        step, out = resilience.resume_engine(ecfg, mesh, ck)
        assert step == 2
        _assert_states_bitwise(state, out)
        assert isinstance(out, engine.EngineState)
        assert isinstance(out.rings[0], FreeSlotRing)


# ------------------------------------------------------- seed-module bugs


def test_restore_rejects_keys_absent_from_like():
    """The latent seed bug: restore(like=...) used to silently drop stored
    leaves missing from `like` (and fabricate nothing for extras). Both
    directions must now raise."""
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        ck.save(1, {"a": jnp.zeros(3), "b": {"c": jnp.ones(2)}},
                blocking=True)
        with pytest.raises(ValueError, match="extra keys"):
            ck.restore(like={"a": jnp.zeros(3)})
        with pytest.raises(ValueError, match="missing keys"):
            ck.restore(like={"a": jnp.zeros(3),
                             "b": {"c": jnp.ones(2), "d": jnp.ones(1)}})


def test_restore_shape_mismatch_points_at_elastic_path():
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        ck.save(1, {"a": jnp.zeros((4,))}, blocking=True)
        with pytest.raises(ValueError, match="elastic"):
            ck.restore(like={"a": jnp.zeros((2,))})


def test_failure_injector_fires_once():
    """Resume past fail_at_step must not re-raise (a restarted process is a
    different process); once=False keeps the every-pass behavior."""
    inj = FailureInjector(fail_at_step=3)
    inj.check(2)
    with pytest.raises(SimulatedFailure):
        inj.check(3)
    inj.check(3)                        # the resumed pass sails through
    inj.check(4)
    always = FailureInjector(fail_at_step=3, once=False)
    with pytest.raises(SimulatedFailure):
        always.check(3)
    with pytest.raises(SimulatedFailure):
        always.check(3)


# --------------------------------------------------- metrics + overhead


def test_ckpt_overhead_lands_in_metrics_stream():
    from repro.obs.metrics import (MetricsStream, read_jsonl,
                                   validate_stream)
    ecfg = _ecfg(async_n=1)
    mesh = make_debug_mesh(data=1, model=1)
    with tempfile.TemporaryDirectory() as tmp:
        jsonl = os.path.join(tmp, "m.jsonl")
        ck = Checkpointer(os.path.join(tmp, "ck"))
        with MetricsStream(jsonl_path=jsonl, config={"test": True}) as st:
            resilience.run_engine(
                ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0),
                num_steps=4, ckpt=ck, ckpt_every=2, stream=st)
        header, steps = read_jsonl(jsonl)
        assert validate_stream([header] + steps) == []
        with_ckpt = [s for s in steps if "ckpt/bytes" in s["counters"]]
        assert [s["step"] for s in with_ckpt] == [1, 3]
        for s in with_ckpt:
            assert s["counters"]["ckpt/bytes"] > 0
            assert s["counters"]["ckpt/fetch_us"] >= 0
            assert "ckpt/write_us" in s["counters"]
        assert all("ckpt/bytes" not in s["counters"]
                   for s in steps if s["step"] in (0, 2))


# ------------------------------------------------------ SIGTERM handling


def test_sigterm_stops_loop_and_writes_final_checkpoint():
    """Preemption drill: SIGTERM mid-run must stop the loop at the next
    step boundary and leave one final checkpoint labeled with the next
    step to run, so ``resume_engine`` restarts the preempted run bitwise.
    The previous handler is reinstalled afterwards."""
    import signal

    ecfg = _ecfg(async_n=2)
    mesh = make_debug_mesh(data=1, model=1)
    step = engine.make_engine_step(ecfg, mesh)
    calls = {"n": 0}

    def wrapped(s):
        calls["n"] += 1
        if calls["n"] == 3:       # delivered mid-step-3; loop stops before 4
            signal.raise_signal(signal.SIGTERM)
        return step(s)

    before = signal.getsignal(signal.SIGTERM)
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        fin, diags = resilience.run_engine(
            ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0),
            num_steps=8, ckpt=ck, step_fn=wrapped)
        assert len(diags) == 3          # steps 0..2 ran, 3.. preempted
        assert signal.getsignal(signal.SIGTERM) is before
        step_r, restored = resilience.resume_engine(ecfg, mesh, ck)
        assert step_r == 3              # labeled with the next step to run
        _assert_states_bitwise(restored, fin, "sigterm final ckpt")
        # the resumed run completes and matches an uninterrupted one
        fin_r, diags_r = resilience.run_engine(
            ecfg, mesh, restored, num_steps=5, step_fn=step)
        ref, ref_diags = resilience.run_engine(
            ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0),
            num_steps=5, step_fn=step)
        _assert_states_bitwise(fin_r, ref, "sigterm resume")
        _assert_diags_bitwise(diags_r, ref_diags[3:], "sigterm resume")


def test_sigterm_no_duplicate_checkpoint_when_boundary_already_saved():
    """A SIGTERM landing right after a periodic checkpoint must not write
    the same step twice — the final save is skipped when the boundary is
    already durable."""
    import signal

    ecfg = _ecfg(async_n=1)
    mesh = make_debug_mesh(data=1, model=1)
    step = engine.make_engine_step(ecfg, mesh)
    calls = {"n": 0}

    def wrapped(s):
        calls["n"] += 1
        if calls["n"] == 2:
            signal.raise_signal(signal.SIGTERM)
        return step(s)

    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        resilience.run_engine(
            ecfg, mesh, engine.init_engine_state(ecfg, mesh, 0),
            num_steps=8, ckpt=ck, ckpt_every=2, step_fn=wrapped)
        steps = sorted(int(d.name.split("_")[-1])
                       for d in os.scandir(tmp) if d.is_dir())
        assert steps == [2]
