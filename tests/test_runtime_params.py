"""Static/traced config split (``core/params.py``) — the ensemble
prerequisite — plus the config-hashability bugfix sweep.

Pins, in order:

* **bugfixes** — list-valued ``b_field``/``ionization`` are tuple-normalized
  in ``__post_init__`` (they used to survive as lists and crash the first
  jit with the config static: lists are unhashable); ``n_init > capacity``
  is rejected at construction naming the offending species, with
  ``n_init == capacity`` explicitly legal;
* **bitwise parity** — for 'unified' and 'fused', a step with every runtime
  scalar TRACED (``RuntimeParams``) is bit-identical to the static step that
  bakes the same values in as constants, full physics on (b rotation,
  collision menu, SEE, ionization, absorbing walls). Same for the async
  multi-device engine across D x async_n (``with_params=True``);
* **explicit refusal** — 'explicit' (Pallas kernel bakes its scalars) and
  'async_batched' (XLA:CPU contracts mul+add into FMA inside the scan body
  when the kick scalar is traced, a 1-ulp divergence) raise
  NotImplementedError instead of silently breaking the bitwise contract;
* **compile-once** — two parameter points (different dt, rates, yield, b)
  share ONE executable; overriding a static knob through ``runtime_params``
  is rejected with an error saying it needs a fresh config/compile.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import pic_bit1
from repro.core import pic
from repro.core.params import (RUNTIME_FIELDS, RuntimeParams, b_active,
                               runtime_params)
from repro.distributed import engine
from repro.launch.mesh import make_debug_mesh

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
    prog = f"from test_runtime_params import {func_name}; {func_name}()"
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]


def _full_cfg(strategy="fused", nc=64, n=512, **kw):
    """Full-churn single-domain config: collisions + SEE + ionization +
    absorbing walls + a nonzero b so every runtime scalar is live."""
    cfg = pic_bit1.make_resilience_config(nc=nc, n=n, strategy=strategy)
    return dataclasses.replace(cfg, b_field=(0.0, 0.01, 0.05), **kw)


def _assert_trees_equal(a, b, ctx=""):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(la) == len(lb), ctx
    for (kp, x), (_, y) in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, (ctx, kp)
        assert np.array_equal(x, y), f"{ctx} leaf {jax.tree_util.keystr(kp)}"


# ----------------------------------------------------- satellite bugfixes


def test_list_b_field_and_ionization_are_normalized_hashable():
    """Seed bug: a list-valued b_field or ionization triple rode through
    construction untouched and blew up the FIRST jit with the config static
    (``TypeError: unhashable type: 'list'``). ``__post_init__`` must
    tuple-normalize both, like it already did species/collisions."""
    cfg = _full_cfg(n=128)
    cfg = dataclasses.replace(cfg, b_field=[0.0, 0.0, 0.1],
                              ionization=[2, 0, 1])
    assert isinstance(cfg.b_field, tuple)
    assert isinstance(cfg.ionization, tuple)
    hash(cfg)  # the original crash site (jit's static-argument hashing)
    state = pic.init_state(cfg, 0)
    step = pic.make_step(cfg)  # config rides through jit closure + diag
    state, diag = step(state)
    assert np.isfinite(float(np.asarray(diag["e/ke"]).sum()))


def test_n_init_over_capacity_rejected_naming_species():
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, 256, 128, vth=1.0),
          pic.SpeciesConfig("D+", 1.0, 3672.0, 256, 300, vth=0.02))
    with pytest.raises(ValueError) as err:
        pic.PICConfig(nc=32, dx=1.0, dt=0.1, species=sp)
    assert "D+" in str(err.value)
    assert "n_init=300" in str(err.value) and "capacity=256" in str(err.value)


def test_n_init_equal_to_capacity_is_legal():
    sp = (pic.SpeciesConfig("e", -1.0, 1.0, 256, 256, vth=1.0),)
    cfg = pic.PICConfig(nc=32, dx=1.0, dt=0.1, species=sp)
    state = pic.init_state(cfg, 0)
    assert int(np.asarray(state.species[0].count())) == 256


# ------------------------------------------- single-domain bitwise parity


def _parity_check(strategy: str, steps: int = 4) -> None:
    cfg = _full_cfg(strategy)
    rp = runtime_params(cfg)
    step = pic.make_step(cfg)
    s_static = pic.init_state(cfg, 3)
    s_traced = jax.tree.map(jnp.copy, s_static)
    for _ in range(steps):
        s_static, d_static = step(s_static)
        s_traced, d_traced = step(s_traced, rp)
    _assert_trees_equal(s_static, s_traced, f"state strategy={strategy}")
    _assert_trees_equal(d_static, d_traced, f"diag strategy={strategy}")


def test_traced_params_bitwise_parity_unified():
    _parity_check("unified")


def test_traced_params_bitwise_parity_fused():
    _parity_check("fused")


@pytest.mark.parametrize("strategy", ["explicit", "async_batched"])
def test_traced_params_refused_where_not_bitwise(strategy):
    """'explicit' bakes scalars into the Pallas kernel; 'async_batched'
    picks up FMA contraction inside its scan body when the kick scalar is
    traced (1-ulp v drift vs the static build). Both must refuse traced
    params loudly rather than quietly break the parity contract."""
    cfg = _full_cfg(strategy)
    rp = runtime_params(cfg)
    state = pic.init_state(cfg, 3)
    step = pic.make_step(cfg)
    with pytest.raises(NotImplementedError, match=strategy):
        step(state, rp)


# ------------------------------------------------------ compile-once pins


def test_two_parameter_points_share_one_executable():
    cfg = _full_cfg("fused", n=256)
    step = pic.make_step(cfg)
    rp1 = runtime_params(cfg, dt=0.4, ionization_rate=1e-3)
    rp2 = runtime_params(cfg, dt=0.6, emission_yield=0.3,
                         b_field=(0.0, 0.0, 0.1),
                         collision_rates=(1e-3, 2e-3, 5e-4))
    s1 = pic.init_state(cfg, 0)
    s2 = pic.init_state(cfg, 1)
    s1, _ = step(s1, rp1)
    s2, _ = step(s2, rp2)
    assert step._cache_size() == 1


def test_static_knob_override_is_rejected():
    cfg = _full_cfg("fused", n=128)
    with pytest.raises(ValueError, match="fresh compile"):
        runtime_params(cfg, nc=128)
    with pytest.raises(ValueError, match="3-entry menu"):
        runtime_params(cfg, collision_rates=(1e-3,))


def test_runtime_params_products_match_host_f64():
    cfg = _full_cfg("fused", n=128)
    rp = RuntimeParams.from_config(cfg)
    for si, sc in enumerate(cfg.species):
        want = np.float32(float(cfg.dt) * sc.stride)
        assert np.asarray(rp.dts)[si] == want
        want = np.float32((sc.charge / sc.mass) * float(cfg.dt) * sc.stride)
        assert np.asarray(rp.qm_dts)[si] == want
    assert b_active(cfg)
    assert not b_active(dataclasses.replace(cfg, b_field=(0.0, 0.0, 0.0)))
    assert set(RUNTIME_FIELDS) == {"dt", "ionization_rate",
                                   "emission_yield", "b_field"}


# ------------------------------------------------- engine parity (4 dev)


def engine_params_parity_check() -> None:
    """``with_params=True`` engine step vs the static engine step, bitwise,
    across D x async_n — and one executable across two parameter points."""
    cfg = _full_cfg("fused", nc=64, n=1024)
    for d, async_n in ((1, 2), (2, 2), (4, 4)):
        mesh = make_debug_mesh(data=d, model=1)
        ecfg = pic_bit1.make_engine_config(cfg, async_n=async_n,
                                           max_migration=512, max_births=256)
        rp = runtime_params(cfg)
        step_a = engine.make_engine_step(ecfg, mesh)
        step_b = engine.make_engine_step(ecfg, mesh, with_params=True)
        sa = engine.init_engine_state(ecfg, mesh, seed=5)
        sb = jax.tree.map(jnp.copy, sa)
        for _ in range(4):
            sa, da = step_a(sa)
            sb, db = step_b(sb, rp)
        ctx = f"D={d} async_n={async_n}"
        _assert_trees_equal(sa, sb, ctx)
        _assert_trees_equal(da, db, ctx)
        # a second parameter point reuses the same executable
        sb, _ = step_b(sb, runtime_params(cfg, dt=0.25, emission_yield=0.2))
        assert step_b._cache_size() == 1, ctx


def test_engine_traced_params_parity():
    _dispatch("engine_params_parity_check")
