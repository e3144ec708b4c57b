"""Online auto-tuner: the pure control law on synthetic windows, exact
state carry-over across knob retunes (``engine.retarget_state``), and the
closed loop reducing measured queue skew on a churning SEE workload.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.pic_bit1 import (make_engine_config, make_see_config)
from repro.core import pic
from repro.distributed import engine
from repro.launch.mesh import make_debug_mesh
from repro.obs import autotune
from repro.obs.metrics import StepMetrics

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
HERE = os.path.dirname(__file__)


def _dispatch(func_name: str, *args) -> None:
    """Run a check in-process when 4 devices exist, else in a subprocess
    with emulated host devices (same idiom as ``test_async_engine``)."""
    if jax.device_count() >= 4:
        globals()[func_name](*args)
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + HERE
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    prog = (f"from test_autotune import {func_name}; "
            f"{func_name}(*{args!r})")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]


def _ecfg(**kw):
    kw.setdefault("nc", 32)
    kw.setdefault("n", 512)
    async_n = kw.pop("async_n", 4)
    mig = kw.pop("max_migration", 1024)
    births = kw.pop("max_births", 1024)
    reb_skew = kw.pop("rebalance_skew", 0)
    reb_every = kw.pop("rebalance_every", 0)
    return make_engine_config(async_n=async_n, max_migration=mig,
                              max_births=births, rebalance_skew=reb_skew,
                              rebalance_every=reb_every, strategy="fused",
                              **kw)


def _win(counters, queues=None, n=4):
    return [StepMetrics(step=i, wall_us=1000.0, counters=dict(counters),
                        queues=dict(queues or {})) for i in range(n)]


# -------------------------------------------------------------- control law


def test_decide_empty_window_is_noop():
    assert autotune.decide(_ecfg(), [], autotune.TunerPolicy()) == {}


def test_decide_grows_budget_on_overflow():
    pol = autotune.TunerPolicy()
    ecfg = _ecfg(max_migration=1024)
    win = _win({"e/migration_overflow": 3.0, "e/migrated_left": 1024.0})
    changes = autotune.decide(ecfg, win, pol)
    assert changes["max_migration"] == 2048
    assert changes["max_migration"] % ecfg.async_n == 0
    # already at the cap: no change proposed
    capped = _ecfg(max_migration=pol.max_budget)
    assert "max_migration" not in autotune.decide(capped, win, pol)


def test_decide_grows_birth_budget_on_overflow():
    win = _win({"birth_overflow": 2.0, "n_ionized": 100.0})
    changes = autotune.decide(_ecfg(max_births=512), win,
                              autotune.TunerPolicy())
    assert changes["max_births"] == 1024


def test_decide_shrinks_calm_oversized_budgets():
    pol = autotune.TunerPolicy(min_budget=64)
    win = _win({"e/migration_overflow": 0.0, "e/migrated_left": 10.0,
                "e/migrated_right": 12.0, "n_ionized": 5.0,
                "birth_overflow": 0.0})
    changes = autotune.decide(_ecfg(max_migration=1024, max_births=1024),
                              win, pol)
    assert changes["max_migration"] == 512
    assert changes["max_births"] == 512
    # traffic near the budget: no shrink
    busy = _win({"e/migration_overflow": 0.0, "e/migrated_left": 700.0})
    assert "max_migration" not in autotune.decide(_ecfg(max_migration=1024),
                                                  busy, pol)
    # floor respected
    floor = autotune.decide(_ecfg(max_migration=64, max_births=64),
                            win, pol)
    assert "max_migration" not in floor


def test_decide_arms_rebalance_on_skew():
    pol = autotune.TunerPolicy(window=6, skew_frac=0.25)
    queues = {"e": [400, 100, 100, 100]}     # mean 175, skew 300
    win = _win({"e/queue_skew": 300.0, "e/migrated_left": 500.0},
               queues=queues)
    changes = autotune.decide(_ecfg(), win, pol)
    assert changes["rebalance_skew"] == int(0.25 * 175)
    # trigger armed but skew persists -> periodic backstop
    armed = _ecfg(rebalance_skew=changes["rebalance_skew"])
    again = autotune.decide(armed, win, pol)
    assert again.get("rebalance_every") == pol.window
    # balanced queues -> nothing
    calm = _win({"e/queue_skew": 2.0, "e/migrated_left": 500.0},
                queues={"e": [200, 199, 201, 200]})
    assert "rebalance_skew" not in autotune.decide(_ecfg(), calm, pol)


# ---------------------------------------------------------- state carry-over


# the budget retunes that resize EngineState.pending: each alone and both
RETUNES = {"max_migration": dict(max_migration=128),
           "max_births": dict(max_births=256),
           "both": dict(max_migration=128, max_births=256)}


def retarget_flush_check(retune: str):
    """A budget retune mid-run must conserve every particle — including the
    in-flight pending arrivals/births the merge deferred to the next step's
    ingest. Counts are compared before/after the flush+rebuild. Needs D=2:
    a single domain is fully periodic, so nothing ever migrates and the
    pending blocks stay empty."""
    ecfg = _ecfg(async_n=2, max_migration=64, max_births=64)
    mesh = make_debug_mesh(data=2, model=1)
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh, donate=False)
    for _ in range(3):
        state, diag = step(state)

    def totals(st):
        # buffer alive counts + pending in-flight rows, per species stack
        alive = sum(int(np.asarray(b.alive).sum()) for b in st.pic.species)
        pend = sum(int(np.asarray(p.alive).sum()) for p in st.pending)
        return alive, pend

    alive0, pend0 = totals(state)
    assert pend0 > 0, "workload produced no in-flight rows; test is vacuous"
    new = dataclasses.replace(ecfg, **RETUNES[retune])
    state2 = engine.retarget_state(ecfg, new, mesh, state)
    alive1, pend1 = totals(state2)
    assert pend1 == 0                    # rebuilt pending starts empty
    assert alive1 == alive0 + pend0      # every in-flight row landed
    # the new config's step accepts the carried state and conserves charge
    step2 = engine.make_engine_step(new, mesh, donate=False)
    _, diag2 = step2(state2)
    _, diag1 = step(state)
    for k in diag1:
        if k.endswith(("/count", "/charge")):
            assert np.allclose(np.asarray(diag1[k]), np.asarray(diag2[k])), k


@pytest.mark.parametrize("retune", list(RETUNES))
def test_retarget_state_flushes_pending_exactly(retune):
    _dispatch("retarget_flush_check", retune)


def test_retarget_state_identity_when_compatible():
    ecfg = _ecfg(async_n=2, max_migration=64, max_births=64)
    mesh = make_debug_mesh(data=1, model=1)
    state = engine.init_engine_state(ecfg, mesh, 0)
    new = dataclasses.replace(ecfg, async_n=1, rebalance_every=3,
                              rebalance_skew=7, metrics=True)
    assert engine.retarget_state(ecfg, new, mesh, state) is state
    bad = dataclasses.replace(
        ecfg, pic=dataclasses.replace(ecfg.pic, dt=0.5))
    try:
        engine.retarget_state(ecfg, bad, mesh, state)
        raise AssertionError("physics change must be rejected")
    except ValueError:
        pass


# -------------------------------------------------------------- closed loop


def test_autotuner_reduces_queue_skew_on_churn():
    """Acceptance loop: on the SEE churn workload (absorbing walls +
    secondary emission drifting the per-queue occupancy apart) the tuner
    must arm the skew-triggered rebalance and end with lower measured
    queue skew than the fixed-knob baseline."""
    cfg = make_see_config(nc=64, n=2048, strategy="fused",
                          emission_yield=0.7)
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = make_engine_config(cfg, async_n=4, max_migration=256,
                              max_births=256)
    steps = 14

    def skew_of(diag):
        return max(int(np.asarray(v)) for k, v in diag.items()
                   if k.endswith("/queue_skew"))

    # fixed knobs: skew drifts upward unchecked
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh)
    for _ in range(steps):
        state, diag = step(state)
    fixed_skew = skew_of(diag)

    # tuned: a tight skew threshold (the budgets are deliberately sized so
    # the budget rules stay quiet and the skew rule is what fires)
    policy = autotune.TunerPolicy(window=4, skew_frac=0.004,
                                  shrink_frac=0.0)
    tuner = autotune.AutoTuner(ecfg, mesh, policy=policy)
    state = engine.init_engine_state(tuner.ecfg, mesh, 0)
    for _ in range(steps):
        state, diag = tuner.run_step(state)
    tuned_skew = skew_of(diag)

    assert tuner.retunes >= 1, tuner.log
    assert tuner.ecfg.rebalance_skew > 0, tuner.log
    assert tuned_skew < fixed_skew, (tuned_skew, fixed_skew, tuner.log)
