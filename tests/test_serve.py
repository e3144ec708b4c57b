"""Simulation-as-a-service session layer (``serve/service.py``).

Pins the inference-engine-shaped serving contract: more sessions than slots
queue and reuse freed slots; every session runs at its own parameter point
yet the whole server compiles each hot function exactly once; poll exposes
running/done status with per-session diagnostics.
"""

import dataclasses
import os
from pathlib import Path

import numpy as np
import pytest

from repro.configs import pic_bit1
from repro.core.params import runtime_params
from repro.launch import cache
from repro.serve import SimService

import jax


def _cfg(n=128):
    cfg = pic_bit1.make_resilience_config(nc=64, n=n)
    return dataclasses.replace(cfg, b_field=(0.0, 0.0, 0.02))


def test_sessions_queue_reuse_slots_and_share_one_compile():
    svc = SimService(_cfg(), width=2)
    a = svc.submit({"dt": 0.3, "ionization_rate": 4e-3}, seed=1, steps=2)
    b = svc.submit({"dt": 0.5, "emission_yield": 0.2}, seed=2, steps=3)
    c = svc.submit({"dt": 0.7, "collision_rates": (1e-3, 2e-3, 5e-4)},
                   seed=3, steps=2)
    # two slots, three sessions: c waits for a freed slot
    assert svc.poll(c)["status"] == "queued"
    assert svc.stats()["running"] == 2 and svc.stats()["queued"] == 1
    svc.run_until_drained()
    polls = {s: svc.poll(s) for s in (a, b, c)}
    assert all(p["status"] == "done" for p in polls.values())
    assert [polls[s]["steps_done"] for s in (a, b, c)] == [2, 3, 2]
    # c ran in a slot freed by a (slot reuse, not growth)
    assert polls[c]["slot"] in (0, 1)
    # distinct parameter points -> distinct physics
    kes = {s: float(np.asarray(polls[s]["diag"]["e/ke"]).sum())
           for s in (a, b, c)}
    assert len({round(v, 9) for v in kes.values()}) == 3
    st = svc.stats()
    assert st["compiles"] == 1
    assert st["running"] == 0 and st["queued"] == 0 and st["free"] == 2


def test_poll_running_reports_latest_diag():
    svc = SimService(_cfg(), width=2)
    sid = svc.submit({"dt": 0.4}, seed=0, steps=5)
    assert svc.poll(sid)["status"] == "running"
    svc.step(2)
    p = svc.poll(sid)
    assert p["status"] == "running" and p["steps_done"] == 2
    assert "e/ke" in p["diag"]
    svc.step(3)
    assert svc.poll(sid)["status"] == "done"


def test_submit_validation():
    svc = SimService(_cfg(), width=1)
    with pytest.raises(ValueError, match="steps"):
        svc.submit({}, steps=0)
    with pytest.raises(ValueError, match="fresh compile"):
        svc.submit({"nc": 128})


def test_prebuilt_params_and_cache_dir(tmp_path, monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        # a directory from the environment stands: no code sets another
        monkeypatch.setenv(cache.ENV, str(tmp_path))
        assert cache.enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        # unset: the fixed, git-ignored directory in the checkout, exported
        # so that child processes share it
        monkeypatch.delenv(cache.ENV)
        want = str(cache.CHECKOUT_CACHE)
        assert cache.enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.environ[cache.ENV] == want
        assert cache.CHECKOUT_CACHE.parent == Path(__file__).parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    cfg = _cfg()
    svc = SimService(cfg, width=1)
    sid = svc.submit(params=runtime_params(cfg, dt=0.25), steps=1)
    svc.run_until_drained()
    assert svc.poll(sid)["status"] == "done"
