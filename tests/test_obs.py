"""Observability layer (``repro.obs``): metrics-stream schema, trace
annotations surviving into the lowered computation, bitwise parity of the
engine with the metrics toggle on vs off, and atomic JSON writes.

The parity matrix (D in {1, 2, 4} x async_n in {1, 2, 4}) needs 4 devices:
when the process exposes them the check runs in-process; otherwise it
re-runs itself in a subprocess with emulated host devices (same idiom as
``test_async_engine``).
"""

import json
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np

from repro.core import pic
from repro.distributed import engine
from repro.launch.mesh import make_debug_mesh
from repro.obs import metrics as obs_metrics
from repro.obs import tracing

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
    prog = f"from test_obs import {func_name}; {func_name}()"
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]


def _cfg(nc=32, n=512, cap=2048, ionization=True):
    """The (e-, D+, D) ionization triple at test scale (engine workload
    with MC births on the ring); ``ionization=False`` drops the source."""
    sp = (
        pic.SpeciesConfig("e", -1.0, 1.0, cap, n, vth=1.0),
        pic.SpeciesConfig("D+", 1.0, 3672.0, cap, n, vth=0.02),
        pic.SpeciesConfig("D", 0.0, 3672.0, cap, n, vth=0.05),
    )
    ion = dict(ionization=(2, 0, 1), ionization_rate=3e-3,
               ionization_vth_e=1.0) if ionization else {}
    return pic.PICConfig(nc=nc, dx=1.0, dt=0.2, species=sp,
                         field_solve=False, boundary="periodic",
                         strategy="fused", **ion)


def _fake_diag(step_seed=0):
    """A diag-shaped dict of device/np arrays like the engine emits."""
    return {
        "e/count": np.float32(512 + step_seed),
        "e/queue_occ": np.array([128, 130, 126, 128 + step_seed]),
        "e/queue_skew": np.int32(4 + step_seed),
        "e/migration_overflow": np.int32(0),
        "n_ionized": np.int32(3),
    }


# ------------------------------------------------------------ metrics stream


def test_metrics_stream_schema_roundtrip():
    """Every record a produced stream writes validates against the schema
    contract (header first, steps strictly increasing, typed fields)."""
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "metrics.jsonl")
        with obs_metrics.MetricsStream(capacity=8, jsonl_path=path,
                                       config={"async_n": 4}) as stream:
            for i in range(5):
                rec = stream.record(_fake_diag(i), wall_us=1000.0 + i)
                assert rec.step == i
                assert rec.queues["e"] == [128, 130, 126, 128 + i]
        header, steps = obs_metrics.read_jsonl(path)
        assert header is not None and header["config"] == {"async_n": 4}
        assert len(steps) == 5
        errs = obs_metrics.validate_stream([header] + steps)
        assert errs == [], errs
    summary = stream.summary()
    assert summary["steps"] == 5
    assert summary["max_queue_skew"] == 8.0          # 4 + last seed
    assert summary["totals"]["n_ionized"] == 15.0    # 3 per step


def test_metrics_ring_is_bounded():
    stream = obs_metrics.MetricsStream(capacity=3)
    for i in range(10):
        stream.record(_fake_diag(), wall_us=1.0, step=i)
    assert [m.step for m in stream.window(99)] == [7, 8, 9]
    assert stream.window(2)[-1].step == 9
    assert stream.window(0) == []


def test_validate_record_rejects_malformed():
    good = obs_metrics.StepMetrics(0, 10.0, {"a": 1.0},
                                   {"e": [1, 2]}).to_json()
    assert obs_metrics.validate_record(good) == []
    bad = [
        dict(good, schema=99),
        dict(good, step=-1),
        dict(good, wall_us="fast"),
        dict(good, counters={"a": "nope"}),
        dict(good, queues={"e": [1.5]}),
        dict(good, kind="mystery"),
        "not a record",
    ]
    for rec in bad:
        assert obs_metrics.validate_record(rec), rec
    # header records: schema + config object only
    assert obs_metrics.validate_record(
        {"schema": 1, "kind": "header", "config": {}}) == []
    assert obs_metrics.validate_record(
        {"schema": 1, "kind": "header", "config": "x"})
    # stream-level: header must be first, steps strictly increasing
    hdr = {"schema": 1, "kind": "header", "config": {}}
    assert obs_metrics.validate_stream([hdr, good, dict(good, step=0)])
    assert obs_metrics.validate_stream([good, hdr])
    assert obs_metrics.validate_stream([hdr, good, dict(good, step=1)]) == []


def test_atomic_write_preserves_existing_on_failure():
    """An unserializable payload must leave the previous file intact (an
    interrupted writer never truncates it)."""
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "record.json")
        obs_metrics.atomic_write_json(path, {"good": 1})
        try:
            obs_metrics.atomic_write_json(path, {"bad": object()})
            raise AssertionError("expected TypeError")
        except TypeError:
            pass
        with open(path) as fh:
            assert json.load(fh) == {"good": 1}
        assert os.listdir(td) == ["record.json"]       # no tmp litter


# ---------------------------------------------------------- trace annotations


def test_engine_phase_scopes_reach_the_jaxpr():
    """The engine's phase annotations survive into the traced computation:
    both the trace-time capture hook and the jaxpr name stacks see them."""
    import dataclasses

    cfg = dataclasses.replace(_cfg(), field_solve=True)
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=2,
                               max_migration=64, max_births=64)
    state = engine.init_engine_state(ecfg, mesh, 0)
    step = engine.make_engine_step(ecfg, mesh, donate=False)
    with tracing.capture_scopes() as seen:
        closed = jax.make_jaxpr(step)(state)
    for want in ("engine/state", "engine/ingest", "engine/field",
                 "engine/sources", "engine/split", "engine/push/q0",
                 "engine/push/q1", "engine/ionize/q0", "engine/migrate/q1",
                 "engine/merge", "engine/diag"):
        assert want in seen, (want, sorted(set(seen)))
    stacks = tracing.jaxpr_scope_names(closed)
    for want in ("engine/push/q0", "engine/push/q1", "engine/migrate/q0",
                 "engine/merge", "engine/diag", "halo/sum", "halo/poisson",
                 "halo/efield", "halo/ppermute", "engine/split",
                 "engine/sources/ne_deposit", "engine/push/q0/field_gather",
                 "engine/push/q1/move", "engine/push/q0/deposit",
                 "engine/ionize/q0/draw", "engine/ionize/q1/births",
                 "engine/migrate/q1/pack", "engine/migrate/q0/ring",
                 "engine/migrate/q0/send/halo/ppermute",
                 "engine/merge/layout", "engine/merge/claim"):
        assert any(want in s for s in stacks), (want, len(stacks))


def test_trace_session_writes_capture():
    """start/stop capture around real device work produces trace files;
    a None profile dir is a no-op."""
    with tracing.trace_session(None):
        pass
    with tempfile.TemporaryDirectory() as td:
        profile_dir = os.path.join(td, "trace")
        with tracing.trace_session(profile_dir):
            with tracing.host_span("test/host_work"):
                jax.block_until_ready(
                    jax.jit(lambda x: x * 2)(np.arange(8.0)))
        files = [os.path.join(r, f) for r, _, fs in os.walk(profile_dir)
                 for f in fs]
        assert files, "trace capture wrote no files"


# ------------------------------------------------------ metrics-toggle parity


def metrics_parity_matrix():
    """EngineConfig.metrics is diagnostics-only: final state and the shared
    diag keys are bitwise identical across D x async_n (acceptance grid)."""
    cfg = _cfg()
    for d in (1, 2, 4):
        mesh = make_debug_mesh(data=d, model=1)
        for n_q in (1, 2, 4):
            outs = {}
            for flag in (False, True):
                ecfg = engine.EngineConfig(
                    pic=cfg, axis_names=("data",), async_n=n_q,
                    max_migration=64, max_births=64, metrics=flag)
                state = engine.init_engine_state(ecfg, mesh, 0)
                step = engine.make_engine_step(ecfg, mesh)
                for _ in range(3):
                    state, diag = step(state)
                outs[flag] = (jax.tree.leaves(state), diag)
            leaves_off, diag_off = outs[False]
            leaves_on, diag_on = outs[True]
            for a, b in zip(leaves_off, leaves_on):
                assert np.array_equal(np.asarray(a), np.asarray(b)), \
                    (d, n_q, "state leaf differs")
            for k, v in diag_off.items():
                assert np.array_equal(np.asarray(v),
                                      np.asarray(diag_on[k])), (d, n_q, k)
            extra = set(diag_on) - set(diag_off)
            assert any(k.endswith("/ring_free") for k in extra), (d, n_q)
            assert any(k.endswith("/pending_rows") for k in extra), (d, n_q)


def test_metrics_toggle_bitwise_parity():
    _dispatch("metrics_parity_matrix")
