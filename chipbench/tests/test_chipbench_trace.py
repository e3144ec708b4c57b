"""The trace reduction on constructed events, and its loader on a real
(CPU) trace file."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, trace_reduce as tr  # noqa: E402
from chipbench.layer_metrics import (bookkeeping_ms, collective_exposed_ms,  # noqa: E402
                                     idle_share, push_ms, push_roofline)

E = tr.Event

# one chip, a window of [0, 60) ns:
#   [0, 10)  fusion.1           engine/push/q0
#   [10, 30) while.1            engine/ingest, enclosing
#   [12, 20)   fusion.2         engine/merge
#   [30, 40) idle
#   [40, 50) collective-permute-done.1   halo/ppermute (sync part)
#   [50, 55) copy.3             no scope
#   [55, 60) idle
# and one in-flight collective [25, 48) on the async line.
OPS = [E(0, 10, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"),
       E(10, 20, "%while.1 = (s32[]) while((s32[]) %t), body=%b"),
       E(12, 8, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop"),
       E(40, 10, "%collective-permute-done.1 = f32[8]{0} "
                 "collective-permute-done((f32[8]{0}) %s)"),
       E(50, 5, "%copy.3 = f32[8]{0} copy(f32[8]{0} %r)")]
ASYNC = [E(25, 23, "%collective-permute-start.1 = (f32[8]{0}) "
                   "collective-permute-start(f32[8]{0} %a)"),
         E(5, 10, "%copy-start.7 = (f32[8]{0}) copy-start(f32[8]{0} %c)")]
HOST = [E(-5, 65, tr.WINDOW_SPAN), E(0, 1, "chipbench/step"),
        E(1, 59, "chipbench/block")]
SCOPES = {"fusion.1": "jit(f)/engine/push/q0/add",
          "while.1": "jit(f)/engine/ingest/while",
          "fusion.2": "jit(f)/engine/ingest/engine/merge/x",
          "collective-permute-done.1":
              "jit(f)/engine/migrate/q0/halo/ppermute/ppermute",
          "copy.3": ""}


@pytest.fixture
def reduced():
    host = [E(0, 60, tr.WINDOW_SPAN)] + HOST[1:]
    return tr.reduce({"/device:TPU:0": tr.Device(OPS, ASYNC)}, host, SCOPES)


def test_busy_is_union_and_idle_share(reduced):
    assert reduced.window_ns == 60
    assert reduced.busy_ns == {"/device:TPU:0": 45}
    ctx = harness.LayerContext(reduced, steps=1, pushed=[1], domains=1,
                               peaks={"hbm_bytes_per_s": 1e9})
    assert idle_share.compute(ctx) == pytest.approx(100 * 15 / 60)


def test_scope_self_time_and_unscoped(reduced):
    per = reduced.scope_ns["/device:TPU:0"]
    assert per["engine/push/q0/add"] == 10
    assert per["engine/ingest/while"] == 12          # 20 less its body's 8
    assert per["engine/ingest/engine/merge/x"] == 8
    assert per["engine/migrate/q0/halo/ppermute/ppermute"] == 10
    assert per["unscoped"] == 5
    assert sum(per.values()) == reduced.busy_ns["/device:TPU:0"]
    assert reduced.scope_sum(["unscoped"]) == 5


def test_exposed_collective(reduced):
    # collective [25, 50) less compute [0, 30) and [50, 55)
    assert reduced.exposed_ns == {"/device:TPU:0": 20}
    ctx = harness.LayerContext(reduced, steps=2, pushed=[1, 1], domains=1,
                               peaks={"hbm_bytes_per_s": 1e9})
    assert collective_exposed_ms.compute(ctx) == pytest.approx(10 / 1e6)


def test_top_ops_and_gaps(reduced):
    assert reduced.top_ops[0][0].startswith("while.1 [engine/ingest")
    assert [v for _, v in reduced.top_ops] == sorted(
        (v for _, v in reduced.top_ops), reverse=True)
    assert [v for _, v in reduced.gaps] == [10, 5]
    assert all(k.startswith("chipbench/block") for k, _ in reduced.gaps)


def test_layer_readers(reduced):
    ctx = harness.LayerContext(reduced, steps=2, pushed=[10, 10], domains=1,
                               peaks={"hbm_bytes_per_s": 1e9})
    assert push_ms.compute(ctx) == pytest.approx(5e-6)
    # 10 particles x 32 B at 1 GB/s = 320 ns against 5 ns per step
    assert push_roofline.compute(ctx) == pytest.approx(100 * 320 / 5)
    assert bookkeeping_ms.compute(ctx) == pytest.approx((12 + 8 + 10) / 2e6)


def test_reader_without_data_returns_none():
    red = tr.reduce({"/device:TPU:0": tr.Device([], [])},
                    [E(0, 10, tr.WINDOW_SPAN)], {})
    ctx = harness.LayerContext(red, steps=1, pushed=[1], domains=1,
                               peaks={"hbm_bytes_per_s": 1e9})
    assert push_ms.compute(ctx) is None
    assert push_roofline.compute(ctx) is None


def test_interval_helpers():
    assert tr.union([(3, 5), (0, 2), (1, 4)]) == [[0, 5]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.is_collective("%all-reduce.2 = f32[] all-reduce(f32[] %x)")
    assert not tr.is_collective("%fusion.3 = f32[] fusion(f32[] "
                                "%all-reduce.2)")


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((1024,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    devices, host = tr.load(str(path))
    assert devices == {}                       # no TPU planes on the CPU
    assert [e.name for e in host] == [tr.WINDOW_SPAN]
    assert host[0].dur_ns > 0
