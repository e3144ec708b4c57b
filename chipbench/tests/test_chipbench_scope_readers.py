"""The readers of the engine's finer scopes (``queue_layout_ms``,
``field_gather_ms``, ``unscoped_share``) on constructed events."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, trace_reduce as tr  # noqa: E402
from chipbench.layer_metrics import (bookkeeping_ms, field_gather_ms,  # noqa: E402
                                     push_ms, queue_layout_ms,
                                     unscoped_share)

E = tr.Event
PEAKS = {"hbm_bytes_per_s": 1e9}


def one_chip(program):
    """Reduce one step on one chip, its ops back to back; ``program`` is
    a list of (instruction, self ns, op_name)."""
    ops, t = [], 0
    for name, ns, _ in program:
        ops.append(E(t, ns, f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"))
        t += ns
    red = tr.reduce({"/device:TPU:0": tr.Device(ops, [])},
                    [E(0, t, tr.WINDOW_SPAN)],
                    {name: op for name, _, op in program})
    return harness.LayerContext(red, steps=1, pushed=[1], domains=1,
                                peaks=PEAKS)


# one step on one chip, by the engine's finer scopes (self ns):
#   fusion.4   engine/split             30
#   fusion.5   push q0 field_gather     20
#   fusion.13  push q1 field_gather     20   (the vmapped gather's op_name)
#   fusion.7   push q1 move              5
#   fusion.18  engine/merge/layout      40
#   fusion.9   engine/merge/claim       10
#   fusion.90  no op_name               15
#   fusion.40  vmap(field_gather): a scope opened inside a vmap, 10
FINE = [("fusion.4", 30, "jit(f)/engine/split/gather"),
        ("fusion.5", 20, "jit(f)/engine/push/q0/field_gather/vmap()/gather"),
        ("fusion.13", 20, "jit(f)/engine/push/q1/field_gather/vmap()/gather"),
        ("fusion.7", 5, "jit(f)/engine/push/q1/move/vmap()/add"),
        ("fusion.18", 40, "jit(f)/engine/merge/layout/jit(take)/gather"),
        ("fusion.9", 10, "jit(f)/engine/merge/claim/vmap()/scatter"),
        ("fusion.90", 15, ""),
        ("fusion.40", 10, "jit(f)/engine/push/q0/vmap(field_gather)/mul")]

# the same step as a program with the phase scopes alone
COARSE = [("fusion.1", 10, "jit(f)/engine/push/q0/add"),
          ("fusion.2", 20, "jit(f)/engine/merge/gather"),
          ("fusion.3", 10, "jit(f)/engine/ionize/q0/select"),
          ("copy.4", 5, "")]


@pytest.fixture
def fine():
    return one_chip(FINE)


def test_queue_layout_reads_split_and_merge_layout(fine):
    assert queue_layout_ms.compute(fine) == pytest.approx((30 + 40) / 1e6)
    # the merge layout is a part of the bookkeeping as well
    assert bookkeeping_ms.compute(fine) == pytest.approx((40 + 10) / 1e6)


def test_field_gather_reads_every_queue_and_is_part_of_push(fine):
    # q0 and q1; a scope opened inside the vmap is not the mover's path
    assert field_gather_ms.compute(fine) == pytest.approx((20 + 20) / 1e6)
    assert push_ms.compute(fine) == pytest.approx((20 + 20 + 5 + 10) / 1e6)


def test_unscoped_share_is_of_all_self_time(fine):
    assert unscoped_share.compute(fine) == pytest.approx(100 * 15 / 150)


def test_unscoped_share_is_a_mean_over_chips():
    def chip(unscoped, scoped):
        return tr.Device([E(0, scoped, "%fusion.1 = f32[] fusion()"),
                          E(scoped, unscoped, "%copy.2 = f32[] copy()")], [])

    red = tr.reduce({"/device:TPU:0": chip(10, 90),
                     "/device:TPU:1": chip(30, 70)},
                    [E(0, 100, tr.WINDOW_SPAN)],
                    {"fusion.1": "jit(f)/engine/push/q0/add", "copy.2": ""})
    ctx = harness.LayerContext(red, steps=1, pushed=[1], domains=2,
                               peaks=PEAKS)
    assert unscoped_share.compute(ctx) == pytest.approx(20.0)


def test_new_readers_without_data_return_none():
    red = tr.reduce({"/device:TPU:0": tr.Device([], [])},
                    [E(0, 10, tr.WINDOW_SPAN)], {})
    ctx = harness.LayerContext(red, steps=1, pushed=[1], domains=1,
                               peaks=PEAKS)
    for reader in (queue_layout_ms, field_gather_ms, unscoped_share):
        assert reader.compute(ctx) is None


def test_new_readers_find_nothing_in_a_program_without_the_scopes():
    """A program with the phase scopes alone (no split, layout or
    field_gather scopes) gives no reading; the unscoped share still has
    one."""
    ctx = one_chip(COARSE)
    assert queue_layout_ms.compute(ctx) is None
    assert field_gather_ms.compute(ctx) is None
    assert unscoped_share.compute(ctx) == pytest.approx(100 * 5 / 45)
