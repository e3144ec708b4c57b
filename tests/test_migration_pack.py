"""The migration pack finds its crossers without a full-length scatter.

``particles.first_true_indices`` returns the first ``size`` True indices
of a mask, padded with ``len(mask)``, by a binary search over the mask's
running count. Its oracle is the form it replaces,
``jnp.nonzero(mask, size=size, fill_value=len(mask))[0]``, whose
``bincount`` scatter-adds one update per slot: the two must agree bit for
bit, in value and dtype, on every mask. ``engine._exchange_queue`` built on
it must give every output of the pack, bit for bit, as with the old line
put back: crossers both ways, more leavers than the pack holds (the clamp
and retry, ``migration_overflow``) and the absorbing walls.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.particles import StackedSpecies, first_true_indices
from repro.distributed import engine


def old_first_true(mask, size):
    return jnp.nonzero(mask, size=size, fill_value=mask.shape[0])[0]


def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, (a.dtype, b.dtype)
    assert a.tobytes() == b.tobytes()


# ------------------------------------------------------------ the helper

def _mask(kind, cap, seed=0):
    rng = np.random.default_rng(seed)
    m = np.zeros(cap, bool)
    if kind == "all_true":
        m[:] = True
    elif kind == "first_slot":
        m[0] = True
    elif kind == "last_slot":
        m[-1] = True
    elif kind == "sparse":
        m = rng.random(cap) < 1e-3
    elif kind == "dense":
        m = rng.random(cap) < 0.7
    return jnp.asarray(m)


# (mask, cap, size): size below, at and past the number of trues, and
# past the mask's own length
CASES = [
    ("empty", 4096, 64),
    ("all_true", 4096, 64),           # more trues than size
    ("all_true", 100, 256),           # size > cap
    ("first_slot", 4096, 8),
    ("last_slot", 4096, 8),
    ("last_slot", 1, 3),
    ("sparse", 65536, 512),
    ("sparse", 65536, 8),             # more trues than size
    ("dense", 5000, 8192),            # size > cap
    ("dense", 65536, 1024),
]


@pytest.mark.parametrize("kind,cap,size", CASES)
def test_first_true_indices_equals_nonzero(kind, cap, size):
    mask = _mask(kind, cap)
    got = first_true_indices(mask, size)
    _same(got, old_first_true(mask, size))
    _same(jax.jit(first_true_indices, static_argnums=1)(mask, size),
          old_first_true(mask, size))


@pytest.mark.parametrize("size", [16, 2048])
def test_first_true_indices_under_vmap(size):
    """Over a species axis, as the pack runs it: one mask per row, each
    with its own count of trues."""
    rng = np.random.default_rng(7)
    mask = jnp.asarray(np.stack([
        np.zeros(8192, bool), rng.random(8192) < 0.01,
        rng.random(8192) < 0.5, np.ones(8192, bool)]))
    got = jax.vmap(lambda m: first_true_indices(m, size))(mask)
    _same(got, jax.vmap(lambda m: old_first_true(m, size))(mask))


# -------------------------------------------------------------- the pack

L_LOCAL, CAPQ, S = 64.0, 4096, 3


def _queue(seed, leave_share, left_share=0.5):
    """A (S, CAPQ) queue: 60% of rows alive, ``leave_share`` of the live
    rows past an edge (``left_share`` of those past the left one), the rest
    inside; dead rows hold positions past both edges, which must not
    leave."""
    rng = np.random.default_rng(seed)
    alive = rng.random((S, CAPQ)) < 0.6
    x = rng.uniform(0.0, L_LOCAL, (S, CAPQ))
    leave = alive & (rng.random((S, CAPQ)) < leave_share)
    left = leave & (rng.random((S, CAPQ)) < left_share)
    x = np.where(left, rng.uniform(-2.0, -1e-6, (S, CAPQ)), x)
    x = np.where(leave & ~left, L_LOCAL + rng.uniform(0.0, 2.0, (S, CAPQ)),
                 x)
    x = np.where(alive, x, rng.choice([-5.0, L_LOCAL + 5.0], (S, CAPQ)))
    v = rng.normal(size=(S, CAPQ, 3))
    w = np.where(alive, rng.uniform(0.5, 1.5, (S, CAPQ)), 0.0)
    return StackedSpecies(x=jnp.asarray(x, jnp.float32),
                          v=jnp.asarray(v, jnp.float32),
                          w=jnp.asarray(w, jnp.float32),
                          alive=jnp.asarray(alive))


# (queue seed, share of live rows leaving, of those to the left, pack
# budget m per direction, boundary, at the first domain, at the last)
PACKS = {
    "both_ways": (1, 0.01, 0.5, 64, "periodic", True, True),
    "one_way_past_its_budget": (2, 0.01, 0.95, 16, "periodic", False,
                                False),
    "more_than_the_pack": (3, 0.2, 0.5, 64, "periodic", False, False),
    "absorbing_walls": (4, 0.01, 0.5, 64, "absorb", True, True),
    "absorbing_left_wall_overflow": (5, 0.2, 0.3, 64, "absorb", True,
                                     False),
}


@pytest.mark.parametrize("case", sorted(PACKS))
def test_exchange_queue_equals_nonzero_pack(monkeypatch, case):
    seed, share, left, m, boundary, first, last = PACKS[case]
    q = _queue(seed, share, left)
    args = (q, L_LOCAL, m, boundary, jnp.asarray(first), jnp.asarray(last))
    got = engine._exchange_queue(*args)
    monkeypatch.setattr(engine, "first_true_indices", old_first_true)
    want = engine._exchange_queue(*args)
    g, w = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(g) == len(w)
    for a, b in zip(g, w):
        _same(a, b)

    diag = got[-1]
    sent = diag["migrated_left"] + diag["migrated_right"]
    assert int(jnp.min(sent + diag["wall_absorbed"])) > 0
    over = int(jnp.min(diag["migration_overflow"]))
    if case in ("more_than_the_pack", "one_way_past_its_budget",
                "absorbing_left_wall_overflow"):
        assert over > 0
    else:
        assert int(jnp.max(diag["migration_overflow"])) == 0
    if boundary == "absorb":
        assert int(jnp.min(diag["wall_absorbed"])) > 0
