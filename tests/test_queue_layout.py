"""The engine's queue layout: slot c belongs to queue c % n.

``_split_queues`` takes each queue as a strided slice, ``_merge_queues``
interleaves the queues back into slot order with the lane-shuffle kernel
(``kernels/interleave.py``, interpret mode here) and ``_queue_occupancy``
counts each queue with a masked sum. None of them indexes. The oracles
below are the index forms they replace (``a[:, k::n]``, a ``take`` back to
slot order, a strided count): every result must equal them bit for bit,
for every n that divides the capacity, for f32 (S, cap), f32 (S, cap, 3)
and bool leaves, with -0.0, infinities, NaN payloads and subnormals in the
dead rows. A whole engine step run through the oracles gives the same
state and diag, bit for bit.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import pic
from repro.core.collisions import CollisionConfig
from repro.core.particles import StackedSpecies
from repro.distributed import engine
from repro.launch.mesh import make_debug_mesh

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
HERE = os.path.dirname(os.path.abspath(__file__))

# f32 bit patterns put in the dead rows: -0.0, +inf, -inf, a quiet NaN
# with a payload, a negative NaN, the smallest and the largest subnormal
SPECIAL = np.array([0x80000000, 0x7F800000, 0xFF800000, 0x7FC00001,
                    0xFFC12345, 0x00000001, 0x807FFFFF], np.uint32)

# (n, capacity): capacities of whole 128-lane kernel blocks, and one whose
# queues are not (the kernel's padded path), which n = 3 divides too
SIZES = [(1, 8192), (2, 8192), (4, 8192), (2, 1200), (3, 1200), (4, 1200)]
LEAVES = ["f32", "f32x3", "bool"]


# ------------------------------------------------------------ the oracles

def old_split(a, n):
    return [a[:, k::n] for k in range(n)]


def old_merge(xs, n):
    capq = xs[0].shape[1]
    c = jnp.arange(capq * n)
    return jnp.take(jnp.concatenate(xs, axis=1), (c % n) * capq + c // n,
                    axis=1)


def old_occupancy(alive, n):
    return jnp.stack([jnp.sum(alive[k::n].astype(jnp.int32))
                      for k in range(n)])


def old_split_queues(st, n):
    if n == 1:
        return [st]
    return [jax.tree.map(lambda a: a[:, k::n], st) for k in range(n)]


def old_merge_queues(queues, n):
    if n == 1:
        return queues[0]
    return jax.tree.map(lambda *xs: old_merge(xs, n), *queues)


# ------------------------------------------------------------------ data

def _leaf(kind, cap, seed, s=3):
    """A stacked leaf with random bits in live rows and the special
    patterns (cycled) in dead rows; returns (leaf, alive)."""
    rng = np.random.default_rng(seed)
    alive = rng.random((s, cap)) < 0.6
    if kind == "bool":
        return alive, alive
    shape = (s, cap) + ((3,) if kind == "f32x3" else ())
    bits = rng.integers(0, 2 ** 32, size=shape, dtype=np.uint64).astype(
        np.uint32)
    dead = ~alive if kind == "f32" else np.repeat(~alive[..., None], 3, -1)
    bits[dead] = np.resize(SPECIAL, int(dead.sum()))
    return bits.view(np.float32), alive


def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype, (a.shape, b.shape)
    assert a.tobytes() == b.tobytes()


def _split(a, n):
    st = StackedSpecies(x=a, v=a, w=a, alive=a)
    return [q.x for q in engine._split_queues(st, n)]


def _merge(qs, n):
    return engine._merge_queues(
        [StackedSpecies(x=q, v=q, w=q, alive=q) for q in qs], n).x


# ---------------------------------------------------------------- the forms

@pytest.mark.parametrize("kind", LEAVES)
@pytest.mark.parametrize("n,cap", SIZES)
def test_split_equals_strided_views(n, cap, kind):
    a, _ = _leaf(kind, cap, seed=n * cap)
    got = _split(jnp.asarray(a), n)
    want = old_split(jnp.asarray(a), n)
    assert len(got) == n
    for g, w in zip(got, want):
        _same(g, w)


@pytest.mark.parametrize("kind", LEAVES)
@pytest.mark.parametrize("n,cap", SIZES)
def test_merge_equals_take(n, cap, kind):
    qs = [jnp.asarray(_leaf(kind, cap // n, seed=7 * k + n)[0])
          for k in range(n)]
    _same(_merge(qs, n), old_merge(qs, n))


@pytest.mark.parametrize("kind", LEAVES)
@pytest.mark.parametrize("n,cap", SIZES)
def test_split_then_merge_is_identity(n, cap, kind):
    a, _ = _leaf(kind, cap, seed=cap + n)
    _same(_merge(_split(jnp.asarray(a), n), n), a)


@pytest.mark.parametrize("n,cap", SIZES)
def test_occupancy_equals_strided_count(n, cap):
    _, alive = _leaf("bool", cap, seed=3 * n)
    for row in alive:
        _same(engine._queue_occupancy(jnp.asarray(row), n),
              old_occupancy(jnp.asarray(row), n))


def test_merged_tree_keeps_dtypes_and_shapes():
    """The engine merges whole StackedSpecies pytrees in one call."""
    n, capq = 2, 640
    qs = [StackedSpecies(
        x=jnp.asarray(_leaf("f32", capq, 1 + k)[0]),
        v=jnp.asarray(_leaf("f32x3", capq, 2 + k)[0]),
        w=jnp.asarray(_leaf("f32", capq, 3 + k)[0]),
        alive=jnp.asarray(_leaf("bool", capq, 4 + k)[0])) for k in range(n)]
    got = engine._merge_queues(qs, n)
    want = old_merge_queues(qs, n)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _same(g, w)


# --------------------------------------------------- the whole engine step

N0, CAP = 1024, 4096
SPECIES = (pic.SpeciesConfig("e", -1.0, 1.0, CAP, N0, vth=1.0),
           pic.SpeciesConfig("D+", 1.0, 3672.0, CAP, N0, vth=0.02),
           pic.SpeciesConfig("D", 0.0, 3672.0, CAP, N0, vth=0.05))
TRAFFIC = {
    # ionization on the free-slot ring, the skew trigger reading the
    # queue occupancy at the ingest
    "ionize": (dict(ionization=(2, 0, 1), ionization_rate=3e-3,
                    ionization_vth_e=1.0), dict(rebalance_skew=1)),
    # the binary-collision menu (Coulomb pairs counted per queue)
    "collide": (dict(collisions=(
        CollisionConfig("elastic", 0, 2, 2e-2),
        CollisionConfig("charge_exchange", 1, 2, 2e-2),
        CollisionConfig("coulomb", 0, None, 2e-3))), {}),
}


def _run_steps(traffic, n, steps=3, seed=11):
    cfg_kw, ecfg_kw = TRAFFIC[traffic]
    cfg = pic.PICConfig(nc=256, dx=1.0, dt=0.4, species=SPECIES,
                        field_solve=False, boundary="periodic",
                        strategy="unified", **cfg_kw)
    ecfg = engine.EngineConfig(pic=cfg, axis_names=("data",), async_n=n,
                               max_migration=256, max_births=512,
                               **ecfg_kw)
    mesh = make_debug_mesh(data=1, model=1)
    state = engine.init_engine_state(ecfg, mesh, seed)
    step = engine.make_engine_step(ecfg, mesh)
    diags = []
    for _ in range(steps):
        state, d = step(state)
        diags.append(jax.device_get(d))
    return jax.device_get(state), diags


def _dump_both_forms(traffic, n, path):
    """Three steps of one seed through the engine as it is, then through
    the engine with the index forms put back; every state leaf and diag
    value of both into ``path`` (npz). Run by the test below in a child
    process."""
    out = {}
    for tag in ("new", "old"):
        if tag == "old":
            engine._split_queues = old_split_queues
            engine._merge_queues = old_merge_queues
            engine._queue_occupancy = old_occupancy
        state, diags = _run_steps(traffic, n)
        for i, leaf in enumerate(jax.tree.leaves(state)):
            out[f"{tag}/leaf{i}"] = np.asarray(leaf)
        for s, d in enumerate(diags):
            for k, v in d.items():
                out[f"{tag}/diag{s}/{k}"] = np.asarray(v)
    np.savez(path, **out)


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_engine_step_equals_index_forms(tmp_path, traffic, n):
    """Three steps of one seed through the engine as it is and through the
    engine with the index forms put back: the same state and diag, bit
    for bit.

    Both run in a child process whose XLA:CPU emits no fused multiply-add
    (``--xla_cpu_max_isa=SSE4_2``). The push hands an unkicked v on as the
    split made it, so XLA fuses the split, or the oracle's gather, into
    what reads v next; with FMA, LLVM contracts the elastic collision's
    sum of squares one way or the other by that producer, and the speed
    comes out one ulp apart though the layouts moved the same bits.
    Without FMA every op rounds once, wherever it is fused."""
    path = tmp_path / "steps.npz"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [SRC, HERE, os.environ.get("PYTHONPATH", "")]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_cpu_max_isa=SSE4_2").strip()
    prog = (f"from test_queue_layout import _dump_both_forms; "
            f"_dump_both_forms({traffic!r}, {n}, {str(path)!r})")
    run = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    got = np.load(path)
    new = {k[4:]: got[k] for k in got.files if k.startswith("new/")}
    old = {k[4:]: got[k] for k in got.files if k.startswith("old/")}
    assert new and new.keys() == old.keys()
    for k in new:
        _same(new[k], old[k])
    counter = "n_ionized" if traffic == "ionize" else "coll_coulomb"
    assert any(int(v) > 0 for k, v in new.items()
               if k.endswith(f"/{counter}"))
