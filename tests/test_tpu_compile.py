"""Ahead-of-time compiles of the PIC Pallas kernels for a TPU v5e.

jaxlib's TPU compiler compiles for a chip that is described and not
attached (``topologies.get_topology_desc``), so these tests catch what
interpret mode cannot — ops Mosaic does not lower, blocks that break the
(8, 128) tiling, more VMEM than a kernel may use — at the widths the
configs select, with no chip. The ``*_pallas`` launchers are called with
``interpret=False`` directly: ``ops`` sees the CPU backend here.

Widths: nc = 4,096 (the bench configs) and the widest each kernel takes —
the paper's 102,400 cells (``pic_bit1.NC_GLOBAL``) for the gather-free
mover and the fused cycle without deposit, ``MAX_NG_PAD`` for the one-hot
deposits, whose next tile the compiler refuses for VMEM.

The engine step is compiled too, at a small size, on one domain and on the
four of the 2x2, to pin its phase-scope coverage on the program the chip
runs: every instruction whose op_name the program wrote lies under an
``engine/`` or ``halo/`` scope, and on four domains every collective lies
under one of the exchange scopes.
"""

import math
import re

import pytest

import jax
import jax.numpy as jnp

from repro.configs.pic_bit1 import CAPACITY, NC_GLOBAL
from repro.kernels import collide, deposit, fused_cycle, mover, ops
from repro.kernels.deposit import KernelWidthError

LANES = 128
ROWS = CAPACITY // LANES      # one section 3.3 species buffer, as planes
PUSH = dict(qm=-1.0, dt=0.2, b=(0.0, 0.0, 0.0), boundary="periodic",
            interpret=False)


def _nc(ng_pad: int) -> int:
    """Cell count whose node axis pads to exactly ``ng_pad``."""
    return ng_pad - 1


def _ng_pad(nc: int) -> int:
    return nc + 1 + (-(nc + 1)) % LANES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        # what this compiles cannot be read back without a chip
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:     # any failure: no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


@pytest.fixture(scope="module")
def plane(topo):
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct((ROWS, LANES), jnp.float32,
                                sharding=SingleDeviceSharding(topo.devices[0]))


def _compile(f, *args):
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("nc", [4096, NC_GLOBAL])
def test_mover_compiles(plane, nc):
    _compile(lambda *a: mover.mover_push_pallas(*a, length=float(nc),
                                                **PUSH), *(plane,) * 6)


def _fused(nc: int, do_deposit: bool):
    return lambda *a: fused_cycle.fused_push_deposit_pallas(
        *a, x0=0.0, dx=1.0, nc=nc, ng_pad=_ng_pad(nc), length=float(nc),
        charge=-1.0, do_deposit=do_deposit, **PUSH)


@pytest.mark.parametrize("nc", [4096, NC_GLOBAL])
def test_fused_without_deposit_compiles(plane, nc):
    _compile(_fused(nc, False), *(plane,) * 7)


@pytest.mark.parametrize("nc", [4096, _nc(fused_cycle.MAX_NG_PAD)])
def test_fused_with_deposit_compiles(plane, nc):
    _compile(_fused(nc, True), *(plane,) * 7)


def _deposit(nc: int):
    return lambda x, q: deposit.deposit_pallas(
        x, q, x0=0.0, dx=1.0, nc=nc, ng_pad=_ng_pad(nc), interpret=False)


@pytest.mark.parametrize("nc", [4096, _nc(deposit.MAX_NG_PAD)])
def test_deposit_compiles(plane, nc):
    _compile(_deposit(nc), plane, plane)


@pytest.mark.parametrize("kernel,f,n_in", [
    ("fused", lambda nc: _fused(nc, True), 7),
    ("deposit", _deposit, 2),
])
def test_one_tile_past_the_limit_is_refused(plane, kernel, f, n_in):
    """The limits are tight: the next 128-node tile runs out of VMEM."""
    limit = {"fused": fused_cycle.MAX_NG_PAD,
             "deposit": deposit.MAX_NG_PAD}[kernel]
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        jax.jit(f(_nc(limit + LANES))).lower(*(plane,) * n_in).compile()


@pytest.mark.parametrize("rows", [2048, ROWS])   # bench and 3.3 pair counts
def test_ta_kick_compiles(topo, rows):
    from jax.sharding import SingleDeviceSharding

    p = jax.ShapeDtypeStruct((rows, LANES), jnp.float32,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    _compile(lambda *a: collide.ta_kick_pallas(*a, interpret=False),
             *(p,) * 5)


@pytest.mark.parametrize("kernel", ["fused_push_deposit", "deposit"])
def test_too_wide_raises_named_error_on_tpu(monkeypatch, kernel):
    """On TPU a width past the limit fails where the kernel path is taken,
    naming the limit — never a silent fallback."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    limit = {"fused_push_deposit": fused_cycle.MAX_NG_PAD,
             "deposit": deposit.MAX_NG_PAD}[kernel]
    ng = limit + 1
    x = jnp.zeros((1024,), jnp.float32)
    with pytest.raises(KernelWidthError, match=f"up to ng_pad={limit}"):
        if kernel == "deposit":
            ops.deposit(x, x, x0=0.0, dx=1.0, nc=ng - 1, ng=ng)
        else:
            ops.fused_push_deposit(
                x, jnp.zeros((1024, 3)), x > 0, x, jnp.zeros((ng,)),
                x0=0.0, dx=1.0, length=float(ng - 1), qm=-1.0, dt=0.1,
                charge=-1.0)


# ------------------------------------------------- engine step scope coverage

_COMP = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:body|condition|true_computation|false_computation"
                     r")=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_SCOPED = re.compile(r"(?:^|/)(?:engine|halo)/")
# the SPMD partitioner names the ops it makes after the shard_map itself:
# its bare name, or that and one instruction or primitive name
# (``.../shard_map/slice.12``, ``.../shard_map/reduce_window_sum`` of a
# cached lowering that kept no scope); the one-domain program has no
# partitioner ops, so an unscoped JAX op right under shard_map shows there
_PARTITIONER = re.compile(r"^jit\([^)]*\)/shard_map(?:/[\w.\-]+)?$")
_COLLECTIVE = re.compile(r"[\s)](collective-permute|all-reduce|all-gather|"
                         r"reduce-scatter|all-to-all)(?:-start|-done)?\(")
_EXCHANGE = re.compile(r"engine/migrate/q\d+/send/|halo/|engine/diag/reduce/")


def _top_level_op_names(hlo: str) -> dict:
    """``{instruction: op_name}`` of the instructions the device runs as
    ops: those of the entry computation and of the loop and branch bodies
    it calls, not those inside fusions (a fusion takes its root's
    op_name) or reducers."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.strip() == "}":
            cur = None
        elif cur:
            comps[cur].append(line)
    out, todo, seen = {}, [entry], set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += _CALLED.findall(line)
            for grp in _BRANCHES.findall(line):
                todo += [n.strip().lstrip("%") for n in grp.split(",")]
            m = _INSTR.match(line)
            if m:
                op = _OP_NAME.search(line)
                out[m.group(1)] = op.group(1) if op else ""
    return out


# the slots of each species in the compiled step below, and its queues
ENGINE_CAP, ENGINE_ASYNC_N = 2 ** 14, 2


@pytest.fixture(scope="module", params=[1, 4], ids=["d1", "d4"])
def engine_hlo(request, topo):
    """The ionization step on one v5e domain and on the four of the 2x2
    (nc 4,096 and 2**14 slots per species per domain, async_n 2), compiled
    as the chip runs it: the Pallas kernels compiled, not interpreted
    (``ops`` sees the CPU backend here). Returns (domains, HLO text)."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.pic_bit1 import make_bench_config, make_engine_config
    from repro.distributed import engine

    d = request.param
    mesh = Mesh(np.asarray(topo.devices[:d]).reshape(d, 1), ("data", "model"))
    ecfg = make_engine_config(
        make_bench_config(nc=4096 * d, n=d * ENGINE_CAP // 2),
        async_n=ENGINE_ASYNC_N, max_migration=512, max_births=1024)
    args = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        engine.state_shape(ecfg, mesh), engine.state_shardings(ecfg, mesh))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        return d, engine.make_engine_step(ecfg, mesh).lower(args).compile(
            ).as_text()


def test_engine_step_ops_are_all_scoped(engine_hlo):
    """No instruction of the engine step with a JAX op_name (``jit(...)``)
    lies outside ``engine/`` or ``halo/``; the ops the compiler makes on
    its own carry no op_name, or on four domains the partitioner's, and
    are not counted. The finer scopes the benchmark reads reach the
    compiled program, and on four domains each collective (the migration
    ppermutes, the n_e halo sum, the diag reductions) lies under
    ``engine/migrate/q<k>/send``, ``halo/`` or ``engine/diag/reduce``."""
    d, hlo = engine_hlo
    ops_ = _top_level_op_names(hlo)
    outside = {i: op for i, op in ops_.items()
               if op.startswith("jit(") and not _SCOPED.search(op)
               and not (d > 1 and _PARTITIONER.match(op))}
    assert not outside, outside
    for scope in ("engine/split/", "engine/push/q0/field_gather/",
                  "engine/push/q1/move/", "engine/merge/layout/",
                  "engine/migrate/q0/pack/", "engine/ionize/q1/draw/"):
        assert any(scope in op for op in ops_.values()), scope
    if d == 1:
        return
    colls = {}      # instruction -> (collective, op_name)
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        c = m and _COLLECTIVE.search(line[m.end():])
        if c:
            op = _OP_NAME.search(line)
            colls[m.group(1)] = (c.group(1), op.group(1) if op else "")
    kinds = {kind for kind, _ in colls.values()}
    assert {"collective-permute", "all-reduce"} <= kinds, sorted(colls)
    loose = {i: op for i, (_, op) in colls.items()
             if not _EXCHANGE.search(op)}
    assert not loose, loose
    for scope in ("engine/migrate/q0/send/", "engine/migrate/q1/send/",
                  "engine/sources/ne_deposit/halo/sum/",
                  "engine/diag/reduce/"):
        assert any(scope in op for _, op in colls.values()), scope


_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([a-z]\w*)"
                  r"\[([\d,]*)\]")
_INDEXED = re.compile(r"=\s*\S+\s+(gather|scatter)\(%?[\w.\-]+,\s*%?"
                      r"([\w.\-]+)")


def test_queue_layout_does_not_index(engine_hlo):
    """The queue split, the merge back to slot order and the diagnostics'
    per-queue counts compile to no gather (JAX lowers ``a[:, k::n]`` and a
    ``take`` to gathers, which move the buffer element by element), and
    the split and merge to no scatter either. The diagnostics' scatters
    are the pending rows landed for the counts: their indices cover the
    pending rows, far fewer than a queue's slots."""
    _, hlo = engine_hlo
    dims = {m.group(1): m.group(3) for m in map(_DEF.match,
                                                hlo.splitlines()) if m}
    found = []
    for line in hlo.splitlines():
        m = _INDEXED.search(line)
        op = _OP_NAME.search(line)
        if m and op:
            found.append((m.group(1), op.group(1),
                          int(dims[m.group(2)].split(",")[0])))
    assert any(kind == "gather" for kind, _, _ in found)   # the parse works
    assert any("tpu_custom_call" in line and "engine/merge/layout/" in line
               for line in hlo.splitlines())
    for kind, op, rows in found:
        assert "engine/split/" not in op, op
        assert "engine/merge/layout/" not in op, op
        if "engine/diag/" in op:
            assert kind == "scatter", op
            assert rows < ENGINE_CAP // ENGINE_ASYNC_N, (op, rows)


_SCATTER = re.compile(r"=\s*\S+\s+scatter\(%?[\w.\-]+,\s*%?([\w.\-]+),"
                      r".*\bindex_vector_dim=(\d+)")
# the scatters of the step whose indices may cover a queue's slots
_PER_SLOT_SCATTER = re.compile(r"engine/sources/ne_deposit/|"
                               r"engine/ionize/q\d+/draw/|/deposit/")


def test_no_scatter_takes_an_index_per_queue_slot(engine_hlo):
    """No scatter of the engine step, scoped or not, takes as many indices
    as a queue has slots, except those the step has a reason for:

    - the n_e deposit (``engine/sources/ne_deposit``): every electron adds
      its weight to its two nodes;
    - the MC draw's own compaction (``engine/ionize/q<k>/draw``):
      ``jnp.nonzero``'s ``bincount`` over the queue's running count;
    - any charge deposit (``.../deposit/``), one index per row deposited.

    The migration pack finds its crossers by a binary search over the
    leave mask's running count (``particles.first_true_indices``); a
    ``jnp.nonzero`` put back there scatter-adds one update per slot of
    every queue, with no op_name, and fails here."""
    _, hlo = engine_hlo
    dims = {m.group(1): m.group(3) for m in map(_DEF.match,
                                                hlo.splitlines()) if m}
    per_slot = []
    for line in hlo.splitlines():
        m = _SCATTER.search(line)
        if not m:
            continue
        shape = [int(s) for s in dims[m.group(1)].split(",") if s]
        vec = int(m.group(2))
        n_idx = math.prod(shape) // (shape[vec] if vec < len(shape)
                                        else 1)
        if n_idx >= ENGINE_CAP // ENGINE_ASYNC_N:
            op = _OP_NAME.search(line)
            per_slot.append((_INSTR.match(line).group(1),
                             op.group(1) if op else "", n_idx))
    kept = [op for _, op, _ in per_slot if _PER_SLOT_SCATTER.search(op)]
    assert any("ne_deposit/" in op for op in kept), per_slot  # parse works
    assert any("/draw/" in op for op in kept), per_slot
    loose = [s for s in per_slot if not _PER_SLOT_SCATTER.search(s[1])]
    assert not loose, loose
