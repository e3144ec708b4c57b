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
"""

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
