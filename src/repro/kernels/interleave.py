"""Pallas TPU kernel: interleave n equal arrays along their last axis.

``out[..., n*i + k] = xs[k][..., i]`` — the engine's queue merge (slot c
belongs to queue c % n). On TPU the last axis is the 128-lane axis, so this
is a lane shuffle: XLA lowers it as a full-length gather, or as a
(..., cap / n, n) temporary whose minor dimension of size n is padded to
128 lanes. The kernel instead shuffles inside the vector registers, with
lane rotations and selects only, so every bit pattern (-0.0, NaN payloads,
subnormals) is moved unchanged.

Per group of 128 lanes of each input, the kernel builds n * 128 output
lanes. Input lane i goes to lane n*i + k by a "dilation": for each bit b
of i, highest first, the elements whose bit b is set move up by
(n - 1) * 2**b (a rotation, kept where the lane mask says an element lands
there). After all seven bits lane i sits at n*i; a rotation by k and a
select by ``lane % n == k`` merge the n inputs. No step overwrites an
element still to be moved, for any n: after the steps for the bits above
b, element i sits at r + n*m with r < 2**b and m a multiple of 2**(b+1)
(the bits of i above b), so the elements that move land at lanes p with
p mod (n * 2**(b+1)) in [n * 2**b, n * 2**b + 2**b), where none rests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANES = 128
_LANE_BITS = 7
# at most this many (rows, 128)-lane slabs shuffled per loop iteration:
# independent chains hide the rotations' latency until their values no
# longer fit the vector registers (v5e, merging 2 x (3, 8 Mi) f32: 4 chains
# 11.9 ms, 8 chains 6.6 ms, 16 chains 14.6 ms; 2 x (3, 3, 8 Mi): 6 chains
# 23.4 ms, 12 chains 17.6 ms)
CHAINS = 12


def _interleave_kernel(*refs, n: int, groups: int, unroll: int):
    ins, o_ref = refs[:n], refs[n]
    width = n * LANES
    slabs = list(np.ndindex(ins[0].shape[:-2]))
    shape = (ins[0].shape[-2], width)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    steps = []
    for b in reversed(range(_LANE_BITS)):
        r = lane % (2 * n << b) - (n << b)
        steps.append(((n - 1) << b, (r >= 0) & (r < (1 << b))))
    owner = lane % n

    def group(t, slab):
        src = slab + (slice(None), pl.ds(pl.multiple_of(t * LANES, LANES),
                                         LANES))
        out = None
        for k, ref in enumerate(ins):
            y = ref[src]
            if y.dtype.itemsize != 4:          # 8-bit rows shuffle as int32
                y = y.astype(jnp.int32)
            y = jnp.concatenate(
                [y, jnp.zeros((shape[0], width - LANES), y.dtype)], axis=1)
            for shift, lands in steps:
                y = jnp.where(lands, pltpu.roll(y, shift, 1), y)
            if k:
                y = pltpu.roll(y, k, 1)
            out = y if out is None else jnp.where(owner == k, y, out)
        dst = slab + (slice(None), pl.ds(pl.multiple_of(t * width, width),
                                         width))
        o_ref[dst] = out.astype(o_ref.dtype)

    def body(i, carry):
        # independent groups side by side: one group's rotations are a
        # chain of dependent steps, which alone leaves the units idle
        for j in range(unroll):
            for slab in slabs:
                group(i * unroll + j, slab)
        return carry

    lax.fori_loop(0, groups // unroll, body, 0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def interleave_pallas(*xs: Array, block: int, interpret: bool = False):
    """``xs``: n >= 2 arrays of one shape (..., rows, m) and a 32- or 8-bit
    dtype, with ``block`` a multiple of 128 that divides m. Returns
    (..., rows, n*m). Each (rows, m) slab is shuffled on its own."""
    n = len(xs)
    lead, m = xs[0].shape[:-1], xs[0].shape[-1]
    assert block % LANES == 0 and m % block == 0, (m, block)
    zeros = (0,) * len(lead)

    def spec(width):
        return pl.BlockSpec(lead + (width,), lambda i: zeros + (i,))

    groups = block // LANES
    # interpreted, an unrolled body only adds compile time
    chains = 1 if interpret else max(1, CHAINS // math.prod(lead[:-1]))
    unroll = max(d for d in range(1, chains + 1) if groups % d == 0)
    return pl.pallas_call(
        functools.partial(_interleave_kernel, n=n, groups=groups,
                          unroll=unroll),
        grid=(m // block,),
        in_specs=[spec(block)] * n,
        out_specs=spec(n * block),
        out_shape=jax.ShapeDtypeStruct(lead + (n * m,), xs[0].dtype),
        interpret=interpret,
    )(*xs)
