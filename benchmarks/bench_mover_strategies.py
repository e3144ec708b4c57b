"""Paper Fig. 7/8 analogue: mover execution time per data-movement strategy.

The paper compares OpenMP Target / OpenACC offload with explicit copies vs
unified memory on 1-2 GPUs. Our strategies (DESIGN.md §2):
  unified       — pure-jnp mover, XLA-managed data movement
  explicit      — fused Pallas kernel, BlockSpec VMEM staging
                  (interpret mode on CPU: validates, does not accelerate)
  async_batched — scan over particle batches (the async extension)
  fused         — single-pass push+deposit (kernels/fused_cycle.py on TPU,
                  flattened-deposit jnp elsewhere)
Also benchmarked: the full-cycle comparison the fused strategy exists for —
the seed-style two-pass cycle (push, then re-read the particles to deposit)
vs the fused single pass with donated buffers — plus the deposit scatter
variants and the 'onehot' MXU-style field gather vs dynamic gather.

``bench()`` returns (csv rows, machine-readable dict); ``run.py`` persists
the dict as BENCH_mover.json so later PRs have a perf trajectory.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.common import row, time_chained, time_fn
from repro.core.grid import Grid1D, deposit
from repro.core.mover import push, push_fused, push_unified
from repro.core.particles import init_uniform
from repro.kernels import ops

N = 262_144
NC = 4_096


def bench(n: int = N, nc: int = NC, iters: int = 5,
          full_cycle: bool = True) -> tuple[list[str], dict]:
    key = jax.random.PRNGKey(0)
    grid = Grid1D(nc=nc, dx=1.0)
    buf = init_uniform(key, n, n, grid.length, vth=1.0)
    e = jax.random.normal(jax.random.PRNGKey(1), (grid.ng,))

    rows: list[str] = []
    results: dict = {"n": n, "nc": nc, "backend": jax.default_backend(),
                     "strategies": {}, "full_cycle": {}}

    for strategy in ("unified", "async_batched", "explicit", "fused"):
        fn = jax.jit(lambda b, ee, s=strategy: push(
            b, ee, grid, -1.0, 0.1, strategy=s, boundary="periodic").buf.x)
        us = time_fn(fn, buf, e, iters=iters)
        rows.append(row(f"mover/{strategy}", us,
                        f"{n / us:.1f}Mparticles_per_s"))
        results["strategies"][strategy] = {
            "us_per_push": us, "particles_per_s": n / us * 1e6}

    for mode in ("take", "onehot"):
        small = Grid1D(nc=512, dx=8.0)        # onehot viable for small grids
        fn = jax.jit(lambda b, ee, m=mode: push(
            b, ee, small, -1.0, 0.1, strategy="unified", boundary="periodic",
            gather_mode=m).buf.x)
        us = time_fn(fn, buf, jax.random.normal(jax.random.PRNGKey(2),
                                                (small.ng,)), iters=iters)
        rows.append(row(f"gather/{mode}", us, ""))

    dep_x = jax.jit(lambda b: deposit(grid, b, 1.0))
    us = time_fn(dep_x, buf, iters=iters)
    rows.append(row("deposit/xla_scatter", us, ""))
    results["deposit_xla_scatter_us"] = us
    dep_k = jax.jit(lambda b: ops.deposit(b.x, b.w * b.alive, x0=0.0,
                                          dx=grid.dx, nc=grid.nc,
                                          ng=grid.ng))
    us = time_fn(dep_k, buf, iters=iters)
    rows.append(row("deposit/pallas_onehot", us, "interpret_mode"))

    if full_cycle:
        # ---- the comparison the fused strategy exists for ----
        # seed-style two-pass cycle: push writes the particles out, the
        # deposit reads them all back (two HBM round-trips, two scatters)
        @jax.jit
        def two_pass(b, ee):
            out = push_unified(b, ee, grid, -1.0, 0.1,
                               boundary="periodic").buf
            return out, deposit(grid, out, -1.0)

        # fused single pass: deposit happens inside the push over the
        # still-resident post-push state; buffers are donated so XLA
        # updates the particle arrays in place
        @partial(jax.jit, donate_argnums=0)
        def single_pass(b, ee):
            res = push_fused(b, ee, grid, -1.0, 0.1, boundary="periodic",
                             deposit_charge=-1.0)
            return res.buf, res.rho

        us_two = time_chained(lambda st: two_pass(st[0], e),
                              (buf, None), iters=iters)
        fresh = jax.tree.map(jnp.copy, buf)
        us_fused = time_chained(lambda st: single_pass(st[0], e),
                                (fresh, None), iters=iters)
        speedup = us_two / us_fused
        rows.append(row("full_cycle/unified_two_pass", us_two,
                        f"{n / us_two:.1f}Mparticles_per_s"))
        rows.append(row("full_cycle/fused_single_pass", us_fused,
                        f"speedup_vs_two_pass={speedup:.2f}x"))
        results["full_cycle"] = {
            "unified_two_pass_us": us_two,
            "fused_single_pass_us": us_fused,
            "particles_per_s_two_pass": n / us_two * 1e6,
            "particles_per_s_fused": n / us_fused * 1e6,
            "speedup": speedup,
        }
    return rows, results


def main() -> list[str]:
    return bench()[0]


if __name__ == "__main__":
    print("\n".join(main()))
