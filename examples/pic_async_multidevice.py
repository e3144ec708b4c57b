"""The asynchronous multi-device engine end-to-end on emulated devices.

Runs the BIT1 scenario under the async(n) queue scheduler with the
halo-exchange field phase and verifies conservation against the initial
population.

    PYTHONPATH=src python examples/pic_async_multidevice.py \
        --domains 4 --async-n 2 [--steps 40]

For the per-phase timeline the paper reads from Nsight, capture a trace of
the same run with ``python -m repro.launch.pic_run --domains 4 --async-n 2
--profile-dir <dir>``: every phase of the step is a named scope there
(``engine/push/q<k>``, ``engine/migrate/q<k>``, ``halo/...``; the tree is
in ``docs/observability.md``).

Emulated host devices are requested automatically when the process exposes
fewer devices than --domains (a TPU slice provides real ones natively).
"""

import argparse
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--domains", type=int, default=4)
    ap.add_argument("--async-n", type=int, default=2)
    ap.add_argument("--rebalance-every", type=int, default=0,
                    help="compact + re-split queues every K steps (0 = off)")
    ap.add_argument("--rebalance-skew", type=int, default=0,
                    help="also re-split when per-queue occupancy skew "
                         "exceeds this threshold (0 = off)")
    ap.add_argument("--ionization", action="store_true",
                    help="keep the scenario's MC ionization source active "
                         "(ring-claimed births on the queue pipeline); the "
                         "conservation check then accounts for the pairs")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--nc", type=int, default=512)
    ap.add_argument("--n", type=int, default=16_384)
    args = ap.parse_args()

    # must run before jax initializes; respects an externally-set XLA_FLAGS
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.domains}")

    import dataclasses

    import jax
    import numpy as np

    from repro.configs.pic_bit1 import make_bench_config, make_engine_config
    from repro.distributed import engine
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(data=args.domains, model=1)
    cfg = make_bench_config(nc=args.nc, n=args.n, strategy="fused")
    # enable the halo field phase (the paper's own test disables it); by
    # default run pure transport, or keep the scenario's MC ionization on
    # the queue pipeline (ring-claimed births) with --ionization
    cfg = dataclasses.replace(
        cfg, field_solve=True,
        ionization=cfg.ionization if args.ionization else None)
    ecfg = make_engine_config(cfg, async_n=args.async_n, max_migration=2048,
                              max_births=2048,
                              rebalance_every=args.rebalance_every,
                              rebalance_skew=args.rebalance_skew)

    state = engine.init_engine_state(ecfg, mesh, seed=0)
    step = engine.make_engine_step(ecfg, mesh)
    n0 = {sc.name: (sc.n_init // args.domains) * args.domains
          for sc in cfg.species}

    t0 = time.perf_counter()
    migrated = ionized = 0
    for _ in range(args.steps):
        state, diag = step(state)
        migrated += int(np.asarray(diag["e/migrated_left"])) + int(
            np.asarray(diag["e/migrated_right"]))
        if args.ionization:
            ionized += int(np.asarray(diag["n_ionized"]))
    jax.block_until_ready(state.species[0].x)
    wall = time.perf_counter() - t0

    print(f"{args.steps} steps on D={args.domains} devices, "
          f"async_n={args.async_n}: {wall:.2f}s "
          f"({wall / args.steps * 1e3:.1f} ms/step), "
          f"{migrated} electron migrations, {ionized} ionizations")
    # every ionization kills one neutral and births an (e-, D+) pair
    delta = {"e": ionized, "D+": ionized, "D": -ionized}
    ok = True
    for sc in cfg.species:
        cnt = int(np.asarray(diag[f"{sc.name}/count"]))
        want = n0[sc.name] + delta.get(sc.name, 0)
        print(f"  {sc.name}: {cnt} particles (expect {want}), "
              f"charge {float(np.asarray(diag[f'{sc.name}/charge'])):+.2f}, "
              f"queue occupancy {np.asarray(diag[f'{sc.name}/queue_occ'])} "
              f"(skew {int(np.asarray(diag[f'{sc.name}/queue_skew']))})")
        ok &= cnt == want
    assert ok, "conservation FAILED"
    print("conservation PASSED")


if __name__ == "__main__":
    main()
