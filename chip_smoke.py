"""Smoke run of the PIC-MC step on TPU at the paper's section 3.3 size.

    python chip_smoke.py               # one chip: the phases below
    python chip_smoke.py --chips 4     # four chips: the 4-domain engine only

Run from the root of a checkout; everything is generated from ``--seed``.
All phases run in this one process (a chip belongs to one process), and a
failed check ends the run with a traceback and a non-zero exit.

1. device  — JAX must see a TPU. Anything else exits non-zero, naming the
   platform it found; there is no CPU fallback.
2. single  — ``make_config()`` unchanged (102,400 cells, 3 x 10,485,760
   particles, 16 Mi slots per species) through ``pic.init_state`` and
   ``pic.run``, the path ``repro.launch.pic_run`` takes at one domain.
3. engine  — the same config on the async engine at D=1, async_n=2, as
   ``pic_run --async-n 2`` runs it, with a birth budget that the expected
   ionization rate cannot overflow.
4. served  — ``SimService`` at width 2, two sessions at bench size, one
   compiled step.

Both PIC phases check exact pair accounting (every ionization adds one
electron and one ion and removes one neutral), the ionized count against
the analytic 1 - exp(-n_e R dt) expectation, and finite kinetic energy.

``--chips 4`` runs only the engine on a 4-domain mesh (async_n=2), twice:
on the published config (ionization on, field off) and with the field
solve on and ionization off, so the halo collectives run. Each run is
compared with the D=1 engine started from the same particles.

Each phase prints its compile seconds, ms/step after warm-up and the
device's peak bytes in use; these lines record the run, they are not a
benchmark. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

SIGMAS = 5.0        # statistical checks: |observed - expected| <= 5 sigma
KE_RTOL = 0.15      # D=4 vs D=1 kinetic energy, as tests/test_async_engine


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def out(line: str) -> None:
    print(line, flush=True)


# --------------------------------------------------------------- helpers


def memory(device, key: str = "peak_bytes_in_use"):
    return (device.memory_stats() or {}).get(key)


def timing(phase: str, compile_s: float, ms_per_step: float,
           devices=None) -> None:
    import jax

    peaks = [memory(d) for d in (devices or jax.devices()[:1])]
    out(f"[{phase}] compile_s={compile_s:.2f} ms_per_step={ms_per_step:.3f} "
        f"peak_bytes_in_use={peaks if len(peaks) > 1 else peaks[0]}")


def host_diag(diags):
    """Per-step diag dicts (device) -> {key: (steps,) numpy array}."""
    import numpy as np

    return {k: np.stack([np.asarray(d[k]) for d in diags]) for k in diags[0]}


def names(cfg):
    ni, ei, ii = cfg.ionization
    return (cfg.species[ni].name, cfg.species[ei].name,
            cfg.species[ii].name)


def expected_ionized(cfg, before: list[dict]) -> tuple[float, float]:
    """Mean and variance of the ionization count over the steps whose
    start-of-step populations are ``before``: each neutral ionizes with
    p = 1 - exp(-n_e R dt), n_e the mean electron density."""
    ni, ei, _ = cfg.ionization
    n_name, e_name, _ = names(cfg)
    mean = var = 0.0
    for c in before:
        n_e = c[e_name] * cfg.species[ei].weight / cfg.length
        p = -math.expm1(-n_e * cfg.ionization_rate * cfg.dt)
        mean += c[n_name] * p
        var += c[n_name] * p * (1.0 - p)
    return mean, var


def check_ionization(tag: str, cfg, n0: dict, d: dict) -> dict:
    """Pair accounting, birth refusals and the analytic rate over one run.

    ``n0`` are the initial populations, ``d`` the host diag of every step
    (``{species}/count`` after each step, ``n_ionized`` per step)."""
    import numpy as np

    n_name, e_name, i_name = names(cfg)
    ionized = int(np.sum(d["n_ionized"]))
    final = {s.name: int(d[f"{s.name}/count"][-1]) for s in cfg.species}
    delta = {k: final[k] - n0[k] for k in final}
    out(f"  {tag}: ionized={ionized} over {len(d['n_ionized'])} steps, "
        f"deltas={delta}, birth_overflow={int(np.sum(d['birth_overflow']))}")
    check(delta[e_name] == delta[i_name] == -delta[n_name] == ionized,
          f"{tag}: pair accounting d{e_name} = d{i_name} = -d{n_name} = "
          f"sum n_ionized = {ionized}")
    check(int(np.sum(d["birth_overflow"])) == 0,
          f"{tag}: no ionization refused (birth_overflow == 0)")
    before = [n0] + [{s.name: int(d[f"{s.name}/count"][k])
                      for s in cfg.species}
                     for k in range(len(d["n_ionized"]) - 1)]
    mean, var = expected_ionized(cfg, before)
    z = (ionized - mean) / math.sqrt(var)
    check(abs(z) <= SIGMAS,
          f"{tag}: ionized {ionized} vs analytic {mean:.1f} "
          f"(z={z:+.2f}, |z| <= {SIGMAS})")
    for s in cfg.species:
        check(bool(np.all(np.isfinite(d[f"{s.name}/ke"]))),
              f"{tag}: {s.name} kinetic energy finite every step")
    return {"ionized": ionized, "mean": mean, "var": var, "final": final}


def engine_config(cfg, budget: int, max_migration: int):
    from repro.configs.pic_bit1 import make_engine_config

    return make_engine_config(cfg, async_n=2, max_migration=max_migration,
                              max_births=budget)


def birth_budget(cfg, domains: int, async_n: int = 2) -> int:
    """Per-domain birth budget: 1.5x the expected events per step on one
    domain, rounded up to whole 1024-row blocks per queue, so that
    ``birth_overflow`` stays 0 over a short run."""
    ni, ei, _ = cfg.ionization
    n_e = cfg.species[ei].n_init * cfg.species[ei].weight / cfg.length
    p = -math.expm1(-n_e * cfg.ionization_rate * cfg.dt)
    per_queue = 1.5 * cfg.species[ni].n_init * p / domains / async_n
    return async_n * 1024 * math.ceil(per_queue / 1024)


def run_engine(tag: str, ecfg, mesh, state, steps: int):
    """Compile, then ``steps`` warm-up and ``steps`` timed engine steps.
    Returns (final state, host diag of all 2 * steps steps, the compiled
    step)."""
    import jax

    from repro.distributed import engine

    t0 = time.perf_counter()
    step = engine.make_engine_step(ecfg, mesh).lower(state).compile()
    compile_s = time.perf_counter() - t0
    diags = []
    for _ in range(steps):
        state, d = step(state)
        diags.append(d)
    jax.block_until_ready((state, diags))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, d = step(state)
        diags.append(d)
    jax.block_until_ready((state, diags))
    ms = (time.perf_counter() - t0) / steps * 1e3
    timing(tag, compile_s, ms, list(mesh.devices.flat))
    return state, host_diag(diags), step


# --------------------------------------------------------------- phases


def device_check(chips: int):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    out(f"[device] jax.devices()={devs}")
    out(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{d0.platform!r} ({d0.device_kind}, {len(devs)} device(s))",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices; JAX "
              f"found {len(devs)}", file=sys.stderr)
        sys.exit(2)
    return d0


def phase_single(cfg, steps: int, seed: int) -> dict:
    """``pic.init_state`` + ``pic.run`` (one scan of ``steps`` steps),
    run twice: the first call warms up, the second is timed."""
    import jax
    import numpy as np

    from repro.core import pic

    out(f"[single] nc={cfg.nc} species="
        f"{[(s.name, s.n_init, s.capacity) for s in cfg.species]}")
    state = pic.init_state(cfg, seed)
    n0 = {s.name: int(b.count()) for s, b in zip(cfg.species, state.species)}
    run = jax.jit(lambda s: pic.run(cfg, steps, state=s), donate_argnums=0)
    t0 = time.perf_counter()
    run = run.lower(state).compile()
    compile_s = time.perf_counter() - t0
    state, d1 = jax.block_until_ready(run(state))
    t0 = time.perf_counter()
    state, d2 = jax.block_until_ready(run(state))
    ms = (time.perf_counter() - t0) / steps * 1e3
    timing("single", compile_s, ms)
    d = {k: np.concatenate([np.asarray(d1[k]), np.asarray(d2[k])])
         for k in d1}
    check(int(d["ionize_dropped"].sum()) == 0,
          "single: no birth dropped by a full buffer")
    res = check_ionization("single", cfg, n0, d)
    del state
    return res


def phase_engine(cfg, steps: int, seed: int, single: dict | None) -> dict:
    """The engine at D=1, async_n=2 over 2 * ``steps`` steps."""
    import numpy as np

    from repro.distributed import engine
    from repro.launch.mesh import make_debug_mesh

    budget = birth_budget(cfg, domains=1)
    out(f"[engine] D=1 async_n=2 max_births={budget}")
    mesh = make_debug_mesh(data=1, model=1)
    ecfg = engine_config(cfg, budget, max_migration=8192)
    state = engine.init_engine_state(ecfg, mesh, seed)
    n0 = {s.name: s.n_init for s in cfg.species}
    state, d, _ = run_engine("engine", ecfg, mesh, state, steps)
    del state
    for s in cfg.species:
        check(int(d[f"{s.name}/merge_dropped"].sum()) == 0,
              f"engine: no {s.name} arrival dropped at merge")
    res = check_ionization("engine", cfg, n0, d)
    if single is not None:
        n = len(d["n_ionized"])
        check(n == 2 * steps, f"engine: ran {n} steps")
        z = (res["ionized"] - single["ionized"]) / math.sqrt(
            res["var"] + single["var"])
        check(abs(z) <= SIGMAS,
              f"engine vs single: ionized {res['ionized']} vs "
              f"{single['ionized']} (z={z:+.2f}, |z| <= {SIGMAS})")
    out(f"  engine: migration_overflow="
        f"{ {s.name: int(np.sum(d[f'{s.name}/migration_overflow'])) for s in cfg.species} }")
    return res


def phase_served(cfg, steps: int, seed: int) -> None:
    """Two sessions at their own parameter points through one compiled
    step of a width-2 ``SimService``."""
    import jax
    import numpy as np

    from repro.serve import SimService

    n_name, e_name, i_name = names(cfg)
    n0 = {s.name: s.n_init for s in cfg.species}
    svc = SimService(cfg, width=2)
    total = steps + 2                   # compile step, timed, last step
    sids = [svc.submit({"dt": cfg.dt * (1.0 + 0.1 * i),
                        "ionization_rate": cfg.ionization_rate * (1 + i)},
                       seed=seed + i, steps=total) for i in range(2)]
    t0 = time.perf_counter()
    svc.step(1)                         # compiles the ensemble step
    jax.block_until_ready(svc.state)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    svc.step(steps)
    jax.block_until_ready(svc.state)
    ms = (time.perf_counter() - t0) / steps * 1e3
    svc.run_until_drained()
    timing("served", first_s, ms)
    st = svc.stats()
    out(f"  served: stats={st}")
    check(st["compiles"] == 1, "served: one compiled step for both sessions")
    for sid in sids:
        p = svc.poll(sid)
        check(p["status"] == "done" and p["steps_done"] == total,
              f"served {sid}: done after {total} steps")
        c = {k: int(p["diag"][f"{k}/count"]) for k in n0}
        de, di, dn = (c[e_name] - n0[e_name], c[i_name] - n0[i_name],
                      c[n_name] - n0[n_name])
        check(de == di == -dn > 0,
              f"served {sid}: pair accounting d{e_name}={de} "
              f"d{i_name}={di} d{n_name}={dn}")
        check(all(np.isfinite(p["diag"][f"{k}/ke"]) for k in n0),
              f"served {sid}: kinetic energy finite")


def phase_multichip(cfg, steps: int, seed: int) -> None:
    """The engine on a 4-domain mesh vs the D=1 engine, from the same
    particles: the D=1 initial state is re-split across the four domains
    by the elastic-restore path (``resplit_host`` + ``elastic_state``)."""
    import jax
    import numpy as np

    # the checkpoint's {keypath: numpy} form, which resplit_host reads
    from repro.ckpt.checkpoint import _flatten
    from repro.distributed import engine
    from repro.launch.mesh import make_debug_mesh

    mesh1 = make_debug_mesh(data=1, model=1)
    mesh4 = make_debug_mesh(data=4, model=1)
    eps = float(np.finfo(np.float32).eps)
    # the transport run's field at this density (omega_p dt ~ 2) drives
    # fast crossers: a send budget that never overflows keeps D=4 and D=1
    # moving the same particles
    runs = (("ionization", cfg, 8192),
            ("transport", dataclasses.replace(cfg, field_solve=True,
                                              ionization=None), 262144))
    for name, c, max_migration in runs:
        budget = birth_budget(c, domains=1) if c.ionization else 2048
        ecfg = engine_config(c, budget, max_migration)
        out(f"[multichip/{name}] field_solve={c.field_solve} "
            f"ionization={c.ionization} max_births={budget} "
            f"max_migration={max_migration}")
        s1 = engine.init_engine_state(ecfg, mesh1, seed)
        flat = _flatten(s1)
        s1, d1, _ = run_engine(f"multichip/{name}/D=1", ecfg, mesh1, s1,
                               steps)
        del s1
        species, counts = engine.resplit_host(ecfg, mesh4, flat, d_old=1)
        s4 = engine.elastic_state(ecfg, mesh4, species, counts,
                                  flat["pic/key"][0])
        del flat, species
        shards = s4.pic.species[0].x.addressable_shards
        devs = {sh.device for sh in shards}
        check(len(devs) == 4 and devs == set(mesh4.devices.flat),
              f"{name}: the four domains sit on four distinct devices "
              f"{sorted(d.id for d in devs)}")
        own = {d: 0 for d in mesh4.devices.flat}
        for leaf in jax.tree.leaves(s4):
            for sh in leaf.addressable_shards:
                own[sh.device] += sh.data.nbytes
        inuse = [memory(d, "bytes_in_use") for d in mesh4.devices.flat]
        out(f"  {name}: state bytes per device={list(own.values())} "
            f"bytes_in_use per device={inuse}")
        # a device holding a second domain would exceed the others by a
        # whole domain's state; device 0 also keeps what the D=1 run left
        dom = min(own.values())
        check(None not in inuse and min(inuse) >= dom
              and max(inuse) - min(inuse) < dom,
              f"{name}: each device holds one domain (bytes_in_use within "
              f"one domain's {dom} bytes of each other)")
        s4, d4, step4 = run_engine(f"multichip/{name}/D=4", ecfg, mesh4, s4,
                                   steps)
        del s4
        hlo = step4.as_text()
        check("collective-permute" in hlo,
              f"{name}: the D=4 step exchanges over the chip interconnect "
              f"(collective-permute in the compiled program)")
        migrated = sum(int(d4[f"{s.name}/migrated_{side}"].sum())
                       for s in c.species for side in ("left", "right"))
        overflow = {s.name: int(d4[f"{s.name}/migration_overflow"].sum())
                    for s in c.species}
        out(f"  {name}: D=4 migrated={migrated} "
            f"migration_overflow={overflow}")
        check(migrated > 0, f"{name}: particles migrated between domains")
        n0 = {s.name: s.n_init for s in c.species}
        if c.ionization:
            r1 = check_ionization(f"{name}/D=1", c, n0, d1)
            r4 = check_ionization(f"{name}/D=4", c, n0, d4)
            z = (r4["ionized"] - r1["ionized"]) / math.sqrt(
                r4["var"] + r1["var"])
            check(abs(z) <= SIGMAS,
                  f"{name}: ionized D=4 {r4['ionized']} vs D=1 "
                  f"{r1['ionized']} (z={z:+.2f})")
        else:
            for s in c.species:
                a, b = d4[f"{s.name}/count"][-1], d1[f"{s.name}/count"][-1]
                check(a == b == s.n_init,
                      f"{name}: {s.name} count D=4 {a} == D=1 {b}")
        # net charge: an f32 sum whose last bits follow the reduction order
        q = {k: sum(float(d[f"{s.name}/charge"][-1]) for s in c.species)
             for k, d in (("D=1", d1), ("D=4", d4))}
        tol = sum(math.ceil(math.log2(s.n_init)) * s.n_init
                  * abs(s.charge) * s.weight * eps for s in c.species)
        check(abs(q["D=4"] - q["D=1"]) <= tol,
              f"{name}: total charge D=4 {q['D=4']} vs D=1 {q['D=1']} "
              f"(tol {tol:.3g})")
        for s in c.species:
            k4, k1 = (float(d4[f"{s.name}/ke"][-1]),
                      float(d1[f"{s.name}/ke"][-1]))
            check(math.isfinite(k4) and abs(k4 - k1) <= KE_RTOL * abs(k1),
                  f"{name}: {s.name} KE D=4 {k4:.6g} vs D=1 {k1:.6g} "
                  f"(rtol {KE_RTOL})")
        jax.clear_caches()


# --------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-domain engine and its D=1 "
                         "reference")
    ap.add_argument("--steps", type=int, default=5,
                    help="steps per warm-up and per timed window")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.cache import enable_compilation_cache

    out(f"[setup] compilation cache: {enable_compilation_cache()}")
    d0 = device_check(args.chips)

    from repro.configs.pic_bit1 import make_bench_config, make_config

    cfg = make_config()
    if args.chips == 4:
        phase_multichip(cfg, args.steps, args.seed)
    else:
        single = phase_single(cfg, args.steps, args.seed)
        phase_engine(cfg, args.steps, args.seed, single)
        phase_served(make_bench_config(), args.steps, args.seed)

    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
