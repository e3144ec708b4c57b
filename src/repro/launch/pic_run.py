"""PIC launcher: run the paper's scenario, single- or multi-domain.

    PYTHONPATH=src python -m repro.launch.pic_run --steps 100 \
        [--domains 4] [--async-n 2] [--rebalance-every K] \
        [--rebalance-skew T] [--cell-order] [--max-births N] \
        [--see-yield Y] [--collisions elastic,cx,coulomb] \
        [--strategy unified|explicit|async_batched|fused] \
        [--field-solve] [--diag-every K] [--profile-dir DIR] \
        [--ckpt-dir DIR --ckpt-every K] [--resume] [--fail-at-step N]

--domains > 1 runs the asynchronous multi-device engine
(``repro.distributed``): the domain's particles are split into --async-n
queues whose migration collectives overlap the next queue's push, and
--rebalance-every K periodically compacts + re-splits the queues so their
occupancy stays even under churn (per-queue counts and skew are printed);
--rebalance-skew T additionally triggers the re-split whenever the
per-queue occupancy skew exceeds T. The scenario's MC ionization runs on
the same queue pipeline through the free-slot ring (--max-births bounds
births per step, like max_migration bounds sends); --see-yield Y switches
the walls to absorbing and re-emits secondary electrons with yield Y
(BIT1's plasma-wall SEE source, also ring-routed). --collisions turns on
the binary-collision menu (any comma list of elastic, cx, coulomb): the
per-cell collide phase runs between each queue's push and its migration
exchange; --cell-order makes the rebalance a BIT1-style counting sort by
cell so the queue slices stay cell-striped. If the process exposes
fewer jax devices than --domains, emulated host devices are requested via
XLA_FLAGS before jax initializes (a TPU slice provides real ones
natively); the first line printed names the platform, device kind and
device count the run used.

Observability (``repro.obs``): --profile-dir DIR captures a profiler trace
of the run (``jax.profiler.start_trace``; open in TensorBoard/Perfetto):
every device op of the engine step carries its phase scope
(``engine/<phase>[/q<k>][/<part>]``, the scope table of
``docs/observability.md``) and each step is a ``pic_run/step`` range on
the host track, with ``pic_run/metrics`` around the per-step metrics
record (the capture opens after the compile); --metrics-jsonl FILE
streams one structured metrics record per engine step (schema in
``docs/observability.md``); --autotune lets the online controller retune
the engine knobs (async_n, migration/birth budgets, rebalance triggers)
from the measured stream between steps. The last two force the engine
path even at --domains 1.

Serving (``repro.serve``): --ensemble W runs the simulation-as-a-service
demo instead of a single run — a width-W vmapped ensemble server over ONE
compiled step, fed 2*W queued sessions on a dt x ionization-rate grid
(slot reuse as sessions finish). Prints each session's final diagnostics
and the server stats; ``compiles`` staying at 1 across all sessions is the
point. Single device, --strategy unified|fused only.

Resilience (``repro.runtime.resilience``): --ckpt-dir DIR checkpoints the
full EngineState asynchronously every --ckpt-every steps; --resume restarts
from the newest complete checkpoint (bitwise when --domains matches the
save, elastic re-split otherwise); --fail-at-step N injects a simulated
failure at step N — the restart drill is to re-run the same command with
--resume. These flags force the engine path and exclude --autotune.
"""

from __future__ import annotations

import argparse
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--nc", type=int, default=4096)
    ap.add_argument("--particles", type=int, default=131_072)
    ap.add_argument("--domains", type=int, default=1)
    ap.add_argument("--async-n", type=int, default=1,
                    help="migration/compute queues per domain (paper's "
                         "async(n))")
    ap.add_argument("--rebalance-every", type=int, default=0,
                    help="compact + re-split the async queues every K steps "
                         "(0 = never); bounds per-queue occupancy skew")
    ap.add_argument("--rebalance-skew", type=int, default=0,
                    help="also compact + re-split whenever the per-queue "
                         "occupancy skew exceeds this threshold (0 = off)")
    ap.add_argument("--max-births", type=int, default=8192,
                    help="ionization birth budget per domain per step "
                         "(clamped births retry; see birth_overflow)")
    ap.add_argument("--see-yield", type=float, default=0.0,
                    help="enable absorbing walls + secondary electron "
                         "emission with this yield (0 = off)")
    ap.add_argument("--collisions", default="",
                    help="comma list from {elastic, cx, coulomb}: enable "
                         "the per-cell binary-collision menu")
    ap.add_argument("--cell-order", action="store_true",
                    help="rebalance by counting sort by cell (BIT1-style "
                         "per-cell ordering) instead of plain compaction")
    ap.add_argument("--strategy", default="unified",
                    choices=["unified", "explicit", "async_batched",
                             "fused"])
    ap.add_argument("--field-solve", action="store_true",
                    help="enable the halo-exchange field phase (the paper's "
                         "benchmark scenario disables it)")
    ap.add_argument("--diag-every", type=int, default=1,
                    help="compute full diagnostics every K-th step "
                         "(single-domain only)")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax profiler trace of the run into this "
                         "directory (TensorBoard/Perfetto); its device ops "
                         "carry the engine's phase scopes, which give the "
                         "step's per-phase times (scope table: "
                         "docs/observability.md)")
    ap.add_argument("--metrics-jsonl", default="",
                    help="stream per-step engine metrics records to this "
                         "JSONL file (engine path; schema in "
                         "docs/observability.md)")
    ap.add_argument("--autotune", action="store_true",
                    help="retune the engine knobs online from the metrics "
                         "stream (engine path)")
    ap.add_argument("--ensemble", type=int, default=0, metavar="W",
                    help="serve a width-W parameter sweep through the "
                         "vmapped ensemble engine instead of one run "
                         "(simulation-as-a-service demo; single device)")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint EngineState into this directory "
                         "(async write; engine path)")
    ap.add_argument("--ckpt-every", type=int, default=5,
                    help="checkpoint cadence in steps (with --ckpt-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest complete checkpoint in "
                         "--ckpt-dir (elastic: --domains may differ from "
                         "the save; see docs/resilience.md)")
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="inject a simulated failure at this step (restart "
                         "drill; restart the command with --resume)")
    args = ap.parse_args()
    resilient = bool(args.ckpt_dir) or args.fail_at_step >= 0
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")
    if args.autotune and resilient:
        ap.error("--autotune cannot be combined with the checkpoint flags "
                 "(the knob retunes would change the state pytree mid-run)")
    if args.ensemble and (args.domains > 1 or args.async_n > 1 or resilient
                          or args.autotune):
        ap.error("--ensemble is the single-device serving demo; it excludes "
                 "--domains/--async-n > 1, the checkpoint flags and "
                 "--autotune")

    if args.domains > 1:
        # must happen before jax initializes; a no-op when XLA_FLAGS is
        # already set (e.g. a real TPU slice or an outer test harness).
        # It only adds host devices: on TPU the mesh takes the chips.
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.domains}")

    import dataclasses

    import jax
    import numpy as np

    from repro.launch.cache import enable_compilation_cache

    enable_compilation_cache()
    devs = jax.devices()
    print(f"device: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}")

    from repro.configs.pic_bit1 import (make_bench_config,
                                        make_collision_menu,
                                        make_engine_config, make_see_config)
    from repro.core import pic
    from repro.distributed import engine
    from repro.launch.mesh import make_debug_mesh

    if args.see_yield > 0.0:
        cfg = make_see_config(nc=args.nc, n=args.particles,
                              strategy=args.strategy,
                              emission_yield=args.see_yield,
                              diag_every=args.diag_every)
    else:
        cfg = make_bench_config(nc=args.nc, n=args.particles,
                                strategy=args.strategy,
                                diag_every=args.diag_every)
    if args.field_solve:
        cfg = dataclasses.replace(cfg, field_solve=True)
    if args.collisions:
        menu = tuple(m for m in args.collisions.split(",") if m)
        cfg = dataclasses.replace(cfg,
                                  collisions=make_collision_menu(menu))
    if args.ensemble:
        from repro.serve import SimService

        svc = SimService(cfg, width=args.ensemble)
        t0 = time.perf_counter()
        sids = []
        for i in range(2 * args.ensemble):
            # a small dt x ionization-rate grid: every session is its own
            # parameter point, all through ONE compiled vmapped step
            sids.append(svc.submit(
                {"dt": cfg.dt * (1.0 + 0.1 * (i % args.ensemble)),
                 "ionization_rate": cfg.ionization_rate * (1 + i)},
                seed=i, steps=args.steps))
        svc.run_until_drained()
        wall = time.perf_counter() - t0
        for sid in sids:
            p = svc.poll(sid)
            kes = {k: float(np.asarray(v).sum()) for k, v in p["diag"].items()
                   if k.endswith("/ke")}
            print(f"session {sid}: slot={p['slot']} "
                  f"steps={p['steps_done']} ke={kes}")
        st = svc.stats()
        print(f"{len(sids)} sessions x {args.steps} steps, width="
              f"{args.ensemble}: {wall:.2f}s — stats {st}")
        assert st["compiles"] == 1, st
        return

    from repro.obs import MetricsStream, tracing

    want_stream = bool(args.metrics_jsonl or args.autotune)
    profile_dir = args.profile_dir or None
    t0 = time.perf_counter()
    mesh = ecfg = None
    if (args.domains == 1 and args.async_n == 1
            and args.rebalance_every == 0 and args.rebalance_skew == 0
            and not args.cell_order and not want_stream and not resilient):
        state = pic.init_state(cfg, 0)
        fn = jax.jit(lambda s: pic.run(cfg, args.steps, state=s))
        if profile_dir:
            # keep the (huge) XLA compile out of the captured trace: the
            # profile should show the run's phase ranges, not the compiler
            fn = fn.lower(state).compile()
        with tracing.trace_session(profile_dir):
            final, diags = jax.block_until_ready(fn(state))
        # count from the final state, not the diag trace: with
        # --diag-every K the trace holds zeros on off-steps
        counts = {f"{sc.name}/count": int(buf.count())
                  for sc, buf in zip(cfg.species, final.species)}
        colls = {k: int(np.asarray(v).sum()) for k, v in diags.items()
                 if k.startswith("coll_")}
        if colls:
            print("collisions (total):", colls)
        balance = {}
    else:
        mesh = make_debug_mesh(data=args.domains, model=1)
        ecfg = make_engine_config(cfg, max_migration=8192,
                                  async_n=args.async_n,
                                  max_births=args.max_births,
                                  rebalance_every=args.rebalance_every,
                                  rebalance_skew=args.rebalance_skew,
                                  cell_order=args.cell_order,
                                  metrics=want_stream)
        state = engine.init_engine_state(ecfg, mesh, 0)
        stream = None
        if want_stream:
            stream = MetricsStream(
                jsonl_path=args.metrics_jsonl or None,
                config={"domains": args.domains,
                        "async_n": args.async_n,
                        "max_births": args.max_births,
                        "rebalance_every": args.rebalance_every,
                        "rebalance_skew": args.rebalance_skew,
                        "steps": args.steps,
                        "autotune": bool(args.autotune)})
        if resilient:
            from repro.ckpt.checkpoint import Checkpointer
            from repro.runtime import resilience
            from repro.runtime.fault_tolerance import (FailureInjector,
                                                       SimulatedFailure)
            ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
            if args.resume:
                step0, state = resilience.resume_engine(ecfg, mesh, ckpt)
                print(f"resumed from checkpoint step {step0} "
                      f"in {args.ckpt_dir}")
            inj = (FailureInjector(args.fail_at_step)
                   if args.fail_at_step >= 0 else None)
            diag = {}
            try:
                with tracing.trace_session(profile_dir):
                    state, run_diags = resilience.run_engine(
                        ecfg, mesh, state, num_steps=args.steps, ckpt=ckpt,
                        ckpt_every=args.ckpt_every, injector=inj,
                        stream=stream, collect=True)
                if run_diags:
                    diag = run_diags[-1]
            except SimulatedFailure as e:
                if stream is not None:
                    stream.close()
                print(f"simulated failure: {e} — restart the same command "
                      f"with --resume to continue from the newest "
                      f"checkpoint")
                return
        elif args.autotune:
            from repro.obs.autotune import AutoTuner
            tuner = AutoTuner(ecfg, mesh, stream=stream)
            with tracing.trace_session(profile_dir):
                for i in range(args.steps):
                    with jax.profiler.StepTraceAnnotation("pic_run/step",
                                                          step_num=i):
                        state, diag = tuner.run_step(state)
            ecfg = tuner.ecfg
            for line in tuner.log:
                print("autotune:", line)
        else:
            step = engine.make_engine_step(ecfg, mesh)
            if profile_dir:
                step = step.lower(state).compile()  # compile outside trace
            with tracing.trace_session(profile_dir):
                for i in range(args.steps):
                    with jax.profiler.StepTraceAnnotation("pic_run/step",
                                                          step_num=i):
                        ts = time.perf_counter()
                        state, diag = step(state)
                        if stream is not None:
                            with tracing.host_span("pic_run/metrics"):
                                jax.block_until_ready(diag)
                                stream.record(diag, wall_us=(
                                    time.perf_counter() - ts) * 1e6)
                jax.block_until_ready(state.species[0].x)
        if stream is not None:
            print("metrics:", stream.summary())
            stream.close()
        counts = {k: int(np.asarray(v)) for k, v in diag.items()
                  if k.endswith("/count")}
        sources = {k: int(np.asarray(v)) for k, v in diag.items()
                   if k in ("n_ionized", "birth_overflow")
                   or k.startswith("coll_")
                   or k.endswith(("/emitted", "/emission_overflow"))}
        if sources:
            print("mc sources (last step):", sources)
        balance = {k: np.asarray(v).tolist() for k, v in diag.items()
                   if k.endswith(("/queue_occ", "/queue_skew"))}
    wall = time.perf_counter() - t0
    if profile_dir:
        print(f"profiler trace written to {profile_dir}")
    print(f"{args.steps} steps, {args.domains} domain(s), "
          f"async_n={args.async_n}, rebalance_every={args.rebalance_every}, "
          f"strategy={args.strategy}: {wall:.2f}s "
          f"({wall / args.steps * 1e3:.1f} ms/step)")
    print("final populations:", counts)
    if balance:
        print("queue balance:", balance)


if __name__ == "__main__":
    main()
