"""Benchmark runner: one module per paper table/figure. CSV to stdout.

  bench_hybrid_total     — Fig. 3 (total vs mover, per strategy)
  bench_scaling          — Fig. 4 (mover scaling with domain count)
  bench_mover_strategies — Fig. 7/8 (data-movement strategies) + Fig. 5/6
                           (explicit vs unified traffic proxies) + the
                           fused-vs-two-pass full-cycle comparison
  bench_ionization       — §3.3 physics scenario throughput
  bench_lm               — assigned-architecture substrate reference

The mover-strategy results are also written as machine-readable JSON
(default ``BENCH_mover.json``) so successive PRs accumulate a perf
trajectory, and the distributed-engine scaling sweep writes per-phase
times + speedup/PE to ``BENCH_scaling.json``; both artifacts are written
atomically (temp file + rename) so an interrupted run never truncates a
committed trajectory. ``--smoke`` runs the mover benchmark at a reduced
size plus a small scaling sweep (the CI configuration, see
``scripts/ci.sh``); ``--profile-dir DIR`` captures a jax profiler trace
of the in-process benchmark work (the engine's named phase scopes appear
as Perfetto/TensorBoard ranges).

One process per device: the scaling sweep measures in child processes, so
it runs first, while this process has not touched a device yet; the
in-process benchmarks run after it. A failed child fails the run.
"""

from __future__ import annotations

import argparse
import sys
import traceback


def _write_json(path: str, results: dict) -> None:
    from repro.obs import atomic_write_json

    atomic_write_json(path, results)
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: mover benchmark only, small N")
    ap.add_argument("--json", default="BENCH_mover.json",
                    help="where to write the mover-strategy results")
    ap.add_argument("--scaling-json", default="BENCH_scaling.json",
                    help="where to write the engine scaling results")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax profiler trace of the in-process "
                         "benchmark work into this directory")
    args = ap.parse_args()

    from repro.launch.cache import enable_compilation_cache
    from repro.obs import tracing

    from benchmarks import bench_mover_strategies, bench_scaling

    enable_compilation_cache()           # exported to the sweep's children
    print("name,us_per_call,derived")
    if args.smoke:
        for r in bench_scaling.smoke(args.scaling_json):
            print(f"smoke_scaling/{r}", flush=True)
        with tracing.trace_session(args.profile_dir or None):
            rows, results = bench_mover_strategies.bench(n=65_536, nc=1_024,
                                                         iters=3)
        for r in rows:
            print(f"smoke_strategies/{r}", flush=True)
        results["mode"] = "smoke"
        _write_json(args.json, results)
        return

    from benchmarks import bench_hybrid_total, bench_ionization, bench_lm
    modules = [
        ("fig4_scaling", bench_scaling),
        ("fig3_hybrid_total", bench_hybrid_total),
        ("fig7_8_strategies", bench_mover_strategies),
        ("sec3_ionization", bench_ionization),
        ("lm_substrate", bench_lm),
    ]
    failed = False
    # the trace captures the in-process benchmarks; the scaling sweep runs
    # its measurements in subprocesses, which a host trace cannot see
    with tracing.trace_session(args.profile_dir or None):
        for tag, mod in modules:
            try:
                if mod is bench_mover_strategies:
                    rows, results = mod.bench()
                    results["mode"] = "full"
                    _write_json(args.json, results)
                else:
                    rows = mod.main()
                for r in rows:
                    print(f"{tag}/{r}", flush=True)
            except Exception:
                failed = True
                print(f"{tag}/ERROR,,", flush=True)
                traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
