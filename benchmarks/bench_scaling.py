"""Paper Tables 2-4 / Fig. 4 analogue: engine scaling with domain count.

The paper scales BIT1's optimized mover to 400 GPUs and reports per-phase
Nsight times, speedup and parallel efficiency PE = T1/(D*TD). Here the
asynchronous multi-device engine (``repro.distributed``) runs on D emulated
host devices in subprocesses, and ``perf.phase_breakdown`` produces the
per-phase table per domain count (see ``docs/benchmarks.md`` for the JSON
schema); per-queue occupancy and skew from ``perf.queue_stats`` record the
load-balance state the ``rebalance_every`` knob bounds. Speedup/PE land in
the machine-readable ``BENCH_scaling.json`` (the container exposes two
physical cores, so this measures harness overhead/correctness, not parallel
speedup — the JSON records the environment so the numbers are never
mistaken for the paper's).

The scenarios:

* ``transport`` — migration + halo field solve, no MC sources (the pure
  queue-pipeline workload);
* ``ionization`` — the paper's §3.3 BIT1 test: MC ionization on the queue
  pipeline through the free-slot ring, field solve off (as the paper's
  test runs it). This is the MC-source workload the ring-aware merge
  exists for;
* ``collisions`` — the binary-collision menu (elastic + charge exchange +
  Coulomb) on the per-cell substrate, ionization off: isolates the
  ``collide`` phase, run with ``cell_order=True`` so the rebalance
  exercises the BIT1-style counting sort by cell;
* ``checkpoint`` — checkpoint overhead on the full-churn resilience
  workload (``make_resilience_config``): median step wall with the async
  EngineState checkpoint every other step vs the same loop without it,
  plus the checkpoint payload size and the synchronous device-to-host
  fetch time (the only part the step loop pays — the npz write is on the
  writer thread). Its per-domain record is
  ``{total, baseline_total, overhead_frac, ckpt_bytes, ckpt_fetch_us}``
  rather than a phase table (``scripts/check_perf.py`` knows both);
* ``ensemble`` — the vmapped ensemble engine (``repro.serve``) sweeping
  the member width on ONE device: W parameter points per compiled step,
  every member at a different dt. Its per-domain record (keyed by WIDTH,
  not domain count) is ``{total, width, members_per_sec, compiles}``;
  ``compiles`` must be exactly 1 — the compile-once serving contract is
  part of the perf gate.

    PYTHONPATH=src python -m benchmarks.bench_scaling [--smoke] \
        [--scenario transport|ionization|collisions|checkpoint|ensemble|all]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCENARIOS = ("transport", "ionization", "collisions", "checkpoint",
             "ensemble")

_PROG = """
import json
from repro.configs.pic_bit1 import (make_bench_config, make_collision_config,
                                    make_engine_config)
from repro.distributed import engine, perf
from repro.launch.mesh import make_debug_mesh
import dataclasses

p = json.loads(%r)
mesh = make_debug_mesh(data=p["d"], model=1)
if p["scenario"] == "collisions":
    # the binary-collision menu on the per-cell substrate, ionization off:
    # isolates the collide phase; cell_order exercises the counting sort
    cfg = make_collision_config(nc=p["nc"], n=p["n"], strategy="fused")
else:
    cfg = make_bench_config(nc=p["nc"], n=p["n"], strategy="fused")
if p["scenario"] == "transport":
    # enable the halo field phase so the 'field' row measures the
    # distributed solve, and drop the MC source to isolate the transport
    # pipeline (migration + merge through the free-slot ring)
    cfg = dataclasses.replace(cfg, field_solve=True, ionization=None)
# 'ionization' keeps the paper's section-3.3 setting: MC ionization on the
# async queue pipeline (ring-claimed births), field solver disabled
# collisions default to a periodic rebalance so the cell_order counting
# sort actually runs inside the measured steps
reb = p["rebalance_every"] or (4 if p["scenario"] == "collisions" else 0)
ecfg = make_engine_config(cfg, max_migration=p["m"], async_n=p["async_n"],
                          max_births=p["max_births"],
                          rebalance_every=reb,
                          cell_order=(p["scenario"] == "collisions"))
probe = perf.phase_breakdown(ecfg, mesh, iters=p["iters"], warmup=1)
queues = perf.queue_stats(ecfg, mesh, steps=3)
print("RESULTJSON " + json.dumps({
    "probe": probe, "queues": queues,
    "engine": {"rebalance_every": ecfg.rebalance_every,
               "cell_order": ecfg.cell_order}}))
"""


_CKPT_PROG = """
import json, tempfile, time
import jax
import numpy as np
from repro.configs.pic_bit1 import make_engine_config, make_resilience_config
from repro.distributed import engine
from repro.ckpt.checkpoint import Checkpointer
from repro.launch.mesh import make_debug_mesh
from repro.runtime import resilience

p = json.loads(%r)
mesh = make_debug_mesh(data=p["d"], model=1)
cfg = make_resilience_config(nc=p["nc"], n=p["n"])
ecfg = make_engine_config(cfg, max_migration=p["m"], async_n=p["async_n"],
                          max_births=p["max_births"])
step = engine.make_engine_step(ecfg, mesh)

def timed(ckpt_every, ckpt):
    state = engine.init_engine_state(ecfg, mesh, 0)
    state, diag = step(state)              # compile outside the timing
    jax.block_until_ready(diag)
    walls, info = [], None
    for i in range(p["iters"]):
        t0 = time.perf_counter()
        state, diag = step(state)
        if ckpt is not None and (i + 1) %% ckpt_every == 0:
            info = resilience.save_engine(ckpt, ecfg, mesh, i + 1, state)
        jax.block_until_ready(diag)
        walls.append((time.perf_counter() - t0) * 1e6)
    if ckpt is not None:
        ckpt.wait()
    return float(np.median(walls)), info

base, _ = timed(0, None)
with tempfile.TemporaryDirectory() as tmp:
    tot, info = timed(p["ckpt_every"], Checkpointer(tmp))
print("RESULTJSON " + json.dumps({
    "total": tot, "baseline_total": base,
    "overhead_frac": max(tot - base, 0.0) / base,
    "ckpt_bytes": info["bytes"], "ckpt_fetch_us": info["fetch_us"],
    "ckpt_every": p["ckpt_every"]}))
"""


_ENS_PROG = """
import json, time
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs.pic_bit1 import make_resilience_config
from repro.core.params import runtime_params
from repro.serve import ensemble

p = json.loads(%r)
cfg = make_resilience_config(nc=p["nc"], n=p["n"])
cfg = dataclasses.replace(cfg, b_field=(0.0, 0.0, 0.02))
w = p["width"]
es = ensemble.init_ensemble(cfg, w)
mk = ensemble.make_member_init(cfg)
ins = ensemble.make_member_insert(cfg)
for slot in range(w):
    # every member at its OWN parameter point: the timing (and the
    # compiles=1 pin) covers the heterogeneous case the engine exists for
    rp = runtime_params(cfg, dt=0.3 + 0.05 * slot,
                        ionization_rate=1e-3 * (slot + 1))
    es = ins(es, mk(jnp.int32(slot)), rp, jnp.int32(slot))
step = ensemble.make_ensemble_step(cfg)
es, diag = step(es)              # compile outside the timing
jax.block_until_ready(diag)
walls = []
for _ in range(p["iters"]):
    t0 = time.perf_counter()
    es, diag = step(es)
    jax.block_until_ready(diag)
    walls.append((time.perf_counter() - t0) * 1e6)
tot = float(np.median(walls))
print("RESULTJSON " + json.dumps({
    "total": tot, "width": w, "members_per_sec": w / (tot / 1e6),
    "compiles": step._cache_size()}))
"""


def _measure(d: int, *, nc: int, n: int, async_n: int, iters: int,
             max_migration: int, rebalance_every: int, scenario: str,
             max_births: int, ckpt_every: int = 2) -> dict:
    """Run one measurement in a child process (the only process of the
    sweep that touches a device); a child that prints no result fails the
    sweep."""
    params = json.dumps(dict(d=d, nc=nc, n=n, async_n=async_n, iters=iters,
                             m=max_migration, rebalance_every=rebalance_every,
                             scenario=scenario, max_births=max_births,
                             ckpt_every=ckpt_every, width=d))
    prog = {"checkpoint": _CKPT_PROG,
            "ensemble": _ENS_PROG}.get(scenario, _PROG)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    # the ensemble scenario is single-device by construction (d is a WIDTH)
    nd = 1 if scenario == "ensemble" else d
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={nd}"
    out = subprocess.run([sys.executable, "-c", prog % params], env=env,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RESULTJSON "):
            return json.loads(line[len("RESULTJSON "):])
    raise RuntimeError(
        f"scenario={scenario} domains={d}: the child exited "
        f"{out.returncode} with no result:\n{out.stderr[-2000:]}")


def _sweep_checkpoint(domains, *, nc: int, n: int, async_n: int, iters: int,
                      max_migration: int, max_births: int,
                      ckpt_every: int = 2) -> tuple[list[str], dict]:
    """The checkpoint-overhead sweep (its own record shape — no phases)."""
    per_domain = {
        d: _measure(d, nc=nc, n=n, async_n=async_n, iters=iters,
                    max_migration=max_migration, rebalance_every=0,
                    scenario="checkpoint", max_births=max_births,
                    ckpt_every=ckpt_every)
        for d in domains}
    payload = {
        "async_n": async_n, "ckpt_every": ckpt_every,
        "config": {"nc": nc, "n_per_species": n, "iters": iters,
                   "max_migration": max_migration,
                   "max_births": max_births},
        "domains": {str(d): per_domain[d] for d in per_domain},
    }
    rows = [f"engine_ckpt;domains={d};async_n={async_n},"
            f"{m['total']:.1f},overhead={m['overhead_frac']:.3f};"
            f"bytes={m['ckpt_bytes']}"
            for d, m in sorted(per_domain.items())]
    return rows, payload


def _sweep_ensemble(widths, *, nc: int, n: int,
                    iters: int) -> tuple[list[str], dict]:
    """The ensemble-width sweep (single device; ``domains`` keys are member
    widths). Each width measures W heterogeneous parameter points through
    ONE compiled vmapped step on the full-churn resilience workload."""
    per_width = {
        w: _measure(w, nc=nc, n=n, async_n=1, iters=iters, max_migration=0,
                    rebalance_every=0, scenario="ensemble", max_births=0)
        for w in widths}
    payload = {
        "config": {"nc": nc, "n_per_species": n, "iters": iters},
        "domains": {str(w): per_width[w] for w in per_width},
    }
    rows = [f"ensemble_step;width={w},{m['total']:.1f},"
            f"members_per_sec={m['members_per_sec']:.1f};"
            f"compiles={m['compiles']}"
            for w, m in sorted(per_width.items())]
    return rows, payload


def sweep(domains=(1, 2, 4, 8), *, nc: int = 4096, n: int = 131_072,
          async_n: int = 2, iters: int = 5, max_migration: int = 8192,
          rebalance_every: int = 0, scenario: str = "transport",
          max_births: int = 8192) -> tuple[list[str], dict]:
    """One scenario's domain sweep. Returns (CSV rows, scenario payload)."""
    from repro.distributed import perf

    if scenario not in SCENARIOS:
        raise ValueError(f"scenario must be one of {SCENARIOS}")
    if scenario == "checkpoint":
        return _sweep_checkpoint(domains, nc=nc, n=n, async_n=async_n,
                                 iters=iters, max_migration=max_migration,
                                 max_births=max_births)
    if scenario == "ensemble":
        # the sweep axis is the member WIDTH, not a device count; keep the
        # per-member population CI-sized (the vmapped step does W x the work
        # of one domain on a single device)
        return _sweep_ensemble(domains, nc=nc, n=min(n, 16_384),
                               iters=iters)
    per_domain, per_domain_queues = {}, {}
    for d in domains:
        res = _measure(d, nc=nc, n=n, async_n=async_n, iters=iters,
                       max_migration=max_migration,
                       rebalance_every=rebalance_every, scenario=scenario,
                       max_births=max_births)
        per_domain[d] = res["probe"]
        per_domain_queues[d] = res["queues"]
        engine_knobs = res["engine"]
    metrics = perf.scaling_metrics(per_domain)
    payload = {
        "async_n": async_n,
        # the EFFECTIVE engine knobs the subprocess ran with (the
        # collisions scenario defaults to a periodic cell-order rebalance
        # when none was requested — the JSON must record what ran)
        "rebalance_every": engine_knobs["rebalance_every"],
        "cell_order": engine_knobs["cell_order"],
        "config": {"nc": nc, "n_per_species": n, "iters": iters,
                   "max_migration": max_migration,
                   "max_births": max_births},
        "domains": {
            str(d): {**metrics[d], "queues": per_domain_queues[d]}
            for d in metrics},
    }
    rows = []
    for d in sorted(metrics):
        m = metrics[d]
        rows.append(
            f"engine_step/{scenario};domains={d};async_n={async_n},"
            f"{m['total']:.1f},"
            f"speedup={m['speedup']:.2f};pe="
            f"{m['parallel_efficiency']:.2f}")
    return rows, payload


def run(domains=(1, 2, 4, 8), *, json_path: str = "BENCH_scaling.json",
        mode: str = "full", scenario: str = "all", **kw) -> list[str]:
    """Run the requested scenario sweep(s) and write one JSON artifact."""
    from repro.distributed import perf

    names = SCENARIOS if scenario in ("all", "both") else (scenario,)
    rows, scenarios = [], {}
    for name in names:
        r, payload = sweep(domains, scenario=name, **kw)
        rows += r
        scenarios[name] = payload
    perf.write_scaling_json(json_path, {
        "mode": mode,
        "environment": "emulated host devices, 2-core CPU container "
                       "(harness overhead, not hardware scaling)",
        "scenarios": scenarios,
    })
    return rows


def smoke(json_path: str = "BENCH_scaling.json",
          scenario: str = "all") -> list[str]:
    """CI-sized scaling sweep at the acceptance point: small grid,
    D in {1, 2, 4}, async_n=4 — by default all five scenarios:
    transport, the §3.3 MC-ionization workload (the ring-routed source),
    the binary-collision menu on the per-cell substrate, the
    checkpoint-overhead probe on the resilience workload, and the
    ensemble-width sweep of the vmapped serving engine (the same
    (1, 2, 4) tuple read as member widths). 5 timing
    iters per probe: at 2 the cumulative differencing was dominated by
    recompile/host noise (the committed breakdown once reported a merge
    phase larger than the total). The single definition of the CI smoke
    point: the CLI ``--smoke`` flag and ``benchmarks.run --smoke`` both
    land here."""
    return run((1, 2, 4), nc=512, n=16_384, async_n=4, iters=5,
               max_migration=2048, max_births=2048, json_path=json_path,
               mode="smoke", scenario=scenario)


def main() -> list[str]:
    return run()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sweep (D in {1,2,4}, all scenarios)")
    ap.add_argument("--scenario", default="all",
                    choices=SCENARIOS + ("all", "both"))
    ap.add_argument("--json", default="BENCH_scaling.json")
    args = ap.parse_args()
    if args.smoke:
        out = smoke(args.json, args.scenario)
    else:
        out = run(json_path=args.json, scenario=args.scenario)
    print("\n".join(out))
