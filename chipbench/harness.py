"""One run of one cell: set-up, the measured window, the check, the result.

Set-up builds the engine state on the device from the seed, compiles the
one step the window drives, and runs it twice through that compiled step:
the second step, which lands the first step's arrivals and births, is the
step the check compares with the reference (its state before and after is
copied to the host). The window then drives the same compiled step from
the host for ``--seconds``, as ``launch/pic_run.py`` drives it: one call
per step, no host sync until the window ends, each step's diag left on the
device. After the window the device's peak memory is read, the state is
freed, and the reference runs.

The last line of standard output is the result (``result_line``); the
numbers that decide ``correct`` are printed beside their limits as the
last lines of standard error, and under ``checks``, last in the result.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import math
import shutil
import sys
import time
from pathlib import Path

from chipbench import cells, trace_reduce, work
from chipbench.references.common import DROPS

HERE = Path(__file__).resolve().parent
# numbers compared by magnitude (signed z-scores)
SIGNED = ("_z",)


class Refused(Exception):
    """The machine cannot run this cell: no TPU, or too few chips."""


@dataclasses.dataclass
class LayerContext:
    trace: trace_reduce.Reduced
    steps: int          # steps in the traced window
    pushed: list        # particles alive at the start of each traced step
    domains: int
    peaks: dict


def log(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    tag = f"{d0.platform} {d0.device_kind} x{len(devs)}"
    if require_tpu and d0.platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {tag}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found {tag}")
    return d0, len(devs), tag


def reference_module(traffic: dict):
    return importlib.import_module(
        f"chipbench.references.{traffic['reference']}")


def window_numbers(traffic: dict, species: list, counts0: dict,
                   diag: dict, final: dict) -> tuple[int, int]:
    """Rows unaccounted for over every step of the window, and the
    number of steps with any: a step's change of
    each population against the events it reports (an ionization adds an
    electron and an ion and takes a neutral; nothing else creates or
    destroys a particle here), anything dropped or refused, and the last
    step's counts against the state the window left."""
    import numpy as np

    ion = traffic.get("ionization") or None
    sign = {s: 0 for s in species}
    if ion:
        sign.update({ion["electron"]: 1, ion["ion"]: 1, ion["neutral"]: -1})
    n_ion = (diag["n_ionized"] if ion
             else np.zeros(len(diag[f"{species[0]}/count"]), np.int64))
    per_step = np.zeros(len(n_ion), np.int64)
    prev = dict(counts0)
    for k in range(len(n_ion)):
        for s in species:
            now = int(diag[f"{s}/count"][k])
            per_step[k] += abs(now - prev[s] - sign[s] * int(n_ion[k]))
            prev[s] = now
    for key, val in diag.items():
        if key.endswith(DROPS):
            per_step += np.asarray(val).reshape(len(n_ion), -1).sum(axis=1)
    per_step[-1] += sum(abs(prev[s] - final[s]) for s in species)
    return int(per_step.sum()), int(np.count_nonzero(per_step))


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit (a z-score by its magnitude). A
    number without a limit, or a limit without a number, is not correct."""
    checks = {}
    ok = set(numbers) == set(limits)
    for name in sorted(set(numbers) | set(limits)):
        value, lim = numbers.get(name), limits.get(name)
        checks[name] = {"value": value, "limit": lim}
        if value is None or lim is None:
            continue
        mag = abs(value) if name.endswith(SIGNED) else value
        ok &= bool(math.isfinite(mag) and mag <= lim)
    return ok, checks


def bf16_control(ref, phys, before: dict, key):
    """The control: the reference, in bfloat16, in the program's place."""
    import jax.numpy as jnp

    return ref.step(phys, before, key, jnp.bfloat16)


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True,
             control=None, wrap_step=None,
             trace_dir: Path | None = None) -> dict:
    """Run ``cell`` once and return the result object.

    ``control(ref, phys, before, key) -> (after, diag)`` puts its step in
    the program's place for the checked step (``bf16_control``, or a
    reference with a fault planted in it). ``wrap_step`` wraps the
    compiled step, to plant a fault in the path the window drives; neither
    is used by a benchmark run."""
    import jax
    import numpy as np

    from chipbench import system

    d0, n_dev, tag = device_info(cell.chips, require_tpu)
    eng = system.Engine(cell.config, cell.traffic)
    ref = reference_module(cell.traffic)
    log(tag, f"{cell.name}: seed={seed} domains={eng.domains} "
             f"max_births={eng.ecfg.max_births} "
             f"max_migration={eng.ecfg.max_migration}")

    # ---- set-up --------------------------------------------------------
    state = eng.init(seed)
    compiled = eng.compile(state)
    step = wrap_step(compiled) if wrap_step else compiled
    state, _ = step(state)
    jax.block_until_ready(state)
    before = eng.snapshot(state)
    t = time.perf_counter()
    state, d2 = step(state)
    jax.block_until_ready(state)
    t_step = time.perf_counter() - t
    after = eng.snapshot(state)
    d_check = {k: np.asarray(v) for k, v in jax.device_get(d2).items()}
    counts0 = {s: int(d_check[f"{s}/count"]) for s in eng.species}
    n_steps = max(1, math.ceil(seconds / max(t_step, 1e-6)))
    setup_s = time.perf_counter() - t_start
    log(tag, f"set-up {setup_s:.3f} s; checked step {t_step * 1e3:.1f} ms; "
             f"window of {n_steps} steps")

    # ---- the window ----------------------------------------------------
    diags = []
    tdir = None
    if trace:
        tdir = trace_dir or (HERE.parent / ".chipbench" / "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    t0 = time.perf_counter()
    if trace:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for i in range(n_steps):
                with jax.profiler.StepTraceAnnotation("chipbench/step",
                                                      step_num=i):
                    state, d = step(state)
                diags.append(d)
            with jax.profiler.TraceAnnotation("chipbench/block"):
                jax.block_until_ready((state, diags))
    else:
        for _ in range(n_steps):
            state, d = step(state)
            diags.append(d)
        jax.block_until_ready((state, diags))
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dv in eng.devices)
    final = eng.counts(state)
    diag = system.host_diag(diags)
    scopes = system.hlo_scopes(compiled) if trace else {}
    del state, diags, compiled, step
    log(tag, f"window {window_s:.3f} s, {n_steps} steps; "
             f"peak_bytes_in_use {peak}")

    # ---- the check (after the window; the program's state is freed) ----
    from chipbench.references.common import component_major, phys_of

    phys = phys_of(cell.config, cell.traffic, eng.ecfg.max_births)
    before = component_major(before)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    if control:
        after, d_check = control(ref, phys, before, key)
    else:
        after = component_major(after)
    t = time.perf_counter()
    # the reference's own draws (kept apart from the control's)
    numbers = ref.compare(phys, before, after, d_check,
                          jax.random.fold_in(key, 1))
    numbers["window_lost_rows"], bad_steps = window_numbers(
        cell.traffic, eng.species, counts0, diag, final)
    correct, checks = judge(numbers, cell.traffic["limits"])
    log(tag, f"reference {time.perf_counter() - t:.3f} s")
    del before, after

    # ---- the result ----------------------------------------------------
    pushed = [sum(counts0.values())] + [
        sum(int(diag[f"{s}/count"][k]) for s in eng.species)
        for k in range(n_steps - 1)]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": n_dev, "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": n_steps,
              "failed": bad_steps}
    if not trace:
        values = {"step_ms": window_s / n_steps * 1e3,
                  "pushes_per_s": sum(pushed) / window_s / 1e6,
                  "hbm_peak_gb": peak / 1e9, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    else:
        path = next(tdir.glob("plugins/profile/*/*.xplane.pb"))
        devices, host = trace_reduce.load(str(path))
        red = trace_reduce.reduce(devices, host, scopes)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = LayerContext(trace=red, steps=n_steps, pushed=pushed,
                           domains=eng.domains,
                           peaks=work.chip_peaks(d0.device_kind))
        unscoped = red.scope_sum(["unscoped"])
        total = red.mean({d: sum(v.values())
                          for d, v in red.scope_ns.items()})
        log(tag, f"unscoped device time {unscoped / 1e6:.3f} ms of "
                 f"{total / 1e6:.3f} ms "
                 f"({100 * unscoped / max(total, 1):.2f}%)")
        metrics = {}
        for m in cell.per_layer:
            mod = importlib.import_module(
                f"chipbench.layer_metrics.{m['name']}")
            v = mod.compute(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.mean(red.busy_ns) / 1e9
        device["window_s"] = red.window_ns / 1e9
        result["breakdown"] = {
            "device_ops": [[k, v / 1e9] for k, v in red.top_ops],
            "idle_gaps": [[k, v / 1e9] for k, v in red.gaps]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        log(tag, f"check {name} = {c['value']} (limit {c['limit']})")
    return result


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once (see BENCHMARK.json).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: the bfloat16 reference replaces the program "
                         "in the checked step (must come out not correct)")
    ap.add_argument("--fault", default=None,
                    help="a fault of chipbench/tests/faults.py planted in "
                         "the timed path (must come out not correct)")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the --trace 1 profile in")
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    control = bf16_control if args.control else None
    wrap = None
    if args.fault:
        from chipbench.tests import faults

        kind, fault = faults.FAULTS[args.fault]
        if kind == "step":
            mm = cell.traffic["max_migration"]
            wrap = lambda step: fault(step, mm)  # noqa: E731
        else:
            control = fault
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start, control=control, wrap_step=wrap,
                          trace_dir=(Path(args.keep_trace)
                                     if args.keep_trace else None))
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0
