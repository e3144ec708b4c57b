"""Resilient engine run loop: async checkpoints, restart, elastic restore.

The training-shaped restart contract of ``fault_tolerance.run_training``
ported to the async PIC engine (the follow-on resilience paper's §"fault
tolerance at scale" path):

* ``run_engine`` drives ``engine.make_engine_step`` with a
  ``FailureInjector`` fence at the top of every step and an **asynchronous**
  checkpoint of the full ``EngineState`` every ``ckpt_every`` steps — the
  step loop pays only the device-to-host fetch; the npz/manifest write
  happens on the checkpointer's writer thread. The synchronous cost shows
  up in the metrics stream as ``ckpt/bytes``/``ckpt/fetch_us`` (and the
  off-thread ``ckpt/write_us``), so checkpoint overhead is a first-class
  observable.
* ``resume_engine`` restores the newest complete checkpoint. Same device
  count as the save -> a bitwise typed restore (every leaf, including the
  per-domain RNG keys and free-slot rings, is reproduced exactly — the
  resumed trajectory is bit-identical to the uninterrupted one, pinned in
  tests/test_resilience.py). Different device count -> the elastic path:
  ``engine.resplit_host`` + ``engine.elastic_state`` (deterministic and
  exactly conservative, but a re-seeded RNG stream; see docs/resilience.md).

Checkpoints are labeled with the *next* step to run (save after step k is
labeled k+1), and ``EngineState.pic.step`` carries the same value, so
``run_engine`` resumes from ``state.pic.step`` with no external counter.
"""

from __future__ import annotations

import signal
import time
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh
from jax.tree_util import tree_flatten_with_path

from repro.ckpt.checkpoint import Checkpointer, _path_str
from repro.distributed import engine
from repro.obs.metrics import MetricsStream
from repro.runtime.fault_tolerance import FailureInjector


def save_engine(ckpt: Checkpointer, ecfg: engine.EngineConfig, mesh: Mesh,
                step: int, state: engine.EngineState,
                blocking: bool = False) -> dict:
    """Checkpoint an EngineState; the manifest records the engine layout
    so ``resume_engine`` can decide typed-vs-elastic without a config
    side-channel. Returns the ``Checkpointer.save`` info dict."""
    meta = {"kind": "engine", "domains": ecfg.num_domains(mesh),
            "async_n": ecfg.async_n, "nc": ecfg.pic.nc, "step": int(step)}
    return ckpt.save(step, state, blocking=blocking, meta=meta)


def _stored_matches(flat: dict, like: Any) -> bool:
    """True when the stored leaves match ``like`` key-for-key and
    shape-for-shape — the precondition for a bitwise typed restore."""
    leaves, _ = tree_flatten_with_path(like)
    want = {_path_str(kp): tuple(ref.shape) for kp, ref in leaves}
    return (set(want) == set(flat)
            and all(want[k] == flat[k].shape for k in want))


def resume_engine(ecfg: engine.EngineConfig, mesh: Mesh, ckpt: Checkpointer,
                  step: int | None = None
                  ) -> tuple[int, engine.EngineState]:
    """Restore the newest complete engine checkpoint onto ``mesh``.

    Bitwise when the stored layout matches the current config/mesh
    (same D, async_n, budgets); otherwise the elastic re-split path.
    """
    step, flat, manifest = ckpt.restore_flat(step)
    meta = manifest.get("meta", {}) or {}
    if "pic/key" not in flat:
        raise ValueError(
            f"checkpoint step {step} in {ckpt.dir} is not an engine "
            f"checkpoint (kind={meta.get('kind')!r})")
    like = engine.state_shape(ecfg, mesh)
    if _stored_matches(flat, like):
        _, state = ckpt.restore(step, like=like,
                                shardings=engine.state_shardings(ecfg, mesh))
        return step, state
    d_old = int(meta.get("domains") or flat["pic/key"].shape[0])
    species, counts = engine.resplit_host(ecfg, mesh, flat, d_old=d_old)
    state = engine.elastic_state(ecfg, mesh, species, counts,
                                 flat["pic/key"][0],
                                 step=int(flat["pic/step"]))
    return step, state


def run_engine(ecfg: engine.EngineConfig, mesh: Mesh,
               state: engine.EngineState, *, num_steps: int,
               ckpt: Checkpointer | None = None, ckpt_every: int = 0,
               injector: FailureInjector | None = None,
               stream: MetricsStream | None = None,
               step_fn: Any = None, collect: bool = True,
               handle_sigterm: bool = True
               ) -> tuple[engine.EngineState, list[dict]]:
    """Drive engine steps from ``state.pic.step`` to ``num_steps`` with
    periodic async checkpoints; raises ``SimulatedFailure`` at the
    injector's fence AFTER any due checkpoint (a crash between fences).

    SIGTERM (the preemption signal cluster schedulers send before a kill)
    is handled cooperatively when ``handle_sigterm``: the handler only sets
    a flag, the loop notices it at the next step boundary, stops, and — if
    a checkpointer is attached — writes one final BLOCKING checkpoint
    labeled with the next step to run, so ``resume_engine`` restarts the
    preempted run bitwise. The previous handler is restored on exit, and
    installation is skipped off the main thread (``signal.signal`` raises
    there).

    Returns ``(state, diags)`` — one (host) diag dict per executed step
    when ``collect`` (the bitwise-restart tests compare these too).
    """
    if step_fn is None:
        step_fn = engine.make_engine_step(ecfg, mesh)
    start = int(np.asarray(jax.device_get(state.pic.step)))
    diags: list[dict] = []
    stop = {"seen": False}
    prev_handler: Any = None
    installed = False
    if handle_sigterm:
        def _on_term(signum, frame):
            stop["seen"] = True

        try:
            prev_handler = signal.signal(signal.SIGTERM, _on_term)
            installed = True
        except ValueError:  # not the main thread; run unprotected
            pass
    done_through = start  # steps completed; label of the next step to run
    last_saved = None
    try:
        for step in range(start, num_steps):
            if stop["seen"]:
                break
            if injector is not None:
                injector.check(step)
            t0 = time.perf_counter()
            state, diag = step_fn(state)
            done_through = step + 1
            extra = None
            if ckpt is not None and ckpt_every > 0 \
                    and (step + 1) % ckpt_every == 0:
                info = save_engine(ckpt, ecfg, mesh, step + 1, state)
                last_saved = step + 1
                extra = {"ckpt/bytes": float(info["bytes"]),
                         "ckpt/fetch_us": float(info["fetch_us"]),
                         "ckpt/write_us": float(ckpt.last_write_us)}
            wall_us = (time.perf_counter() - t0) * 1e6
            if collect:
                diag = {k: np.asarray(v) for k, v in diag.items()}
                diags.append(diag)
            if stream is not None:
                stream.record(diag, wall_us=wall_us, step=step, extra=extra)
        if stop["seen"] and ckpt is not None and last_saved != done_through:
            save_engine(ckpt, ecfg, mesh, done_through, state, blocking=True)
    finally:
        if installed:
            signal.signal(signal.SIGTERM, prev_handler)
        # flush the in-flight write even when the injector fence fires: the
        # drill simulates a crash *between* fences, after durable I/O — the
        # truly-torn-write case is covered by the Checkpointer's
        # manifest-last protocol (tests/test_resilience.py)
        if ckpt is not None:
            ckpt.wait()
    return state, diags
