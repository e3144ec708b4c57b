"""Trace annotations for the engine: the paper's Nsight ranges, in JAX.

Two kinds of range, matching the two places time is spent:

* ``phase_scope(name)`` — used INSIDE traced code (the shard-mapped engine
  step). Wraps ``jax.named_scope``: zero runtime cost (the name is attached
  to the lowered ops' metadata at trace time), and the scope shows up in
  Perfetto/TensorBoard device timelines exactly where Nsight would show the
  paper's ``nvtxRangePush`` phase ranges. Engine phases use ``engine/<phase>``
  names, per-queue pipeline stages ``engine/<phase>/q<k>``, halo/field
  collectives ``halo/<op>``.
* ``host_span(name)`` — used in HOST code (step loops, probes, benchmark
  harnesses). Wraps ``jax.profiler.TraceAnnotation``, which emits a range on
  the host track of a captured trace.

``trace_session(profile_dir)`` brackets a run with
``jax.profiler.start_trace`` / ``stop_trace`` — the capture behind
``pic_run --profile-dir`` and ``benchmarks.run --profile-dir``; open the
resulting ``plugins/profile/*`` in TensorBoard or the ``*.trace.json.gz``
in Perfetto (ui.perfetto.dev).

Testing hooks: ``capture_scopes()`` records every ``phase_scope`` entered
while tracing (the cheap, implementation-independent pin), and
``jaxpr_scope_names`` walks a closed jaxpr's equations collecting their
``named_scope`` name stacks — the structural proof that the annotations
survive into the lowered computation.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator

import jax

# test hook: when a capture list is installed, phase_scope records every
# name it enters (at trace time — the scopes are trace-time constructs)
_capture: list[str] | None = None


@contextlib.contextmanager
def capture_scopes() -> Iterator[list[str]]:
    """Record the names of every ``phase_scope`` entered in the block.

    Tracing a jitted function inside the block (e.g. via ``jax.make_jaxpr``
    or a first call) captures the scopes its trace enters — the test-side
    pin that the engine actually annotates its phases.
    """
    global _capture
    prev, _capture = _capture, []
    try:
        yield _capture
    finally:
        _capture = prev


@contextlib.contextmanager
def phase_scope(name: str) -> Iterator[None]:
    """``jax.named_scope`` + capture hook: annotate a traced region.

    Safe anywhere: under jit/shard_map tracing it tags the emitted ops (no
    runtime cost); in eager host code it is effectively a no-op.
    """
    if _capture is not None:
        _capture.append(name)
    with jax.named_scope(name):
        yield


def host_span(name: str):
    """A host-side profiler range (``jax.profiler.TraceAnnotation``).

    Use around host work — a step-loop iteration, a perf probe — so the
    captured trace shows where host time went between device launches.
    """
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def trace_session(profile_dir: str | None) -> Iterator[None]:
    """Capture a profiler trace of the block into ``profile_dir``.

    ``None`` disables capture (the block runs untraced) so call sites can
    thread an optional ``--profile-dir`` straight through. The directory is
    created if missing; view with TensorBoard's profile plugin or Perfetto.
    """
    if not profile_dir:
        yield
        return
    os.makedirs(profile_dir, exist_ok=True)
    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _jaxpr_of(obj):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(obj, ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, Jaxpr):
        return obj
    return None


def jaxpr_scope_names(closed_jaxpr) -> set[str]:
    """Every ``named_scope`` name stack found on the jaxpr's equations.

    Walks sub-jaxprs (jit/shard_map/cond/scan bodies) recursively; an
    equation traced under ``phase_scope("engine/push")`` contributes a
    name-stack string containing ``engine/push``. Used by the tests to pin
    that the annotations survive into the computation the engine actually
    runs.
    """
    names: set[str] = set()
    seen: set[int] = set()

    def walk(jaxpr):
        if jaxpr is None or id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            stack = getattr(eqn.source_info, "name_stack", None)
            if stack is not None:
                s = str(stack)
                if s:
                    names.add(s)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    walk(_jaxpr_of(sub))

    walk(_jaxpr_of(closed_jaxpr) or getattr(closed_jaxpr, "jaxpr", None))
    return names
