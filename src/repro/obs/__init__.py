"""Engine observability: tracing, structured metrics, online auto-tuning.

The source paper's scaling argument is built on per-phase Nsight timelines
(mover, migration, merge, field) and its companion paper (arXiv:2306.16512)
makes profiling the method itself. This package is that layer for the JAX
engine:

* ``tracing``  — ``jax.named_scope`` phase/stage/collective annotations
  threaded through ``distributed/engine.py`` and ``distributed/halo.py``
  (the Nsight-range analogue: the names land in the XLA op metadata and
  show up in Perfetto/TensorBoard traces), ``TraceAnnotation`` host spans,
  and ``trace_session`` capture around a run
  (``pic_run --profile-dir``);
* ``metrics``  — a structured per-step metrics stream (JSONL run report +
  in-memory ring) collecting what the engine already computes but used to
  drop: queue occupancy/skew, migration/birth/emission overflows,
  free-slot-ring occupancy, in-flight pending rows, host wall time per
  step. Enabled by ``EngineConfig.metrics`` (diagnostics-only: the engine
  state is bitwise identical with the toggle on or off);
* ``autotune`` — an online controller that consumes the metrics stream
  between steps and retunes ``async_n`` / ``max_migration`` /
  ``max_births`` / ``rebalance_every`` / ``rebalance_skew`` from the
  measured times and skew (imported lazily — ``repro.obs.autotune`` — so
  the engine can depend on the tracing/metrics layers without a cycle).

``docs/observability.md`` documents the schema, the tuner policy and how
to read a Perfetto trace of one async(n) step.
"""

from repro.obs.metrics import (MetricsStream, StepMetrics, atomic_write_json,
                               read_jsonl, validate_record)
from repro.obs.tracing import (capture_scopes, host_span, jaxpr_scope_names,
                               phase_scope, trace_session)

__all__ = [
    "MetricsStream", "StepMetrics", "atomic_write_json", "read_jsonl",
    "validate_record", "capture_scopes", "host_span", "jaxpr_scope_names",
    "phase_scope", "trace_session",
]
