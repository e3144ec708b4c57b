"""One module per per-layer metric, found by the metric's name in
``BENCHMARK.json``. Each has ``compute(ctx) -> float | None``: ``ctx`` is a
``harness.LayerContext`` (the reduced trace of the window, the steps in
it, the particles pushed, the chip's peaks). A reader that finds nothing
to read returns None, and the metric is left out of the result."""


def ms_per_step(ctx, prefixes) -> float | None:
    ns = ctx.trace.scope_sum(prefixes)
    return ns / ctx.steps / 1e6 if ns > 0 else None
