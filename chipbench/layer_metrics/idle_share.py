"""Share of the traced window in which no op runs, on the worst chip."""

UNIT = "%"


def compute(ctx):
    t = ctx.trace
    if not t.busy_ns or t.window_ns <= 0:
        return None
    return 100.0 * (1.0 - min(t.busy_ns.values()) / t.window_ns)
