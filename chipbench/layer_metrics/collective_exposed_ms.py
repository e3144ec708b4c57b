"""Collective time that no other op hides: the intervals in which a
collective (its in-flight asynchronous interval or its synchronous op)
runs and no other op does, on the worst chip, per step."""

UNIT = "ms/step"


def compute(ctx):
    ns = max(ctx.trace.exposed_ns.values(), default=0.0)
    return ns / ctx.steps / 1e6 if ctx.trace.exposed_ns else None
