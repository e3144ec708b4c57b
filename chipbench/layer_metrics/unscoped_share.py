"""Share of the window's device time that no scope names: self time of the
ops whose op_name lies under no ``engine/`` or ``halo/`` scope (the
compiler's own ops, and fusions whose root lost its op_name), over all
self time, mean over the cell's chips. No other per-layer metric can see
this time."""

UNIT = "%"


def compute(ctx):
    shares = [100.0 * per.get("unscoped", 0.0) / sum(per.values())
              for per in ctx.trace.scope_ns.values() if sum(per.values())]
    return sum(shares) / len(shares) if shares else None
