"""Device time of the mover's field gather: E at the CIC nodes of each
particle (``engine/push/q<k>/field_gather``, a part of ``push_ms``), mean
over the cell's chips, per step."""

import re

UNIT = "ms/step"

_SCOPE = re.compile(r"engine/push/q\d+/field_gather(?:/|$)")


def compute(ctx):
    t = ctx.trace
    ns = sum(v for per in t.scope_ns.values() for sc, v in per.items()
             if _SCOPE.match(sc)) / max(len(t.scope_ns), 1)
    return ns / ctx.steps / 1e6 if ns > 0 else None
