"""Share of the mover's roofline: the least time one chip needs to read
and write the position and velocity of its alive particles at peak HBM
bandwidth (``work.push_floor_s``, bandwidth-bound), over ``push_ms``."""

from chipbench import work
from chipbench.layer_metrics.push_ms import compute as push_ms

UNIT = "%"


def compute(ctx):
    ms = push_ms(ctx)
    if ms is None:
        return None
    per_chip = sum(ctx.pushed) / len(ctx.pushed) / ctx.domains
    return 100.0 * work.push_floor_s(per_chip, ctx.peaks) * 1e3 / ms
