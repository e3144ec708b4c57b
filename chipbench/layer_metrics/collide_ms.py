"""Device time of the binary-collision phase: the cell densities
(``engine/collide_setup``) and the per-queue menu (``engine/collide/q<k>``),
mean over the cell's chips, per step."""

from chipbench.layer_metrics import ms_per_step

UNIT = "ms/step"


def compute(ctx):
    return ms_per_step(ctx, ["engine/collide_setup", "engine/collide/"])
