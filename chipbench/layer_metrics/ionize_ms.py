"""Device time of the MC ionization source: the n_e deposit and event
keys (``engine/sources``) and the per-queue draw, kills and birth claims
(``engine/ionize/q<k>``), mean over the cell's chips, per step."""

from chipbench.layer_metrics import ms_per_step

UNIT = "ms/step"


def compute(ctx):
    return ms_per_step(ctx, ["engine/sources", "engine/ionize/"])
