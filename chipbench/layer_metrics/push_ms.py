"""Device time of the mover: self time of the ops under the engine's
``engine/push/q<k>`` scopes, mean over the cell's chips, per step."""

from chipbench.layer_metrics import ms_per_step

UNIT = "ms/step"


def compute(ctx):
    return ms_per_step(ctx, ["engine/push/"])
