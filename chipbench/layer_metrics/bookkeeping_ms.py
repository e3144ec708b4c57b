"""Device time of the engine's bookkeeping: landing last step's arrivals
(``engine/ingest``), packing and exchanging crossers
(``engine/migrate/q<k>``), the deferred merge (``engine/merge``) and the
diagnostics (``engine/diag``), mean over the cell's chips, per step."""

from chipbench.layer_metrics import ms_per_step

UNIT = "ms/step"


def compute(ctx):
    return ms_per_step(ctx, ["engine/ingest", "engine/migrate/",
                             "engine/merge", "engine/diag"])
