"""Device time of the queue layout: the group's split into its interleaved
queues (``engine/split``) and the gather of the kept queues back into slot
order (``engine/merge/layout``), mean over the cell's chips, per step. The
merge part is also read by ``bookkeeping_ms``, which reads all of
``engine/merge``."""

from chipbench.layer_metrics import ms_per_step

UNIT = "ms/step"


def compute(ctx):
    return ms_per_step(ctx, ["engine/split", "engine/merge/layout"])
