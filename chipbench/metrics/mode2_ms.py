"""Device ms per ALS iteration in the ``mode2`` stage: the per-bucket
compact mode-2 products, before they are summed into M2. Moves
``iter_s``."""
from chipbench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "mode2")
