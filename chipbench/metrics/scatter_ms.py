"""Device ms per ALS iteration in the ``scatter`` stage: the segment sum of
the compact mode-2 products into M2 (argsort, gather, cumsum, difference).
It holds what ``sort_ms`` reads. Moves ``iter_s``."""
from chipbench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "scatter")
