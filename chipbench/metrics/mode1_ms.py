"""Device ms per ALS iteration in the ``mode1`` stage: the mode-1 MTTKRP
M1 from the cached X_k V. Moves ``iter_s``."""
from chipbench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "mode1")
