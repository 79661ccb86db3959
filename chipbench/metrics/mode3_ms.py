"""Device ms per ALS iteration in the ``ykv`` and ``mode3`` stages:
G_k = Y_k V and the mode-3 MTTKRP rows. Moves ``iter_s``."""
from chipbench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "ykv", "mode3")
