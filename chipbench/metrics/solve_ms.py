"""Device ms per ALS iteration in the factor solves: ``h_solve``,
``v_solve`` and ``w_solve`` (the constraint updates, their Grams and the
column normalisation). Moves ``iter_s``."""
from chipbench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "h_solve", "v_solve", "w_solve")
