"""Device ms per ALS iteration in the ``procrustes_b`` and ``project``
stages: the gathers of V and W rows, X_k V and B_k = X_k V S_k H^T, and the
projection Y_k = Q_k^T X_k. Moves ``iter_s``."""
from chipbench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "procrustes_b", "project")
