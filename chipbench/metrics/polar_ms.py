"""Device ms per ALS iteration in the ``polar`` stage: the batched polar
solve of each B_k (Gram, ``eigh``, the Q matmuls) and the subject mask. It
holds what ``eigh_ms`` reads. Moves ``iter_s``."""
from chipbench.stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "polar")
