"""Device ms per ALS iteration in ops that the ``eigh`` primitive lowered
to (the batched Gram eigendecompositions of ``core/procrustes.py``), found
by the name stack of each op's HLO metadata. Moves ``iter_s``."""
import re

from chipbench import trace

_EIGH = re.compile(r"(^|/)eigh\b")


def is_eigh(op):
    return bool(_EIGH.search(op.op_name))


def read(ctx):
    if ctx.trace is None or not ctx.iterations_traced:
        return None
    ns = trace.op_time(ctx.trace, is_eigh)
    return ns / 1e6 / ctx.iterations_traced if ns > 0 else None
