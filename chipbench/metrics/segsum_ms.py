"""Device ms per ALS iteration in the mode-2 segment sum: the ops under
``als/scatter/segsum`` (``core/spartan.py::mode2_scatter``: the sum of the
sorted compact rows into M2, each column's rows on their own). Part of
what ``scatter_ms`` reads. Moves ``iter_s``."""
import re

from chipbench import trace

_SEGSUM = re.compile(r"(?:^|/)als/scatter/segsum(?:/|$)")


def is_segsum(op):
    return bool(_SEGSUM.search(op.op_name))


def read(ctx):
    if ctx.trace is None or not ctx.iterations_traced:
        return None
    ns = trace.op_time(ctx.trace, is_segsum)
    return ns / 1e6 / ctx.iterations_traced if ns > 0 else None
