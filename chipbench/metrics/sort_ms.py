"""Device ms per ALS iteration in sort ops outside ``eigh`` (which sorts its
eigenvalues): the stable argsort of ``core/spartan.py::mode2_scatter``.
Moves ``iter_s``."""
from chipbench import trace
from chipbench.metrics.eigh_ms import is_eigh


def is_sort(op):
    return op.instr.startswith("sort") and not is_eigh(op)


def read(ctx):
    if ctx.trace is None or not ctx.iterations_traced:
        return None
    ns = trace.op_time(ctx.trace, is_sort)
    return ns / 1e6 / ctx.iterations_traced if ns > 0 else None
