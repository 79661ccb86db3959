"""Device time per ALS stage, from the stage scopes of the program.

The program runs each stage of its ALS step under a ``jax.named_scope``
inside one ``als`` scope (``repro.core.parafac2.STAGES``), so the HLO
``op_name`` of every op the step runs reads ``.../als/<stage>/...``;
``trace.hlo_op_names`` puts that name on each traced op. The names are
written out here, not imported, so that the benchmark reads the program as
it stands, and a program without the scopes reads as nothing.

A leaf op is counted once, in the stage its own ``op_name`` names. A fusion
that XLA built from ops of two stages carries the metadata of one of them
and is counted there.
"""
from __future__ import annotations

import re
from typing import Optional

from chipbench import trace

STAGES = ("procrustes_b", "polar", "project", "mode1", "h_solve", "mode2",
          "scatter", "v_solve", "ykv", "mode3", "w_solve", "fit")

_SCOPE = re.compile(r"(?:^|/)als/(" + "|".join(STAGES) + r")(?:/|$)")


def stage_of(op: "trace.Op") -> Optional[str]:
    """The stage whose scope the op's ``op_name`` lies under, or None."""
    m = _SCOPE.search(op.op_name)
    return m.group(1) if m else None


def stage_ms(ctx, *stages: str) -> Optional[float]:
    """Device ms per iteration in the leaf ops of ``stages``; None where the
    trace holds none (a program without stage scopes)."""
    if ctx.trace is None or not ctx.iterations_traced:
        return None
    ns = trace.op_time(ctx.trace, lambda op: stage_of(op) in stages)
    return ns / 1e6 / ctx.iterations_traced if ns > 0 else None


def unscoped_share(tr: "trace.Trace") -> Optional[float]:
    """Leaf-op device time under no stage scope, in % of all leaf-op device
    time in the window; None where no op carries a stage scope."""
    total = trace.op_time(tr, lambda op: True)
    scoped = trace.op_time(tr, lambda op: stage_of(op) is not None)
    if total <= 0 or scoped <= 0:
        return None
    return 100.0 * (total - scoped) / total
