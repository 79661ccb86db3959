"""One machine-readable summary schema for every launch driver.

``decompose.py``, ``stream.py`` and ``dryrun.py`` used to hand-roll three
different ``--json`` dicts; CI and the benchmark gate had to know each one.
Every driver summary now goes through :func:`run_summary`, which stamps

* ``schema_version`` — bumped whenever a consumer-visible key changes
  meaning (adding keys is compatible and does not bump it);
* ``kind`` — which driver produced the blob (``decompose`` | ``stream`` |
  ``dryrun``);
* ``resolved_options`` — the CANONICALIZED option block
  (:func:`resolved_options`): rank/engine/backend/dtype plus the resolved
  constraint specs and compress spec, so a consumer reads what actually ran
  rather than re-deriving defaults from CLI flags.

Driver-specific payload keys stay at the top level (the historical layout
tests and benchmarks consume); the schema block is additive. Notable
decompose additions: ``supervisor`` — the fault-tolerant fit's
:class:`repro.dist.supervisor.SupervisorReport` as a dict
(retry/restore/rollback counts, straggler chunk ids, checkpoints written,
resume step, final ridge) or ``None`` for a bare fit — and
``shard_balance`` — the nnz-balanced shard planner's before/after
max-over-mean imbalance under ``engine="mesh"`` or ``None`` — and
``spans`` — the run's host-span totals,
:func:`repro.core.spans.span_totals`: ``{name: {"count", "s", "self_s"}}``
for the device layout (``layout`` and its children ``layout.plan``,
``layout.route``, ``layout.pack``, ``layout.to_device``) and the fitting
loop (``fit.dispatch`` around each chunk or step call, including the
first call's compile; ``fit.sync`` around its one host read).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

__all__ = ["SCHEMA_VERSION", "resolved_options", "run_summary"]

# version 2 = the unified schema (1 was the implicit hand-rolled layouts)
SCHEMA_VERSION = 2


def resolved_options(opts=None, **extra) -> Dict[str, Any]:
    """Canonical option block from a ``Parafac2Options`` (+ driver extras).

    Specs are canonicalized through the same parsers ``fit`` uses
    (``repro.core.constraints`` / ``repro.core.compress``), so two spellings
    of one configuration serialize identically. ``extra`` keys (format, tol,
    seed, ...) are driver-level knobs that ride along verbatim.
    """
    block: Dict[str, Any] = {}
    if opts is not None:
        from repro.core.compress import preprocess_summary
        from repro.core.constraints import constraint_summary

        block.update(
            rank=opts.rank,
            engine=opts.engine,
            backend=opts.backend,
            check_every=opts.check_every,
            w_layout=opts.w_layout,
            procrustes=opts.procrustes,
            dtype=np.dtype(opts.dtype).name,
            constraints=constraint_summary(opts.constraint_specs()),
            compress=preprocess_summary(opts.compress, opts.rank),
        )
    block.update(extra)
    return block


def run_summary(kind: str, options: Optional[Dict[str, Any]] = None,
                **payload) -> Dict[str, Any]:
    """Assemble one schema-stamped driver summary.

    ``options`` is a :func:`resolved_options` block; ``payload`` keys land at
    the top level (and must not collide with the schema keys).
    """
    reserved = {"schema_version", "kind", "resolved_options"}
    clash = reserved & set(payload)
    if clash:
        raise ValueError(f"summary payload keys {sorted(clash)} collide with "
                         f"the schema block")
    return {
        "schema_version": SCHEMA_VERSION,
        "kind": kind,
        "resolved_options": dict(options or {}),
        **payload,
    }
