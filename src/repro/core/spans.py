"""The program's instrumentation: a stage-launch tally and host spans.

``dispatch_tally``
    A trace-time count of the per-bucket backend stage launches that stream
    I-/C-sized operands (see :func:`dispatch_tally`). Its stage names are
    the ALS stage names of ``core/parafac2.py::STAGES``, which also name the
    ``jax.named_scope`` of each stage inside the compiled step, so a
    profiler trace and the tally speak of the same stages. A key
    ``<stage>.<choice>`` counts a trace-time choice made inside a stage,
    not a launch: ``polar.gram_rows`` and ``polar.gram_cols`` count the
    buckets whose polar factor eigendecomposes the row or the column Gram
    (``core/procrustes.py::polar_gram_eigh``). :func:`launches` sums the
    launches alone.

``span(name)``
    A host span around a phase of the driver loops: the device layout
    (``layout`` and its children ``layout.plan``, ``layout.route``,
    ``layout.pack``, ``layout.to_device``) and the fitting loops
    (``fit.dispatch`` around each chunk or step call, ``fit.sync`` around
    its one host read). Each span opens a ``jax.profiler.TraceAnnotation``,
    so that a profile taken with ``jax.profiler.trace`` shows it on the
    host line beside the device ops, and adds its count, seconds and self
    seconds (its seconds less those of the spans opened inside it) to
    in-memory totals keyed by name. The totals are always on and hold one
    entry per name; :func:`span_totals` reads them, :func:`reset_spans`
    clears them. ``launch/decompose.py`` writes them into its ``--json``
    summary as the ``spans`` block.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax

__all__ = ["dispatch_tally", "launches", "reset_spans", "span", "span_totals"]


# ---------------------------------------------------------------------------
# Dispatch tally: how many per-bucket stage launches stream large operands
# ---------------------------------------------------------------------------

_TALLY: Optional[collections.Counter] = None


@contextlib.contextmanager
def dispatch_tally():
    """Count the per-bucket backend stage launches that stream I-/C-sized
    operands (the slab, XkV/Q, or Yc) — the launches the fused megakernel
    route collapses. Stages that only touch [Kb,R,R]-and-smaller tiles
    (mode-1/mode-3 from a cached YkV) are not counted.

    Counting happens when the backend methods RUN (eagerly or at jit trace
    time), so wrap one untraced/tracing ``als_step`` evaluation::

        with dispatch_tally() as t:
            jax.eval_shape(lambda s: als_step(data, s, opts), state)
        per_bucket = launches(t) / len(data.buckets)

    The staged CC path tallies 5 per bucket per iteration (procrustes_b,
    project, mode1, mode2, ykv); the fused route tallies 4 — the standalone
    projection pass disappears (``project_bucket`` carries Q). Keys with a
    dot (``polar.gram_rows``) count choices, not launches.
    """
    global _TALLY
    prev, _TALLY = _TALLY, collections.Counter()
    try:
        yield _TALLY
    finally:
        _TALLY = prev


def _tick(stage: str) -> None:
    if _TALLY is not None:
        _TALLY[stage] += 1


def launches(tally: collections.Counter) -> int:
    """The stage launches of a tally: its counts less the ``<stage>.<choice>``
    keys."""
    return sum(n for k, n in tally.items() if "." not in k)


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

_TOTALS: Dict[str, List[float]] = {}     # name -> [count, seconds, self s]
_LOCK = threading.Lock()
_OPEN = threading.local()                # per thread: child seconds of each
                                         # open span, innermost last


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Time the enclosed host code under ``name`` (see the module doc)."""
    stack = _OPEN.__dict__.setdefault("stack", [])
    stack.append(0.0)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        dt = time.perf_counter() - t0
        child = stack.pop()
        if stack:
            stack[-1] += dt
        with _LOCK:
            tot = _TOTALS.setdefault(name, [0, 0.0, 0.0])
            tot[0] += 1
            tot[1] += dt
            tot[2] += dt - child


def span_totals() -> Dict[str, Dict[str, float]]:
    """``{name: {"count", "s", "self_s"}}`` of every span closed since the
    last :func:`reset_spans`."""
    with _LOCK:
        return {k: {"count": int(c), "s": s, "self_s": self_s}
                for k, (c, s, self_s) in sorted(_TOTALS.items())}


def reset_spans() -> None:
    with _LOCK:
        _TOTALS.clear()
