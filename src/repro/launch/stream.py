"""Streaming incremental PARAFAC2 service — "PARAFAC2 as an endpoint".

Every fit elsewhere in the repo is a batch job over a frozen dataset; the
paper's target workload (EHR phenotyping over a growing population) is
append-only: new subjects arrive, existing subjects accrue observations.
This module serves that workload. A :class:`StreamService` warm-starts from
a fitted ``(H, V, W)`` bundle and serves *append* requests with the factor
matrices FIXED — each new/touched subject needs only its own Procrustes
basis ``Q_k`` and its own W row, both independent across subjects, so
requests batch into one padded, jitted dispatch
(:func:`repro.core.parafac2.update_subjects` via
:func:`repro.core.engine.make_subject_update`), modeled on the
``launch/serve.py`` prefill/decode loop:

    request queue -> padded subject batch (pinned geometry,
    ``repro.sparse.bucketing.fixed_plan``) -> ONE compiled dispatch ->
    per-request W rows + residuals + latency stats.

Drift and refits: the service tracks per-subject residuals, so
``stream_fit`` is the EXACT fit of the union dataset at the current factors
(old subjects' residuals are unchanged while H/V are frozen). ``drift`` is
how far that has fallen below the fit at the last full (re)fit; when it
crosses ``drift_threshold`` the service triggers a full refit over the
union through the ordinary engines (``opts.engine`` — host/scan/mesh),
warm-started from the current factors (``refit="warm"``) or from the
deterministic cold init (``refit="cold"``, bitwise-reproducing a batch fit
over the same data). ``checkpoint/ckpt.py`` persists the warm state.

Temporal regularization (tPARAFAC2, PAPERS.md): ``smooth_lam > 0`` anchors
a *touched* subject's streamed W row to its previous row with a quadratic
penalty ``lam * ||w - w_prev||^2`` — folded exactly into the row's normal
equations, so it composes with any configured W constraint.

CLI (driver):

  PYTHONPATH=src python -m repro.launch.stream --dataset synthetic \
      --scale 0.003 --rank 4 --warm-iters 20 --warm-frac 0.6 \
      --batch-slots 8 --drift-threshold 0.05 --smooth 0.1 \
      --format auto --json out.json

``--appends FILE.jsonl`` replays externally supplied append payloads (one
JSON object per line: ``rows``/``cols``/``vals`` [+ ``n_rows``, + optional
``subject`` for accrual onto an existing id]); malformed payloads fail fast
with ``ValueError``. ``--json`` writes the machine-readable latency /
throughput / drift summary CI and the stream benchmark consume. See
docs/ARCHITECTURE.md (stage 9).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import (
    Parafac2Options, bucketize, fit, init_state, update_subjects)
from repro.core.engine import make_subject_update
from repro.core.constraints import (
    available as available_constraints, constraint_summary,
    parse_constraint_arg)
from repro.core.irregular import Bucketed
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.summary import resolved_options, run_summary
from repro.sparse import (
    IrregularCOO, SubjectCOO, fixed_plan, plan_layout)
from repro.sparse.bucketing import SCOO_DENSITY_THRESHOLD

__all__ = ["AppendResult", "StreamService", "synthetic_stream",
           "validate_payload", "main"]


def _ceil_to(x: int, align: int) -> int:
    return max(align, ((int(x) + align - 1) // align) * align)


# ---------------------------------------------------------------------------
# append payloads
# ---------------------------------------------------------------------------

def validate_payload(payload: Any, n_cols: int,
                     n_known: int) -> Tuple[Optional[int], SubjectCOO]:
    """Fail-fast validation of one append payload.

    A payload is a mapping with equal-length ``rows``/``cols``/``vals``
    observation triplets (local row ids within the appended block), an
    optional ``n_rows`` (number of observation rows in the block; defaults
    to ``max(rows) + 1``), and an optional ``subject`` id — present means
    the block accrues onto that EXISTING subject, absent means a new
    subject. Returns ``(subject_id_or_None, block_slice)``; raises
    ``ValueError`` naming the first problem found (the service rejects the
    request before it ever reaches the queue or the device).
    """
    if not isinstance(payload, dict):
        raise ValueError(f"append payload must be a mapping, got "
                         f"{type(payload).__name__}")
    for key in ("rows", "cols", "vals"):
        if key not in payload:
            raise ValueError(f"append payload missing required key {key!r}")
    try:
        rows = np.asarray(payload["rows"], dtype=np.int64)
        cols = np.asarray(payload["cols"], dtype=np.int64)
        vals = np.asarray(payload["vals"], dtype=np.float64)
    except (TypeError, ValueError) as e:
        raise ValueError(f"append payload triplets not numeric: {e}") from None
    if not (rows.ndim == cols.ndim == vals.ndim == 1):
        raise ValueError("append payload rows/cols/vals must be 1-D lists")
    if not (rows.size == cols.size == vals.size):
        raise ValueError(
            f"append payload triplet lengths differ: rows={rows.size} "
            f"cols={cols.size} vals={vals.size}")
    if rows.size == 0:
        raise ValueError("append payload has no observations")
    if rows.min() < 0:
        raise ValueError("append payload has negative row indices")
    if cols.min() < 0 or cols.max() >= n_cols:
        raise ValueError(
            f"append payload column ids must be in [0, {n_cols}), got "
            f"[{cols.min()}, {cols.max()}]")
    if not np.all(np.isfinite(vals)):
        raise ValueError("append payload values must be finite")
    n_rows = payload.get("n_rows", int(rows.max()) + 1)
    if not isinstance(n_rows, (int, np.integer)) or n_rows < int(rows.max()) + 1:
        raise ValueError(
            f"append payload n_rows={n_rows!r} inconsistent with max row "
            f"index {int(rows.max())}")
    sid = payload.get("subject")
    if sid is not None:
        if not isinstance(sid, (int, np.integer)):
            raise ValueError(f"append payload subject id must be an int, "
                             f"got {sid!r}")
        if not 0 <= sid < n_known:
            raise ValueError(
                f"append payload subject id {sid} unknown "
                f"(service knows {n_known} subjects)")
    block = SubjectCOO(rows=rows.astype(np.int32), cols=cols.astype(np.int32),
                       vals=vals, n_rows=int(n_rows), n_cols=n_cols)
    return (None if sid is None else int(sid)), block


def _merge_block(base: SubjectCOO, block: SubjectCOO) -> SubjectCOO:
    """Accrue an observation block onto an existing slice: block rows are
    local to the block, appended AFTER the existing observation rows."""
    off = base.n_rows
    return SubjectCOO(
        rows=np.concatenate([base.rows, block.rows + off]).astype(np.int32),
        cols=np.concatenate([base.cols, block.cols]).astype(np.int32),
        vals=np.concatenate([base.vals, block.vals]),
        n_rows=base.n_rows + block.n_rows,
        n_cols=base.n_cols)


@dataclasses.dataclass(frozen=True)
class AppendResult:
    """Per-request serving result (one element of a flushed batch)."""

    request_id: int
    subject_id: int
    is_new: bool
    latency_s: float     # wall time of the batch this request rode in
    batch_size: int      # real requests in that batch (before padding)
    resid: float         # ||X_k - Q_k H S_k V^T||_F^2 at the returned row
    w_row: np.ndarray    # the subject's updated W row [R]


# ---------------------------------------------------------------------------
# the service
# ---------------------------------------------------------------------------

class StreamService:
    """Batched incremental PARAFAC2 serving over a warm-started model.

    Build via :meth:`warm_start` (fit the initial population) or
    :meth:`from_checkpoint` (restore a previously saved service state).
    ``submit`` queues validated requests; ``flush`` drains the queue in
    padded ``batch_slots``-sized dispatches; ``append`` is submit+flush for
    one request. Drift-triggered refits happen inside ``flush``.
    """

    def __init__(self, subjects: Sequence[SubjectCOO], n_cols: int,
                 opts: Parafac2Options, H, V, W, *,
                 batch_slots: int = 8,
                 drift_threshold: float = 0.05,
                 refit: str = "warm",
                 refit_iters: int = 50,
                 refit_tol: float = 1e-7,
                 smooth_lam: float = 0.0,
                 inner_iters: int = 2,
                 format: str = "auto",
                 max_buckets: int = 4,
                 row_align: int = 8,
                 col_align: int = 8,
                 nnz_align: int = 32,
                 seed: int = 0):
        if opts.w_layout != "global":
            raise ValueError("StreamService needs w_layout='global' (streamed "
                             "W rows are indexed by global subject id)")
        if refit not in ("warm", "cold"):
            raise ValueError(f"refit must be 'warm' or 'cold', got {refit!r}")
        if format not in ("cc", "scoo", "auto"):
            raise ValueError(f"unknown stream format {format!r}")
        if batch_slots < 1:
            raise ValueError("batch_slots must be >= 1")
        self.opts = opts
        self.n_cols = int(n_cols)
        self.subjects: List[SubjectCOO] = list(subjects)
        self.H = jnp.asarray(H, opts.dtype)
        self.V = jnp.asarray(V, opts.dtype)
        self.W = np.asarray(W, dtype=np.dtype(jnp.dtype(opts.dtype).name))
        self.batch_slots = int(batch_slots)
        self.drift_threshold = float(drift_threshold)
        self.refit_mode = refit
        self.refit_iters = int(refit_iters)
        self.refit_tol = float(refit_tol)
        self.smooth_lam = float(smooth_lam)
        self.inner_iters = int(inner_iters)
        self.fmt = format
        self.max_buckets = int(max_buckets)
        self.row_align = int(row_align)
        self.col_align = int(col_align)
        self.nnz_align = int(nnz_align)
        self.seed = int(seed)

        # per-subject residual/norm bookkeeping: stream_fit stays the exact
        # union fit because H/V are frozen between refits
        self._sub_norm = np.asarray(
            [float(np.sum(np.square(s.vals, dtype=np.float64)))
             for s in self.subjects], dtype=np.float64)
        self._sub_resid = np.zeros(len(self.subjects), dtype=np.float64)
        self.baseline_fit = float("nan")

        # sticky padded batch geometry (grows monotonically; each distinct
        # (geometry, format) is one compiled dispatch)
        self._i_pad = self.row_align
        self._c_pad = self.col_align
        self._n_pad = self.nnz_align
        self._geometries: set = set()

        self._update = make_subject_update(
            opts, smooth_lam=self.smooth_lam, inner_iters=self.inner_iters)

        self._queue: List[Tuple[int, Optional[int], SubjectCOO]] = []
        self._next_request = 0
        self.latencies: List[float] = []
        self.batch_latencies: List[float] = []
        self.n_appends = 0
        self.n_batches = 0
        self.n_new = 0
        self.n_touched = 0
        self.refit_at: List[int] = []
        self.drift_max = 0.0

    # -- constructors --------------------------------------------------------

    @classmethod
    def warm_start(cls, data: IrregularCOO, opts: Parafac2Options, *,
                   iters: int = 50, tol: float = 1e-7, seed: int = 0,
                   verbose: bool = False, **kw) -> Tuple["StreamService", dict]:
        """Fit the initial population in batch, then serve appends on top.

        Returns ``(service, warm_info)`` with the warm fit/iteration stats.
        """
        svc = cls(data.subjects, data.n_cols, opts,
                  H=jnp.eye(opts.rank, dtype=opts.dtype),
                  V=jnp.zeros((data.n_cols, opts.rank), opts.dtype),
                  W=np.ones((data.n_subjects, opts.rank)), seed=seed, **kw)
        t0 = time.perf_counter()
        bt = svc._bucketize_union(svc.union_data())
        state, hist = fit(bt, opts, max_iters=iters, tol=tol, seed=seed,
                          verbose=verbose)
        svc._adopt(bt, state.H, state.V, state.W)
        info = {"fit": float(hist[-1]), "iters": len(hist),
                "seconds": time.perf_counter() - t0,
                "n_subjects": data.n_subjects, "baseline_fit": svc.baseline_fit}
        return svc, info

    @classmethod
    def from_checkpoint(cls, directory: str, data: IrregularCOO,
                        opts: Parafac2Options, **kw) -> "StreamService":
        """Restore a saved service state (H/V/W + residual bookkeeping) over
        the matching union dataset — the elastic-resume path for a service
        process that died mid-stream."""
        svc = cls(data.subjects, data.n_cols, opts,
                  H=jnp.eye(opts.rank, dtype=opts.dtype),
                  V=jnp.zeros((data.n_cols, opts.rank), opts.dtype),
                  W=np.ones((data.n_subjects, opts.rank)), **kw)
        template = {"H": svc.H, "V": svc.V, "W": jnp.asarray(svc.W),
                    "sub_norm": jnp.asarray(svc._sub_norm),
                    "sub_resid": jnp.asarray(svc._sub_resid)}
        tree, _, extra = ckpt.restore(directory, template)
        if int(extra.get("n_subjects", data.n_subjects)) != data.n_subjects:
            raise ValueError(
                f"checkpoint was written with {extra.get('n_subjects')} "
                f"subjects but the supplied union dataset has "
                f"{data.n_subjects}")
        svc.H = tree["H"]
        svc.V = tree["V"]
        svc.W = np.array(tree["W"])
        svc._sub_norm = np.array(tree["sub_norm"], dtype=np.float64)
        svc._sub_resid = np.array(tree["sub_resid"], dtype=np.float64)
        svc.baseline_fit = float(extra.get("baseline_fit", float("nan")))
        svc.n_appends = int(extra.get("n_appends", 0))
        svc._i_pad = int(extra.get("i_pad", svc._i_pad))
        svc._c_pad = int(extra.get("c_pad", svc._c_pad))
        svc._n_pad = int(extra.get("n_pad", svc._n_pad))
        return svc

    def save(self, directory: str) -> str:
        """Persist the warm state through ``checkpoint/ckpt.py`` (atomic,
        step-stamped by append count, elastic-restorable)."""
        tree = {"H": self.H, "V": self.V, "W": jnp.asarray(self.W),
                "sub_norm": jnp.asarray(self._sub_norm),
                "sub_resid": jnp.asarray(self._sub_resid)}
        return ckpt.save(directory, self.n_appends, tree, extra={
            "baseline_fit": self.baseline_fit,
            "n_subjects": len(self.subjects),
            "n_appends": self.n_appends,
            # sticky batch geometry: restoring it makes the resumed service
            # dispatch bit-identical batches to the uninterrupted one
            "i_pad": self._i_pad, "c_pad": self._c_pad, "n_pad": self._n_pad,
        })

    # -- model/fit bookkeeping ----------------------------------------------

    def union_data(self) -> IrregularCOO:
        """The accumulated dataset: warm subjects + every streamed append."""
        return IrregularCOO(subjects=list(self.subjects), n_cols=self.n_cols)

    def _bucketize_union(self, data: IrregularCOO) -> Bucketed:
        """The batch-path bucketization used for warm fits and refits —
        identical to what ``launch/decompose.py`` would build for the same
        data/format, which is what makes the cold-refit parity exact."""
        rc, ccnt, nnzc = data.row_counts(), data.col_counts(), data.nnz_counts()
        plan, fmts = plan_layout(rc, ccnt, nnzc, format=self.fmt,
                                 max_buckets=self.max_buckets)
        return bucketize(data, dtype=self.opts.dtype, plan=plan, formats=fmts)

    def _adopt(self, bt: Bucketed, H, V, W) -> None:
        """Install new factors and rebuild the per-subject residual ledger:
        one ``update_subjects`` pass over the full union re-solves every
        subject's (Q_k, w_k) at the new factors, so the stored W rows and
        the residual ledger are exactly consistent."""
        self.H = jnp.asarray(H, self.opts.dtype)
        self.V = jnp.asarray(V, self.opts.dtype)
        W_new, resid = update_subjects(
            bt, self.H, self.V, self.opts, w_init=jnp.asarray(W),
            inner_iters=1)
        self.W = np.array(W_new)  # writable host copy (rows mutate per append)
        self._sub_resid = np.maximum(
            np.asarray(resid, dtype=np.float64), 0.0)
        self.baseline_fit = self.stream_fit

    @property
    def stream_fit(self) -> float:
        """Exact fit of the union dataset at the current factors (each
        subject evaluated at its last-solved ``(Q_k, w_k)``)."""
        total = float(self._sub_norm.sum())
        if total <= 0.0:
            return 1.0
        resid = max(float(self._sub_resid.sum()), 0.0)
        return 1.0 - float(np.sqrt(resid / total))

    @property
    def drift(self) -> float:
        """How far the streamed model has fallen below the last (re)fit."""
        return max(0.0, self.baseline_fit - self.stream_fit)

    def refit(self, *, mode: Optional[str] = None) -> dict:
        """Full ALS refit over the union dataset through ``opts.engine``.

        ``mode="warm"`` starts from the current ``(H, V, W)``;
        ``mode="cold"`` from the deterministic seeded init — bitwise the
        same trajectory a batch ``fit`` over the same data would take.
        """
        mode = self.refit_mode if mode is None else mode
        t0 = time.perf_counter()
        bt = self._bucketize_union(self.union_data())
        state0 = None
        if mode == "warm":
            state0 = init_state(bt, self.opts, self.seed)._replace(
                H=jnp.asarray(self.H, self.opts.dtype),
                V=jnp.asarray(self.V, self.opts.dtype),
                W=jnp.asarray(self.W, self.opts.dtype))
        state, hist = fit(bt, self.opts, max_iters=self.refit_iters,
                          tol=self.refit_tol, seed=self.seed, state=state0)
        self._adopt(bt, state.H, state.V, state.W)
        self.refit_at.append(self.n_appends)
        return {"mode": mode, "iters": len(hist), "fit": float(hist[-1]),
                "baseline_fit": self.baseline_fit,
                "seconds": time.perf_counter() - t0,
                "n_subjects": len(self.subjects)}

    # -- the serving loop ----------------------------------------------------

    def submit(self, payload: dict) -> int:
        """Validate (fail fast) and queue one append request; returns its
        request id. Nothing reaches the device until ``flush``."""
        sid, block = validate_payload(payload, self.n_cols, len(self.subjects))
        rid = self._next_request
        self._next_request += 1
        self._queue.append((rid, sid, block))
        return rid

    def append(self, payload: dict) -> AppendResult:
        """submit + flush for a single request (the one-at-a-time API)."""
        self.submit(payload)
        return self.flush()[-1]

    def flush(self) -> List[AppendResult]:
        """Drain the queue in ``batch_slots``-sized padded dispatches; runs
        the drift check (and any triggered refit) after each batch."""
        results: List[AppendResult] = []
        while self._queue:
            chunk, self._queue = (self._queue[: self.batch_slots],
                                  self._queue[self.batch_slots:])
            results.extend(self._dispatch(chunk))
            self.drift_max = max(self.drift_max, self.drift)
            if self.drift > self.drift_threshold:
                self.refit()
        return results

    def _batch_geometry(self, slices: Sequence[SubjectCOO]) -> Tuple[int, int, int]:
        """Grow the sticky padded geometry to cover this batch."""
        need_i = max(s.n_rows for s in slices)
        need_c = max(s.nonzero_cols().size for s in slices)
        need_n = max(max(s.nnz, 1) for s in slices)
        self._i_pad = max(self._i_pad, _ceil_to(need_i, self.row_align))
        self._c_pad = max(self._c_pad, _ceil_to(need_c, self.col_align))
        self._n_pad = max(self._n_pad, _ceil_to(need_n, self.nnz_align))
        return self._i_pad, self._c_pad, self._n_pad

    def _batch_format(self, slices: Sequence[SubjectCOO],
                      i_pad: int, c_pad: int) -> str:
        if self.fmt in ("cc", "scoo"):
            return self.fmt
        dens = sum(s.nnz for s in slices) / max(
            len(slices) * i_pad * c_pad, 1)
        return "scoo" if dens < SCOO_DENSITY_THRESHOLD else "cc"

    def _dispatch(self, chunk: Sequence[Tuple[int, Optional[int], SubjectCOO]]
                  ) -> List[AppendResult]:
        """One padded batch: stage -> compiled update -> host state commit."""
        t0 = time.perf_counter()
        R = self.opts.rank
        merged: List[SubjectCOO] = []
        metas: List[Tuple[int, Optional[int], bool]] = []
        for rid, sid, block in chunk:
            if sid is None:
                merged.append(block)
                metas.append((rid, None, True))
            else:
                merged.append(_merge_block(self.subjects[sid], block))
                metas.append((rid, sid, False))

        i_pad, c_pad, n_pad = self._batch_geometry(merged)
        fmt = self._batch_format(merged, i_pad, c_pad)
        # subject_align pads every chunk to a multiple of batch_slots, so a
        # short final chunk still reuses the full-batch compiled dispatch
        self._geometries.add((i_pad, c_pad, n_pad, fmt,
                              _ceil_to(len(merged), self.batch_slots)))
        plan = fixed_plan(len(merged), i_pad, c_pad,
                          nnz_pad=n_pad if fmt == "scoo" else None)
        batch = bucketize(
            IrregularCOO(subjects=merged, n_cols=self.n_cols), plan=plan,
            formats=[fmt], subject_align=self.batch_slots,
            dtype=self.opts.dtype)
        # pin the Bucketed aux metadata so every flush shares one jit entry
        batch = Bucketed(buckets=batch.buckets, n_subjects=self.batch_slots,
                         n_cols=self.n_cols, norm_sq=0.0)

        np_dt = np.dtype(jnp.dtype(self.opts.dtype).name)
        w_init = np.ones((self.batch_slots, R), np_dt)
        w_prev = np.zeros((self.batch_slots, R), np_dt)
        pmask = np.zeros((self.batch_slots,), np_dt)
        for slot, (_, sid, is_new) in enumerate(metas):
            if not is_new:
                w_init[slot] = self.W[sid]
                w_prev[slot] = self.W[sid]
                pmask[slot] = 1.0
        W_rows, resid = self._update(
            batch, self.H, self.V, jnp.asarray(w_init), jnp.asarray(w_prev),
            jnp.asarray(pmask))
        W_rows = np.asarray(jax.block_until_ready(W_rows))
        resid = np.asarray(resid)
        latency = time.perf_counter() - t0

        # commit host state per request
        out: List[AppendResult] = []
        for slot, ((_, sid, is_new), slice_) in enumerate(zip(metas, merged)):
            rid = metas[slot][0]
            norm = float(np.sum(np.square(slice_.vals, dtype=np.float64)))
            r = max(float(resid[slot]), 0.0)
            if is_new:
                sid = len(self.subjects)
                self.subjects.append(slice_)
                self.W = np.vstack([self.W, W_rows[slot][None]])
                self._sub_norm = np.append(self._sub_norm, norm)
                self._sub_resid = np.append(self._sub_resid, r)
                self.n_new += 1
            else:
                self.subjects[sid] = slice_
                self.W[sid] = W_rows[slot]
                self._sub_norm[sid] = norm
                self._sub_resid[sid] = r
                self.n_touched += 1
            self.n_appends += 1
            self.latencies.append(latency)
            out.append(AppendResult(
                request_id=rid, subject_id=sid, is_new=is_new,
                latency_s=latency, batch_size=len(chunk), resid=r,
                w_row=W_rows[slot].copy()))
        self.batch_latencies.append(latency)
        self.n_batches += 1
        return out

    # -- reporting -----------------------------------------------------------

    def stats(self) -> dict:
        """Machine-readable serving stats (the ``--json`` payload core)."""
        lat = np.asarray(self.latencies, dtype=np.float64)
        lat_ms: Dict[str, float] = {}
        if lat.size:
            lat_ms = {"p50": float(np.percentile(lat, 50) * 1e3),
                      "p99": float(np.percentile(lat, 99) * 1e3),
                      "mean": float(lat.mean() * 1e3),
                      "max": float(lat.max() * 1e3)}
        # every request's latency is its batch's wall time, so throughput
        # divides by the sum over BATCHES, not over requests
        busy = float(np.sum(self.batch_latencies))
        subjects_per_s = (self.n_appends / busy) if busy > 0 else 0.0
        return {
            "appends": self.n_appends, "batches": self.n_batches,
            "new": self.n_new, "touched": self.n_touched,
            "batch_slots": self.batch_slots,
            "latency_ms": lat_ms,
            "subjects_per_s": subjects_per_s,
            "stream_fit": self.stream_fit,
            "baseline_fit": self.baseline_fit,
            "drift": self.drift, "drift_max": self.drift_max,
            "drift_threshold": self.drift_threshold,
            "refits": len(self.refit_at), "refit_at": list(self.refit_at),
            "compiled_geometries": len(self._geometries),
            "n_subjects": len(self.subjects),
            "format": self.fmt, "smooth_lam": self.smooth_lam,
            "inner_iters": self.inner_iters,
        }


# ---------------------------------------------------------------------------
# synthetic stream construction (drivers, tests, benchmarks)
# ---------------------------------------------------------------------------

def synthetic_stream(data: IrregularCOO, *, warm_frac: float = 0.6,
                     touch_frac: float = 0.2, holdout_frac: float = 0.4,
                     seed: int = 0) -> Tuple[IrregularCOO, List[dict]]:
    """Split a dataset into a warm population + an append stream.

    The first ``warm_frac`` of subjects form the warm-start population; the
    rest arrive as *new-subject* payloads. A ``touch_frac`` share of warm
    subjects additionally hold out their last ``holdout_frac`` observation
    rows, which arrive later as *accrual* payloads onto the existing id —
    so the union of warm data + replayed payloads is EXACTLY the original
    dataset (the parity tests rely on this).
    """
    K = data.n_subjects
    n_warm = min(K, max(1, int(round(K * warm_frac))))
    rng = np.random.default_rng(seed)
    warm: List[SubjectCOO] = []
    payloads: List[dict] = []
    for i, s in enumerate(data.subjects[:n_warm]):
        split = max(1, int(round(s.n_rows * (1.0 - holdout_frac))))
        held = s.rows >= split
        if (s.n_rows >= 4 and rng.random() < touch_frac
                and held.any() and (~held).any()):
            warm.append(SubjectCOO(
                rows=s.rows[~held], cols=s.cols[~held], vals=s.vals[~held],
                n_rows=split, n_cols=s.n_cols))
            payloads.append({
                "subject": i,
                "rows": (s.rows[held] - split).tolist(),
                "cols": s.cols[held].tolist(),
                "vals": s.vals[held].tolist(),
                "n_rows": s.n_rows - split,
            })
        else:
            warm.append(s)
    for s in data.subjects[n_warm:]:
        payloads.append({"rows": s.rows.tolist(), "cols": s.cols.tolist(),
                         "vals": s.vals.tolist(), "n_rows": s.n_rows})
    order = rng.permutation(len(payloads))
    return (IrregularCOO(subjects=warm, n_cols=data.n_cols),
            [payloads[i] for i in order])


# ---------------------------------------------------------------------------
# CLI driver
# ---------------------------------------------------------------------------

def main(argv=None) -> dict:
    from repro.launch.decompose import load_dataset

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synthetic",
                    choices=["choa", "movielens", "synthetic"])
    ap.add_argument("--scale", type=float, default=0.003)
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--warm-iters", type=int, default=20,
                    help="batch ALS iterations for the warm-start fit")
    ap.add_argument("--tol", type=float, default=1e-7)
    ap.add_argument("--warm-frac", type=float, default=0.6,
                    help="fraction of subjects in the warm population")
    ap.add_argument("--touch-frac", type=float, default=0.2,
                    help="fraction of warm subjects that later accrue "
                         "held-out observations")
    ap.add_argument("--appends", default="", metavar="FILE.jsonl",
                    help="replay append payloads from this JSONL file "
                         "instead of the synthetic stream (fail-fast on "
                         "malformed payloads)")
    ap.add_argument("--limit", type=int, default=0,
                    help="stream at most this many appends (0 = all)")
    ap.add_argument("--batch-slots", type=int, default=8,
                    help="requests per padded dispatch (the serving batch)")
    ap.add_argument("--drift-threshold", type=float, default=0.05,
                    help="fit drift that triggers a full refit")
    ap.add_argument("--refit", default="warm", choices=["warm", "cold"],
                    help="refit start: warm (current factors) or cold "
                         "(seeded init — bitwise equals a batch fit)")
    ap.add_argument("--refit-iters", type=int, default=50)
    ap.add_argument("--smooth", type=float, default=0.0, metavar="LAM",
                    help="tPARAFAC2 temporal anchor on touched subjects' "
                         "streamed W rows: lam * ||w - w_prev||^2")
    ap.add_argument("--inner-iters", type=int, default=2,
                    help="Q <-> w alternations per streamed subject")
    ap.add_argument("--constraint", default="", metavar="SPECS",
                    help="per-mode factor constraints (as in decompose.py); "
                         f"registered: {', '.join(available_constraints())}")
    ap.add_argument("--backend", default="auto", choices=["jnp", "auto"],
                    help="MTTKRP backend: 'auto' routes each bucket, 'jnp' "
                         "is the plain jnp reference (see repro.core.backend)")
    ap.add_argument("--format", default="auto", choices=["cc", "scoo", "auto"])
    ap.add_argument("--engine", default="host", choices=["host", "scan", "mesh"],
                    help="engine for the warm fit and refits")
    ap.add_argument("--check-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="",
                    help="save the final service state here (ckpt.py layout)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the machine-readable latency/throughput/"
                         "drift summary to PATH")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.constraint:
        specs = parse_constraint_arg(args.constraint)
    else:
        specs = {"v": "nonneg", "w": "nonneg"}
    opts = Parafac2Options(rank=args.rank, constraints=specs,
                           backend=args.backend, engine=args.engine,
                           check_every=args.check_every)

    data = load_dataset(args.dataset, args.scale, args.seed)
    warm, payloads = synthetic_stream(
        data, warm_frac=args.warm_frac, touch_frac=args.touch_frac,
        seed=args.seed)
    if args.appends:
        with open(args.appends) as f:
            payloads = []
            for ln, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    payloads.append(json.loads(line))
                except json.JSONDecodeError as e:
                    raise ValueError(
                        f"{args.appends}:{ln}: not valid JSON: {e}") from None
    if args.limit:
        payloads = payloads[: args.limit]

    print(f"[stream] warm population K={warm.n_subjects} J={warm.n_cols} "
          f"nnz={warm.nnz}; {len(payloads)} appends queued")
    print(f"[constraints] {constraint_summary(specs)}")
    svc, warm_info = StreamService.warm_start(
        warm, opts, iters=args.warm_iters, tol=args.tol, seed=args.seed,
        batch_slots=args.batch_slots, drift_threshold=args.drift_threshold,
        refit=args.refit, refit_iters=args.refit_iters,
        smooth_lam=args.smooth, inner_iters=args.inner_iters,
        format=args.format)
    print(f"[warm] fit={warm_info['fit']:.4f} in {warm_info['iters']} iters "
          f"({warm_info['seconds']:.1f}s)")

    t0 = time.perf_counter()
    for payload in payloads:
        svc.submit(payload)   # fail-fast validation happens HERE
        if len(svc._queue) >= args.batch_slots:
            svc.flush()
    svc.flush()
    stream_s = time.perf_counter() - t0

    st = svc.stats()
    st["subjects_per_s_wall"] = (st["appends"] / stream_s
                                 if stream_s > 0 else 0.0)
    if st["latency_ms"]:
        print(f"[stream] {st['appends']} appends in {st['batches']} batches "
              f"({stream_s:.2f}s wall): p50={st['latency_ms']['p50']:.1f}ms "
              f"p99={st['latency_ms']['p99']:.1f}ms "
              f"{st['subjects_per_s_wall']:.1f} subjects/s")
    print(f"[drift] stream_fit={st['stream_fit']:.4f} "
          f"baseline={st['baseline_fit']:.4f} drift={st['drift']:.4f} "
          f"(max {st['drift_max']:.4f}, threshold {st['drift_threshold']}) "
          f"refits={st['refits']} at {st['refit_at']}")
    if args.ckpt_dir:
        path = svc.save(args.ckpt_dir)
        print(f"[ckpt] saved service state to {path}")

    summary = run_summary(
        "stream",
        # the canonicalized option block every driver shares
        resolved_options(opts, format=args.format, tol=args.tol,
                         seed=args.seed, warm_frac=args.warm_frac,
                         batch_slots=args.batch_slots,
                         drift_threshold=args.drift_threshold,
                         refit=args.refit, smooth_lam=args.smooth),
        dataset=args.dataset, scale=args.scale, rank=args.rank,
        engine=args.engine, backend=args.backend,
        constraints=constraint_summary(specs),
        warm=warm_info,
        stream_seconds=stream_s,
        platform=jax.default_backend(),
        **st,
    )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1)
        print(f"[json] wrote {args.json}")
    return summary


if __name__ == "__main__":
    main()
