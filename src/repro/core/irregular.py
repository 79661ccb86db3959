"""Device-side irregular-tensor formats.

Two formats, both static-shape (XLA) and bucketed (see repro.sparse.bucketing):

* **CC (compressed columns)** — each subject slice X_k (I_k x J) is stored
  *dense over its nonzero columns*: ``vals[k] in R^{I_pad x C_pad}`` plus the
  global column ids ``cols[k] in {0..J-1}^{C_pad}``. This is the functional
  format for all SPARTan math: every identity in the paper becomes a gather
  of V-rows plus a small dense matmul (MXU-shaped). Cost per iteration:
  O(Kb * I_pad * C_pad * R) regardless of the true nonzero count.

* **SCOO (sorted flat COO)** — each subject's nonzeros as flat triplets
  ``vals[k] in R^{N_pad}`` + local ``rows``/``lcols`` indices, sorted
  row-major and padded to the bucket-wide N_pad (subject-aligned padding:
  every subject owns exactly one N_pad segment, so the flat nnz axis is just
  the [Kb, N_pad] leading-axis layout and ``nnz_offsets`` are uniform). The
  kept-column ids/mask are shared with CC, so the projected slices Y_k land
  in the identical compact [R, C_pad] layout. Every contraction is a
  gather + segment-sum in O(nnz * R) — see :mod:`repro.kernels.scoo`. This
  is the format for genuinely sparse buckets (EHR-like ~1% intra-slice
  density), where CC's densified rectangle burns ~100x the FLOPs and HBM.

``bucketize(format=...)`` picks per bucket: ``"cc"`` / ``"scoo"`` force one
format, ``"auto"`` routes each bucket by its measured density (nonzeros over
the densified CC cell count) through :func:`repro.sparse.bucketing.
route_formats`. A :class:`Bucketed` value may therefore mix Bucket and
SparseBucket children; both are pytrees usable under jit/pjit, and subjects
shard along the leading Kb axis of every per-bucket array — the "subjects"
rule in :mod:`repro.dist.sharding`. See docs/ARCHITECTURE.md (stage 2) for
where these formats sit in the end-to-end data flow.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.spans import span
from repro.sparse.coo import IrregularCOO
from repro.sparse.bucketing import BucketPlan, plan_layout, route_formats
from repro.sparse.bucketing import SCOO_DENSITY_THRESHOLD

__all__ = ["Bucket", "SparseBucket", "Bucketed", "bucketize", "bucket_format",
           "cc_bucket_like", "FORMATS"]

FORMATS = ("cc", "scoo", "auto")  # bucketize(format=...) choices


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Bucket:
    """One static-shape bucket of subjects in CC format.

    vals:        f[Kb, I_pad, C_pad]  dense values over kept columns
    cols:        i32[Kb, C_pad]       global column id per kept column (pad: 0)
    col_mask:    f[Kb, C_pad]         1.0 for real kept columns, 0.0 for padding
    subject_ids: i32[Kb]              global subject index (row into W)
    subject_mask:f[Kb]                1.0 real subject, 0.0 padding subject
    row_counts:  i32[Kb]              true I_k (informational; padded rows are 0)
    """

    vals: jax.Array
    cols: jax.Array
    col_mask: jax.Array
    subject_ids: jax.Array
    subject_mask: jax.Array
    row_counts: jax.Array

    format = "cc"  # class tag, not a field (see bucket_format)

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        children = (
            self.vals,
            self.cols,
            self.col_mask,
            self.subject_ids,
            self.subject_mask,
            self.row_counts,
        )
        return children, None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- shape helpers -----------------------------------------------------
    @property
    def kb(self) -> int:
        return self.vals.shape[0]

    @property
    def i_pad(self) -> int:
        return self.vals.shape[1]

    @property
    def c_pad(self) -> int:
        return self.vals.shape[2]

    # -- core contractions (all batched over Kb) ----------------------------
    def gather_v(self, V: jax.Array) -> jax.Array:
        """V-rows for this bucket's kept columns: [Kb, C_pad, R] (pad rows 0)."""
        Vg = jnp.take(V, self.cols, axis=0)  # [Kb, C_pad, R]
        return Vg * self.col_mask[..., None]

    def xk_times_v(self, V: jax.Array, Vg: Optional[jax.Array] = None) -> jax.Array:
        """X_k V for every subject: [Kb, I_pad, R]. The paper's column-sparsity
        exploitation: only V rows of kept columns participate."""
        if Vg is None:
            Vg = self.gather_v(V)
        return jnp.einsum("kic,kcr->kir", self.vals, Vg, preferred_element_type=self.vals.dtype)

    def project(self, Q: jax.Array) -> jax.Array:
        """Y_k = Q_k^T X_k in CC format: [Kb, R, C_pad]; shares self.cols.

        This is the paper's key structural observation: Y_k inherits exactly
        the column-sparsity pattern of X_k.
        """
        return jnp.einsum("kir,kic->krc", Q, self.vals, preferred_element_type=self.vals.dtype)

    def sq_norms(self) -> jax.Array:
        """Per-subject ||X_k||_F^2 [Kb] (padding slots contribute 0) — the
        streaming update path's residual bookkeeping needs the norm per
        subject, not just the dataset-wide ``Bucketed.norm_sq``."""
        return jnp.sum(self.vals * self.vals, axis=(1, 2))

    def scatter_cols_to_dense(self, compact: jax.Array, J: int) -> jax.Array:
        """Expand a CC matrix [Kb, *, C_pad] back to dense [Kb, *, J] (tests)."""
        Kb, mid, Cp = compact.shape
        out = jnp.zeros((Kb, mid, J), compact.dtype)
        k_idx = jnp.arange(Kb)[:, None, None]
        m_idx = jnp.arange(mid)[None, :, None]
        c_idx = self.cols[:, None, :]
        return out.at[k_idx, m_idx, c_idx].add(compact * self.col_mask[:, None, :])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SparseBucket:
    """One static-shape bucket of subjects in SCOO (sorted flat COO) format.

    vals:        f[Kb, N_pad]        nonzero values, row-major sorted per
                                     subject (pad entries 0 — they vanish in
                                     every segment-sum)
    rows:        i32[Kb, N_pad]      local row index in the I_pad row space
                                     (pad: 0 — harmless, its value is 0)
    lcols:       i32[Kb, N_pad]      local kept-column slot in [0, C_pad)
    row_ends:    i32[Kb, I_pad]      CSR-style pointers: one past row i's
                                     last triplet (pads excluded)
    cperm:       i32[Kb, N_pad]      permutation into column-sorted order
                                     (pads stay at the tail)
    col_ends:    i32[Kb, C_pad]      CSC-style pointers into the cperm view
    cols:        i32[Kb, C_pad]      global column id per kept column (pad: 0)
    col_mask:    f[Kb, C_pad]        1.0 for real kept columns
    subject_ids: i32[Kb]             global subject index (row into W)
    subject_mask:f[Kb]               1.0 real subject, 0.0 padding subject
    row_counts:  i32[Kb]             true I_k
    nnz_counts:  i32[Kb]             true nnz_k (<= N_pad; pad subjects 0)
    n_rows_pad:  int (static)        I_pad — the padded row space Q/XkV use

    Subject-aligned padding makes the per-subject flat offsets uniform
    (``nnz_offsets`` is just ``arange(Kb) * N_pad``), so the triplet arrays
    are [Kb, N_pad] with subjects on the leading axis — the same sharding
    story as CC. The sorted order plus the precomputed row/column segment
    boundaries make every segment-sum a cumsum + gather + diff — no
    scatter-add on the hot path (repro.kernels.scoo). The kept-column
    metadata (cols/col_mask) is shared with CC, so ``project`` lands in the
    identical compact Yc layout and every downstream MTTKRP stage is
    format-agnostic.
    """

    vals: jax.Array
    rows: jax.Array
    lcols: jax.Array
    row_ends: jax.Array
    cperm: jax.Array
    col_ends: jax.Array
    cols: jax.Array
    col_mask: jax.Array
    subject_ids: jax.Array
    subject_mask: jax.Array
    row_counts: jax.Array
    nnz_counts: jax.Array
    n_rows_pad: int  # static aux (not derivable from the triplet shapes)

    format = "scoo"

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        children = (
            self.vals, self.rows, self.lcols, self.row_ends, self.cperm,
            self.col_ends, self.cols, self.col_mask,
            self.subject_ids, self.subject_mask, self.row_counts,
            self.nnz_counts,
        )
        return children, (self.n_rows_pad,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n_rows_pad=aux[0])

    # -- shape helpers -----------------------------------------------------
    @property
    def kb(self) -> int:
        return self.vals.shape[0]

    @property
    def i_pad(self) -> int:
        return self.n_rows_pad

    @property
    def c_pad(self) -> int:
        return self.cols.shape[1]

    @property
    def n_pad(self) -> int:
        return self.vals.shape[1]

    @property
    def nnz_offsets(self) -> jax.Array:
        """Per-subject start offset into the flattened nnz axis — uniform
        because the padding is subject-aligned."""
        return jnp.arange(self.kb, dtype=jnp.int32) * self.n_pad

    # -- core contractions (all batched over Kb, all O(nnz * R)) ------------
    def gather_v(self, V: jax.Array) -> jax.Array:
        """V-rows for this bucket's kept columns: [Kb, C_pad, R] (pad rows 0).
        Identical to CC — the kept-column metadata is shared."""
        Vg = jnp.take(V, self.cols, axis=0)
        return Vg * self.col_mask[..., None]

    def xk_times_v(self, V: jax.Array, Vg: Optional[jax.Array] = None) -> jax.Array:
        """X_k V for every subject: [Kb, I_pad, R] — gather-from-V +
        sorted segment-sum over rows (repro.kernels.scoo)."""
        from repro.kernels import scoo

        if Vg is None:
            Vg = self.gather_v(V)
        return scoo.xk_times_v(self.vals, self.rows, self.lcols, Vg,
                               self.i_pad, row_ends=self.row_ends)

    def project(self, Q: jax.Array) -> jax.Array:
        """Y_k = Q_k^T X_k: [Kb, R, C_pad] — gather-from-Q + sorted
        segment-sum over kept columns; shares self.cols, so the output is
        the CC Yc layout."""
        from repro.kernels import scoo

        return scoo.project(self.vals, self.rows, self.lcols, Q, self.c_pad,
                            cperm=self.cperm, col_ends=self.col_ends)

    def sq_norms(self) -> jax.Array:
        """Per-subject ||X_k||_F^2 [Kb] — pad triplets are 0-valued, so the
        flat sum needs no masking (same contract as :meth:`Bucket.sq_norms`)."""
        return jnp.sum(self.vals * self.vals, axis=1)

    def dense_vals(self) -> jax.Array:
        """Materialize the CC vals rectangle [Kb, I_pad, C_pad] (tests)."""
        Kb, _ = self.vals.shape
        out = jnp.zeros((Kb, self.i_pad, self.c_pad), self.vals.dtype)
        k_idx = jnp.arange(Kb)[:, None]
        return out.at[k_idx, self.rows, self.lcols].add(self.vals)

    def scatter_cols_to_dense(self, compact: jax.Array, J: int) -> jax.Array:
        """Expand a compact matrix [Kb, *, C_pad] back to dense [Kb, *, J]
        (tests) — same column metadata as CC."""
        Kb, mid, Cp = compact.shape
        out = jnp.zeros((Kb, mid, J), compact.dtype)
        k_idx = jnp.arange(Kb)[:, None, None]
        m_idx = jnp.arange(mid)[None, :, None]
        c_idx = self.cols[:, None, :]
        return out.at[k_idx, m_idx, c_idx].add(compact * self.col_mask[:, None, :])


def bucket_format(b) -> str:
    """Device-format tag of a bucket: "cc" | "scoo"."""
    return getattr(b, "format", "cc")


def cc_bucket_like(b, vals: jax.Array,
                   row_counts: Optional[jax.Array] = None) -> Bucket:
    """A CC :class:`Bucket` holding ``vals`` [Kb, I', C_pad] under ``b``'s
    column/subject metadata (``b`` may be CC or SCOO — the metadata contract
    is shared). The row space I' may differ from ``b.i_pad``: this is how the
    compression stage (:mod:`repro.core.compress`) wraps the small cores
    ``G_k = P_k^T X_k`` as an ordinary bucket the engines iterate on.
    """
    if vals.shape[0] != b.kb or vals.shape[2] != b.c_pad:
        raise ValueError(
            f"vals shape {vals.shape} does not match bucket metadata "
            f"(Kb={b.kb}, C_pad={b.c_pad})")
    return Bucket(
        vals=vals, cols=b.cols, col_mask=b.col_mask,
        subject_ids=b.subject_ids, subject_mask=b.subject_mask,
        row_counts=b.row_counts if row_counts is None else row_counts)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Bucketed:
    """A bucketed irregular tensor: static-shape buckets + global metadata.

    Registered as a pytree (buckets are children; K/J/norm_sq are static aux)
    so the whole dataset is a jit/pjit argument — the dry-run lowers als_step
    against ShapeDtypeStruct buckets with subjects sharded over (pod, data).
    """

    buckets: List[Bucket]
    n_subjects: int          # K (true count, before subject padding)
    n_cols: int              # J
    norm_sq: float           # ||X||_F^2 over all subjects (for fit computation)

    def tree_flatten(self):
        return (self.buckets,), (self.n_subjects, self.n_cols, self.norm_sq)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(buckets=children[0], n_subjects=aux[0], n_cols=aux[1], norm_sq=aux[2])

    def tree_buckets(self) -> List[Bucket]:
        return self.buckets


def _pad_to(n: int, align: int) -> int:
    return max(align, ((n + align - 1) // align) * align)


def _staging_dtype(dtype) -> np.dtype:
    """Host staging-buffer dtype for device values of ``dtype``: f64 only
    when f64 is actually requested; every other float (f32, bf16, f16, ...)
    stages in f32 and is cast ONCE at device upload. (The old check compared
    against f32 only, silently staging bf16/f16 requests in f64.)"""
    if jnp.dtype(dtype) == jnp.float64:
        return np.dtype(np.float64)
    return np.dtype(np.float32)


def bucketize(
    data: IrregularCOO,
    *,
    max_buckets: int = 4,
    row_align: int = 8,
    col_align: int = 128,
    subject_align: int = 1,
    nnz_align: int = 8,
    dtype=jnp.float32,
    plan: Optional[BucketPlan] = None,
    format: str = "cc",
    formats: Optional[Sequence[str]] = None,
    density_threshold: float = SCOO_DENSITY_THRESHOLD,
) -> Bucketed:
    """Host-side conversion IrregularCOO -> Bucketed device format.

    ``format`` picks the per-bucket device layout: ``"cc"`` (dense over kept
    columns — the historical default), ``"scoo"`` (flat sorted COO triplets,
    O(nnz) algebra, each bucket padded to its members' largest nnz), or
    ``"auto"`` (each bucket routed by its measured density through
    :func:`repro.sparse.bucketing.route_formats`; below ``density_threshold``
    -> SCOO). ``formats`` overrides the routing with an explicit per-bucket
    list (must match ``plan``'s bucket count).

    ``subject_align`` pads each bucket's subject count to a multiple (use the
    data-parallel shard count so the leading axis divides evenly);
    ``nnz_align`` rounds SCOO buckets' per-subject N_pad.
    """
    if format not in FORMATS:
        raise ValueError(f"unknown format {format!r}; choose from {FORMATS}")
    rc = data.row_counts()
    cc = data.col_counts()
    nnzc = data.nnz_counts()
    if plan is None:
        plan, _ = plan_layout(
            rc, cc, nnzc, format=format, max_buckets=max_buckets,
            row_align=row_align, col_align=col_align, nnz_align=nnz_align,
            density_threshold=density_threshold)
    if formats is None:
        formats = route_formats(plan, nnzc, format=format,
                                density_threshold=density_threshold)
    if len(formats) != plan.n_buckets:
        raise ValueError(
            f"formats has {len(formats)} entries for {plan.n_buckets} buckets")
    stage = _staging_dtype(dtype)
    buckets: List = []
    for bi, ((i_pad, c_pad), members) in enumerate(zip(plan.shapes, plan.members)):
        with span("layout.pack"):
            kb = _pad_to(len(members), subject_align)
            fmt = formats[bi]
            cols = np.zeros((kb, c_pad), dtype=np.int32)
            cmask = np.zeros((kb, c_pad), dtype=stage)
            sids = np.zeros((kb,), dtype=np.int32)
            smask = np.zeros((kb,), dtype=stage)
            rows_n = np.zeros((kb,), dtype=np.int32)
            if fmt == "cc":
                vals = np.zeros((kb, i_pad, c_pad), dtype=stage)
            elif fmt == "scoo":
                if plan.nnz_pads is not None:
                    n_pad = plan.nnz_pads[bi]
                else:
                    n_pad = _pad_to(int(max((nnzc[k] for k in members),
                                            default=1)), nnz_align)
                vals = np.zeros((kb, n_pad), dtype=stage)
                trip_rows = np.zeros((kb, n_pad), dtype=np.int32)
                trip_lcols = np.zeros((kb, n_pad), dtype=np.int32)
                row_ends = np.zeros((kb, i_pad), dtype=np.int32)
                # pads keep identity slots at the tail of the col-sorted view;
                # their value is 0 and every col_end is <= nnz, so they never
                # land in a segment
                cperm = np.tile(np.arange(n_pad, dtype=np.int32), (kb, 1))
                col_ends = np.zeros((kb, c_pad), dtype=np.int32)
                nnz_n = np.zeros((kb,), dtype=np.int32)
            else:
                raise ValueError(f"unknown bucket format {fmt!r}")
            for slot, k in enumerate(members):
                s = data.subjects[k]
                kept = s.nonzero_cols()
                remap = {int(c): i for i, c in enumerate(kept)}
                local_c = np.asarray([remap[int(c)] for c in s.cols], dtype=np.int32)
                if fmt == "cc":
                    vals[slot, s.rows, local_c] = s.vals
                else:
                    # sorted flat COO: row-major (row, local col) order gives the
                    # segment-sums contiguous destination runs
                    order = np.lexsort((local_c, s.rows))
                    nz = s.nnz
                    if nz > vals.shape[1]:
                        raise ValueError(
                            f"subject {k} has {nz} nonzeros > bucket N_pad "
                            f"{vals.shape[1]} (stale plan?)")
                    rr, lc = s.rows[order], local_c[order]
                    vals[slot, :nz] = s.vals[order]
                    trip_rows[slot, :nz] = rr
                    trip_lcols[slot, :nz] = lc
                    # CSR/CSC-style boundaries for the scatter-free segment-sums
                    row_ends[slot] = np.searchsorted(rr, np.arange(i_pad),
                                                     side="right")
                    corder = np.lexsort((rr, lc)).astype(np.int32)
                    cperm[slot, :nz] = corder
                    col_ends[slot] = np.searchsorted(lc[corder], np.arange(c_pad),
                                                     side="right")
                    nnz_n[slot] = nz
                cols[slot, : kept.size] = kept
                cmask[slot, : kept.size] = 1.0
                sids[slot] = k
                smask[slot] = 1.0
                rows_n[slot] = s.n_rows
        with span("layout.to_device"):
            common = dict(
                cols=jnp.asarray(cols),
                col_mask=jnp.asarray(cmask, dtype=dtype),
                subject_ids=jnp.asarray(sids),
                subject_mask=jnp.asarray(smask, dtype=dtype),
                row_counts=jnp.asarray(rows_n),
            )
            if fmt == "cc":
                buckets.append(Bucket(vals=jnp.asarray(vals, dtype=dtype), **common))
            else:
                buckets.append(SparseBucket(
                    vals=jnp.asarray(vals, dtype=dtype),
                    rows=jnp.asarray(trip_rows),
                    lcols=jnp.asarray(trip_lcols),
                    row_ends=jnp.asarray(row_ends),
                    cperm=jnp.asarray(cperm),
                    col_ends=jnp.asarray(col_ends),
                    nnz_counts=jnp.asarray(nnz_n),
                    n_rows_pad=i_pad,
                    **common,
                ))
    return Bucketed(
        buckets=buckets,
        n_subjects=data.n_subjects,
        n_cols=data.n_cols,
        norm_sq=data.frobenius_sq(),
    )

