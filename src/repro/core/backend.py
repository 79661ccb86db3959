"""Pluggable MTTKRP compute backends for the SPARTan ALS hot loop.

The ALS algebra (``core/parafac2.py``) never touches a kernel directly: it
asks an :class:`MttkrpBackend` for the per-bucket SPARTan contractions
and the shared stages. Four implementations:

``jnp``
    The pure-jnp math in :mod:`repro.core.spartan` — the reference path, exact
    in f64, used by the algebra tests.
``pallas``
    Dispatches through :mod:`repro.kernels.ops` — Mosaic kernels on TPU,
    ``interpret=True`` emulation elsewhere (a correctness tool, not a fast
    path off-TPU). Outputs are f32 accumulations; f64 inputs are demoted.
``scoo``
    The O(nnz) sparse route (:class:`SparseBackend`): on SCOO buckets
    (:class:`repro.core.irregular.SparseBucket`) every stage contracts the
    flat COO triplets directly via :mod:`repro.kernels.scoo` and the
    projected slices Y_k are NEVER materialized — ``project_bucket`` carries
    Q itself. CC buckets delegate to ``jnp``.
``fused``
    The fused ALS megakernel route (:mod:`repro.kernels.fused`): per CC
    bucket per iteration, four fused launches stream each subject's slab
    through VMEM with double-buffered DMA and write only the small
    [I,R]/[R,R]/[C,R] results — the projected Y_k is NEVER materialized
    (``project_bucket`` carries Q, like the SCOO route). SCOO buckets
    delegate to ``scoo``. ``dispatch_tally`` measures the collapse from the
    staged path's five streaming stage launches to four (four, not one,
    because the Procrustes eigendecomposition and the H-/V-solves are global
    sync points — see kernels/fused.py).
``auto``
    Per-bucket dispatch: SCOO buckets take the ``scoo`` native route; CC
    buckets go to ``fused`` on TPU for kernel-friendly geometry (f32/bf16
    with R a multiple of 8 and C a multiple of 128 — the MXU sublane/lane
    quanta the ``col_align=128`` bucketizer default produces), ``pallas``
    for the array-level CC contractions at the same geometry, and ``jnp``
    everywhere else, including all CPU/GPU runs.

Every backend also takes a ``precision`` knob ("f32" | "bf16" | "f16",
``Parafac2Options.precision`` / ``get_backend(name, precision)``): below
f32, the large streamed operands (the vals slab, Vg, and the staged Y_k)
are cast half-width before each contraction while every dot still
accumulates in f32 via ``kernels.common.accum_dtype`` — bf16 x bf16
products are exact in f32 (8-bit mantissas), so only the cast of the
inputs loses bits, and the streamed HBM bytes halve. ``precision="f32"``
is bitwise-identical to the historical paths.

Two API levels. The *bucket-level* stages (``xkv_bucket`` /
``project_bucket`` / ``ykv_bucket`` / ``mode{1,2,3}_bucket``) are what
``als_step`` calls: they take the bucket itself, so a backend can pick a
representation per device format — this is where the CC-vs-SCOO split lives,
and why a mixed-format ``Bucketed`` (``bucketize(format="auto")``) runs
every engine/backend/constraint combination unchanged. The *array-level*
methods (``mode1`` / ``mode2_compact`` / ``mode3`` / ``ykv`` on explicit
Yc/Vg arrays) remain the CC contraction contract the kernel parity tests
and micro benchmarks exercise.

The backend layer is also the single place the ``"subjects"`` logical-axis
sharding constraints (:func:`repro.dist.sharding.shard`) are applied: every
Kb-leading input and output passes through :meth:`MttkrpBackend.shard_subjects`
uniformly, instead of ad-hoc ``shard`` calls scattered through the math. The
memory-bound :meth:`MttkrpBackend.mode2_scatter` (the sum into J-space of
each column's compact rows, :mod:`repro.kernels.segsum`) is a shared stage
every backend reuses; :meth:`MttkrpBackend.ykv` (the Y_k V product the ALS
step computes once per bucket and feeds to the mode-1/mode-3 reuse entry
points and the fit) dispatches per backend like the modes do.

Select via ``Parafac2Options(backend=...)`` or ``--backend`` on the launchers
and benchmarks. See docs/ARCHITECTURE.md (stage 4½ and the SCOO stage).
"""
from __future__ import annotations

import abc
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import spartan
from repro.core.irregular import SparseBucket
from repro.core.spans import _tick, dispatch_tally
from repro.dist.sharding import shard
from repro.kernels.common import (PRECISIONS, accum_dtype, compute_cast,
                                  fold_subject_mask)

__all__ = [
    "MttkrpBackend",
    "JnpBackend",
    "PallasBackend",
    "SparseBackend",
    "FusedBackend",
    "AutoBackend",
    "BACKENDS",
    "get_backend",
    "dispatch_tally",
]


class MttkrpBackend(abc.ABC):
    """The three SPARTan MTTKRP contractions, per bucket.

    Per-bucket shapes (Kb subjects, C kept-cols padded, rank R):
      Yc [Kb, R, C] compressed slices; Vg [Kb, C, R] gathered V rows;
      Wb [Kb, R] W rows; masks 1.0 = real, 0.0 = padding.
    Subclasses implement ``_mode1`` / ``_mode2_compact`` / ``_mode3``; the
    public methods add the uniform subject-axis sharding constraints.

    ``precision`` ("f32" default) below f32 stages the large streamed
    operands half-width via :func:`repro.kernels.common.compute_cast` while
    accumulating f32 (``accum_dtype``); "f32" keeps every path bitwise
    identical to the unconfigured backend.
    """

    name: str = "?"

    def __init__(self, precision: str = "f32"):
        if precision not in PRECISIONS:
            raise ValueError(
                f"unknown compute precision {precision!r}; "
                f"choose from {PRECISIONS}")
        self.precision = precision

    def _pc(self, x: Optional[jax.Array]) -> Optional[jax.Array]:
        """Cast a streamed operand to the compute precision (identity at
        "f32" — the configured-precision paths stay bitwise otherwise)."""
        return compute_cast(x, self.precision)

    def route(self, b, R: int) -> str:
        """Name of the implementation that runs bucket ``b``'s slab-streaming
        stages at rank ``R`` (the per-bucket route the drivers report)."""
        return self.name

    # -- uniform sharding ---------------------------------------------------
    @staticmethod
    def shard_subjects(x: Optional[jax.Array]) -> Optional[jax.Array]:
        """Constrain a Kb-leading array onto the "subjects" logical axis
        (no-op outside a mesh)."""
        if x is None:
            return None
        return shard(x, ("subjects",) + (None,) * (x.ndim - 1))

    # -- shared stages ------------------------------------------------------
    def ykv(self, Yc: jax.Array, Vg: jax.Array) -> jax.Array:
        """Y_k V [Kb, R, R] — the product the mode-1/mode-3 reuse paths and
        the fit computation share; the ALS step computes it once per bucket."""
        return jnp.einsum("krc,kcl->krl", spartan._f(Yc), spartan._f(Vg))

    mode2_scatter = staticmethod(spartan.mode2_scatter)

    # -- bucket-level stages (the als_step contract) ------------------------
    # These take the bucket itself so an implementation can pick a per-format
    # representation. The dense route below (CC buckets, and SCOO buckets
    # under the jnp/pallas backends, whose SparseBucket.project is an O(nnz)
    # segment-sum into the same compact Yc layout) materializes Yc [Kb,R,C];
    # SparseBackend overrides carry Q instead and never build Yc.

    def xkv_bucket(self, b, V: jax.Array,
                   Vg: Optional[jax.Array] = None) -> jax.Array:
        """X_k V [Kb, I_pad, R] — the Procrustes-step input."""
        if self.precision != "f32" and not isinstance(b, SparseBucket):
            Vg = b.gather_v(V) if Vg is None else Vg
            out = jnp.einsum(
                "kic,kcr->kir", self._pc(b.vals), self._pc(Vg),
                preferred_element_type=accum_dtype(b.vals))
            return self.shard_subjects(out)
        return self.shard_subjects(b.xk_times_v(V, Vg))

    def procrustes_b_bucket(self, b, H: jax.Array, Wb: jax.Array,
                            V: jax.Array, Vg: Optional[jax.Array] = None):
        """Step-1 pair for one bucket: (XkV [Kb,I,R], B [Kb,I,R]) with
        B_k = (X_k V * w_k) H^T — the Procrustes input. The staged default
        is xkv + a small einsum; the fused backend forms both in one slab
        pass."""
        _tick("procrustes_b")
        XkV = self.xkv_bucket(b, V, Vg)
        B = jnp.einsum("kir,lr->kil", XkV * Wb[:, None, :], H)
        return XkV, B

    def mode1_xkv_bucket(self, b, Q: jax.Array, XkV: jax.Array,
                         Wb: jax.Array) -> jax.Array:
        """Partial M1 [R,R] via the mode-1 reuse identity
        Y_k V = Q_k^T (X_k V) — no slab pass, but the [Kb,I,R] operands
        stream. The fused backend reduces M1 in the same dispatch that
        forms the per-subject YkV, which is never written back."""
        _tick("mode1")
        YkV = jnp.einsum("kir,kil->krl", Q, XkV)
        return self.mode1(None, None, Wb, b.subject_mask, YkV=YkV)

    def sketch_bucket(self, b, Omega: jax.Array,
                      Og: Optional[jax.Array] = None) -> jax.Array:
        """Y_k = X_k Ω [Kb, I_pad, S] — the randomized range-finder sketch
        (:mod:`repro.core.compress`). Same contraction as ``xkv_bucket`` with
        a wider right factor: tall-skinny MXU matmuls on CC buckets, O(nnz*S)
        segment-sums on SCOO buckets (the sketch never densifies them)."""
        from repro.kernels import sketch as _sketch

        return self.shard_subjects(_sketch.sketch_bucket(b, Omega, Og))

    def project_bucket(self, b, Q: jax.Array):
        """Per-bucket projected representation consumed by the *_bucket
        stages below: the compact Yc [Kb, R, C] on the dense route (staged
        half-width when ``precision`` is below f32)."""
        _tick("project")
        if self.precision != "f32" and not isinstance(b, SparseBucket):
            Yc = jnp.einsum(
                "kir,kic->krc", self._pc(Q), self._pc(b.vals),
                preferred_element_type=accum_dtype(b.vals))
            return self.shard_subjects(self._pc(Yc))
        return self.shard_subjects(b.project(Q))

    def ykv_bucket(self, b, proj, V: jax.Array) -> jax.Array:
        """Y_k V [Kb, R, R] for factor ``V`` (the W-update/fit G product)."""
        _tick("ykv")
        return self.ykv(proj, self._pc(b.gather_v(V)))

    def mode1_bucket(self, b, proj, Wb: jax.Array,
                     V: Optional[jax.Array] = None, *, YkV=None) -> jax.Array:
        if YkV is None:
            _tick("mode1")
        Vg = None if YkV is not None else self._pc(b.gather_v(V))
        return self.mode1(proj, Vg, Wb, b.subject_mask, YkV=YkV)

    def mode2_bucket(self, b, proj, H: jax.Array, Wb: jax.Array) -> jax.Array:
        _tick("mode2")
        return self.mode2_compact(proj, H, Wb, b.col_mask, b.subject_mask)

    def mode3_bucket(self, b, proj, H: jax.Array,
                     V: Optional[jax.Array] = None, *, YkV=None) -> jax.Array:
        if YkV is None:
            _tick("mode3")
        Vg = None if YkV is not None else self._pc(b.gather_v(V))
        return self.mode3(proj, Vg, H, b.subject_mask, YkV=YkV)

    # -- per-bucket contractions --------------------------------------------
    def mode1(self, Yc, Vg, Wb, subject_mask, *, YkV=None) -> jax.Array:
        """Partial M1 [R, R] = sum_k (Y_k V) * W(k,:). With ``YkV`` cached
        (mode1_reuse), Vg may be None and the gather+matmul is skipped."""
        Yc, Vg, Wb, subject_mask, YkV = map(
            self.shard_subjects, (Yc, Vg, Wb, subject_mask, YkV))
        return self._mode1(Yc, Vg, Wb, subject_mask, YkV=YkV)

    def mode2_compact(self, Yc, H, Wb, col_mask, subject_mask) -> jax.Array:
        """Compact A [Kb, C, R] = (Y_k^T H) * W(k,:); masked rows are 0."""
        Yc, Wb, col_mask, subject_mask = map(
            self.shard_subjects, (Yc, Wb, col_mask, subject_mask))
        return self.shard_subjects(
            self._mode2_compact(Yc, H, Wb, col_mask, subject_mask))

    def mode3(self, Yc, Vg, H, subject_mask, *, YkV=None) -> jax.Array:
        """Per-subject M3 rows [Kb, R] = coldot(H, Y_k V)."""
        Yc, Vg, subject_mask, YkV = map(
            self.shard_subjects, (Yc, Vg, subject_mask, YkV))
        return self.shard_subjects(self._mode3(Yc, Vg, H, subject_mask, YkV=YkV))

    @abc.abstractmethod
    def _mode1(self, Yc, Vg, Wb, subject_mask, *, YkV=None) -> jax.Array: ...

    @abc.abstractmethod
    def _mode2_compact(self, Yc, H, Wb, col_mask, subject_mask) -> jax.Array: ...

    @abc.abstractmethod
    def _mode3(self, Yc, Vg, H, subject_mask, *, YkV=None) -> jax.Array: ...

    # -- whole-tensor helpers (the one callsite shape per mode) -------------
    def mttkrp_mode1(self, buckets: Sequence, Ycs: Sequence[jax.Array],
                     V: jax.Array, W: jax.Array) -> jax.Array:
        """M1 [R, R] over all buckets, with W global [K, R]."""
        return sum(
            self.mode1(Yc, b.gather_v(V), jnp.take(W, b.subject_ids, 0),
                       b.subject_mask)
            for b, Yc in zip(buckets, Ycs))

    def mttkrp_mode2(self, buckets: Sequence, Ycs: Sequence[jax.Array],
                     H: jax.Array, W: jax.Array, J: int) -> jax.Array:
        """M2 [J, R]: compact compute stage per bucket + shared scatter."""
        M2 = jnp.zeros((J, H.shape[0]), H.dtype)
        for b, Yc in zip(buckets, Ycs):
            A = self.mode2_compact(Yc, H, jnp.take(W, b.subject_ids, 0),
                                   b.col_mask, b.subject_mask)
            M2 = M2 + self.mode2_scatter(A, b.cols, J).astype(M2.dtype)
        return M2

    def mttkrp_mode3(self, buckets: Sequence, Ycs: Sequence[jax.Array],
                     V: jax.Array, H: jax.Array, K: int) -> jax.Array:
        """M3 [K, R]: per-subject rows scattered to global subject ids."""
        M3 = jnp.zeros((K, H.shape[0]), H.dtype)
        for b, Yc in zip(buckets, Ycs):
            rows = self.mode3(Yc, b.gather_v(V), H, b.subject_mask)
            M3 = M3.at[b.subject_ids].add(rows.astype(M3.dtype))
        return M3


class JnpBackend(MttkrpBackend):
    """The :mod:`repro.core.spartan` math — today's numerics, exactly."""

    name = "jnp"

    def _mode1(self, Yc, Vg, Wb, subject_mask, *, YkV=None):
        return spartan.mode1_bucket(Yc, Vg, Wb, subject_mask, YkV=YkV)

    def _mode2_compact(self, Yc, H, Wb, col_mask, subject_mask):
        return spartan.mode2_bucket_compact(Yc, H, Wb, col_mask, subject_mask)

    def _mode3(self, Yc, Vg, H, subject_mask, *, YkV=None):
        return spartan.mode3_bucket(Yc, Vg, H, subject_mask, YkV=YkV)


class PallasBackend(MttkrpBackend):
    """Routes through the Pallas kernels (:mod:`repro.kernels.ops`).

    Mosaic on TPU; interpret mode elsewhere. Kernel accumulators are f32, so
    outputs come back f32 regardless of input dtype; f64 inputs are demoted
    to f32 on the way in (use ``jnp`` for f64 algebra).
    """

    name = "pallas"

    @staticmethod
    def _k32(x: Optional[jax.Array]) -> Optional[jax.Array]:
        if x is not None and x.dtype == jnp.float64:
            return x.astype(jnp.float32)
        return x

    def ykv(self, Yc, Vg):
        from repro.kernels import ops
        return ops.ykv(self._k32(Yc), self._k32(Vg))

    def _mode1(self, Yc, Vg, Wb, subject_mask, *, YkV=None):
        from repro.kernels import ops
        return ops.mttkrp_mode1(
            self._k32(Yc), self._k32(Vg), self._k32(Wb),
            subject_mask=self._k32(subject_mask), YkV=self._k32(YkV))

    def _mode2_compact(self, Yc, H, Wb, col_mask, subject_mask):
        from repro.kernels import ops
        return ops.mttkrp_mode2_compact(
            self._k32(Yc), self._k32(H), self._k32(Wb),
            col_mask=self._k32(col_mask), subject_mask=self._k32(subject_mask))

    def _mode3(self, Yc, Vg, H, subject_mask, *, YkV=None):
        from repro.kernels import ops
        return ops.mttkrp_mode3(
            self._k32(Yc), self._k32(Vg), self._k32(H),
            subject_mask=self._k32(subject_mask), YkV=self._k32(YkV))

    # SCOO buckets: the Pallas one-hot/scalar-prefetch segment-sum kernels
    # produce X_k V and the compact Yc (kernels/scoo.py); the per-stage CC
    # kernels then consume Yc exactly as for a CC bucket.
    def xkv_bucket(self, b, V, Vg=None):
        if isinstance(b, SparseBucket):
            from repro.kernels import scoo
            Vg = b.gather_v(V) if Vg is None else Vg
            return self.shard_subjects(scoo.xk_times_v(
                self._pc(self._k32(b.vals)), b.rows, b.lcols,
                self._pc(self._k32(Vg)), b.i_pad,
                nnz_counts=b.nnz_counts, use_pallas=True))
        return super().xkv_bucket(b, V, Vg)

    def project_bucket(self, b, Q):
        if isinstance(b, SparseBucket):
            from repro.kernels import scoo
            _tick("project")
            return self.shard_subjects(scoo.project(
                self._pc(self._k32(b.vals)), b.rows, b.lcols,
                self._k32(Q), b.c_pad,
                nnz_counts=b.nnz_counts, use_pallas=True))
        return super().project_bucket(b, Q)


class SparseBackend(MttkrpBackend):
    """The O(nnz) SCOO-native route (:mod:`repro.kernels.scoo`).

    On SCOO buckets the projected slices are never materialized:
    ``project_bucket`` returns Q itself and every downstream stage contracts
    the flat COO triplets directly (gather + segment-sum / outer-product
    accumulation). CC buckets — present in a mixed-format Bucketed from
    ``bucketize(format="auto")`` — delegate to the inner dense backend
    (``jnp`` by default), as do the array-level CC contraction methods.
    """

    name = "scoo"

    def __init__(self, inner: Optional[MttkrpBackend] = None,
                 precision: str = "f32"):
        super().__init__(precision)
        self._inner = inner if inner is not None else JnpBackend(precision)

    def route(self, b, R: int) -> str:
        if isinstance(b, SparseBucket):
            return self.name
        return self._inner.route(b, R)

    # -- array-level CC contract: delegate wholesale ------------------------
    def ykv(self, Yc, Vg):
        return self._inner.ykv(Yc, Vg)

    def _mode1(self, Yc, Vg, Wb, subject_mask, *, YkV=None):
        return self._inner._mode1(Yc, Vg, Wb, subject_mask, YkV=YkV)

    def _mode2_compact(self, Yc, H, Wb, col_mask, subject_mask):
        return self._inner._mode2_compact(Yc, H, Wb, col_mask, subject_mask)

    def _mode3(self, Yc, Vg, H, subject_mask, *, YkV=None):
        return self._inner._mode3(Yc, Vg, H, subject_mask, YkV=YkV)

    # -- bucket-level stages: SCOO-native, Yc-free --------------------------
    def _ykv_native(self, b: SparseBucket, Q, V):
        from repro.kernels import scoo
        return scoo.ykv_scoo(self._pc(b.vals), b.rows, b.lcols,
                             self.shard_subjects(Q),
                             self._pc(b.gather_v(V)))

    def project_bucket(self, b, Q):
        if not isinstance(b, SparseBucket):
            return self._inner.project_bucket(b, Q)
        return self.shard_subjects(Q)   # carry Q; Yc is never built

    def ykv_bucket(self, b, proj, V):
        if not isinstance(b, SparseBucket):
            return self._inner.ykv_bucket(b, proj, V)
        _tick("ykv")
        return self._ykv_native(b, proj, V)

    def mode1_bucket(self, b, proj, Wb, V=None, *, YkV=None):
        if not isinstance(b, SparseBucket):
            return self._inner.mode1_bucket(b, proj, Wb, V, YkV=YkV)
        if YkV is None:
            _tick("mode1")
            YkV = self._ykv_native(b, proj, V)
        # YkV in hand, the remaining Hadamard + subject reduction is the
        # shared R x R algebra (uniform shard constraints included)
        return self.mode1(None, None, Wb, b.subject_mask, YkV=YkV)

    def mode2_bucket(self, b, proj, H, Wb):
        if not isinstance(b, SparseBucket):
            return self._inner.mode2_bucket(b, proj, H, Wb)
        from repro.kernels import scoo
        _tick("mode2")
        Q, Wb, col_mask, smask = map(
            self.shard_subjects, (proj, Wb, b.col_mask, b.subject_mask))
        return self.shard_subjects(scoo.mode2_compact_scoo(
            self._pc(b.vals), b.rows, b.lcols, Q, H, Wb, col_mask, smask,
            cperm=b.cperm, col_ends=b.col_ends))

    def mode3_bucket(self, b, proj, H, V=None, *, YkV=None):
        if not isinstance(b, SparseBucket):
            return self._inner.mode3_bucket(b, proj, H, V, YkV=YkV)
        if YkV is None:
            _tick("mode3")
            YkV = self._ykv_native(b, proj, V)
        return self.mode3(None, None, H, b.subject_mask, YkV=YkV)


class FusedBackend(MttkrpBackend):
    """The fused ALS megakernel route (:mod:`repro.kernels.fused`).

    On CC buckets the four per-iteration streaming launches each pull the
    subject's [I_pad, C_pad] slab through VMEM with double-buffered DMA and
    write only the small results back; the projected slices are never
    materialized — ``project_bucket`` carries Q itself, exactly like the
    SCOO-native route (so the ``als_step`` contract is unchanged). SCOO
    buckets delegate wholesale to :class:`SparseBackend`; the array-level
    CC contraction methods (explicit Yc in hand) delegate to ``jnp``.

    Unlike :class:`PallasBackend` there is no f64 demotion: f64 inputs
    accumulate f64 (``accum_dtype``), which the interpret-mode parity tests
    rely on. Real TPUs reject f64 Mosaic kernels — ``AutoBackend._fused_ok``
    gates the automatic route to f32/bf16 there.
    """

    name = "fused"

    def __init__(self, precision: str = "f32"):
        super().__init__(precision)
        self._jnp = JnpBackend(precision)
        self._sparse = SparseBackend(inner=self._jnp, precision=precision)

    def route(self, b, R: int) -> str:
        if isinstance(b, SparseBucket):
            return self._sparse.route(b, R)
        return self.name

    @staticmethod
    def _interp() -> bool:
        from repro.kernels import fused
        return fused._interpret()

    # -- array-level CC contract: delegate to jnp ---------------------------
    def ykv(self, Yc, Vg):
        return self._jnp.ykv(Yc, Vg)

    def _mode1(self, Yc, Vg, Wb, subject_mask, *, YkV=None):
        return self._jnp._mode1(Yc, Vg, Wb, subject_mask, YkV=YkV)

    def _mode2_compact(self, Yc, H, Wb, col_mask, subject_mask):
        return self._jnp._mode2_compact(Yc, H, Wb, col_mask, subject_mask)

    def _mode3(self, Yc, Vg, H, subject_mask, *, YkV=None):
        return self._jnp._mode3(Yc, Vg, H, subject_mask, YkV=YkV)

    # -- bucket-level stages: the four fused launches -----------------------
    def procrustes_b_bucket(self, b, H, Wb, V, Vg=None):
        if isinstance(b, SparseBucket):
            return self._sparse.procrustes_b_bucket(b, H, Wb, V, Vg)
        from repro.kernels import fused
        _tick("procrustes_b")
        Vg = b.gather_v(V) if Vg is None else Vg
        XkV, B = fused.fused_procrustes_b(
            self._pc(b.vals), self._pc(Vg), Wb, H, interpret=self._interp())
        return self.shard_subjects(XkV), self.shard_subjects(B)

    def project_bucket(self, b, Q):
        if isinstance(b, SparseBucket):
            return self._sparse.project_bucket(b, Q)
        return self.shard_subjects(Q)   # carry Q; Yc is never built

    def mode1_xkv_bucket(self, b, Q, XkV, Wb):
        from repro.kernels import fused
        _tick("mode1")
        Wb = fold_subject_mask(Wb, b.subject_mask)
        return fused.fused_mode1_xkv(Q, XkV, Wb, interpret=self._interp())

    def ykv_bucket(self, b, proj, V):
        if isinstance(b, SparseBucket):
            return self._sparse.ykv_bucket(b, proj, V)
        from repro.kernels import fused
        _tick("ykv")
        return self.shard_subjects(fused.fused_ykv(
            self._pc(b.vals), proj, self._pc(b.gather_v(V)),
            interpret=self._interp()))

    def mode1_bucket(self, b, proj, Wb, V=None, *, YkV=None):
        if isinstance(b, SparseBucket):
            return self._sparse.mode1_bucket(b, proj, Wb, V, YkV=YkV)
        if YkV is None:
            YkV = self.ykv_bucket(b, proj, V)
        return self.mode1(None, None, Wb, b.subject_mask, YkV=YkV)

    def mode2_bucket(self, b, proj, H, Wb):
        if isinstance(b, SparseBucket):
            return self._sparse.mode2_bucket(b, proj, H, Wb)
        from repro.kernels import fused
        _tick("mode2")
        Q, Wb_m, cm = map(self.shard_subjects,
                          (proj, fold_subject_mask(Wb, b.subject_mask),
                           b.col_mask))
        return self.shard_subjects(fused.fused_mode2_compact(
            self._pc(b.vals), Q, H, Wb_m, cm, interpret=self._interp()))

    def mode3_bucket(self, b, proj, H, V=None, *, YkV=None):
        if isinstance(b, SparseBucket):
            return self._sparse.mode3_bucket(b, proj, H, V, YkV=YkV)
        if YkV is None:
            YkV = self.ykv_bucket(b, proj, V)
        # YkV in hand, mode-3 is the shared [R,R] coldot — no slab pass left
        return self.mode3(None, None, H, b.subject_mask, YkV=YkV)


class AutoBackend(MttkrpBackend):
    """Per-platform, per-bucket dispatch between jnp, pallas, and scoo.

    The decision is made at trace time from static bucket types and
    shapes/dtypes, so under jit each bucket compiles against exactly one
    implementation. SCOO buckets always take the O(nnz) native route
    (:class:`SparseBackend` — the format was chosen *because* the bucket is
    sparse, so the dense kernels are never the right answer for it); CC
    buckets the kernels handle poorly (odd R/C, f64, non-TPU platforms)
    fall back to jnp.
    """

    name = "auto"

    def __init__(self, precision: str = "f32"):
        super().__init__(precision)
        self._jnp = JnpBackend(precision)
        self._pallas = PallasBackend(precision)
        self._sparse = SparseBackend(inner=self._jnp, precision=precision)
        self._fused = FusedBackend(precision)

    def _fused_ok(self, b, R: int) -> bool:
        """Route a CC bucket through the fused megakernel stages: TPU,
        f32/bf16 (Mosaic rejects f64), and MXU-quantized geometry. The
        predicate is a function of static bucket shape/dtype and R only, so
        every stage of an iteration makes the SAME call — the projected
        representation (Q on the fused route, Yc on the staged one) must
        stay coherent across ``project_bucket`` and its consumers."""
        return (not isinstance(b, SparseBucket)
                and jax.default_backend() == "tpu"
                and b.vals.dtype != jnp.float64
                and R % 8 == 0 and b.c_pad % 128 == 0)

    def route(self, b, R: int) -> str:
        if isinstance(b, SparseBucket):
            return self._sparse.route(b, R)
        if self._fused_ok(b, R):
            return self._fused.route(b, R)
        return self._jnp.route(b, R)

    # -- bucket-level: SCOO -> native sparse; friendly CC on TPU -> fused ---
    def xkv_bucket(self, b, V, Vg=None):
        if isinstance(b, SparseBucket):
            return self._sparse.xkv_bucket(b, V, Vg)
        return super().xkv_bucket(b, V, Vg)

    def procrustes_b_bucket(self, b, H, Wb, V, Vg=None):
        if isinstance(b, SparseBucket):
            return self._sparse.procrustes_b_bucket(b, H, Wb, V, Vg)
        if self._fused_ok(b, H.shape[0]):
            return self._fused.procrustes_b_bucket(b, H, Wb, V, Vg)
        return super().procrustes_b_bucket(b, H, Wb, V, Vg)

    def mode1_xkv_bucket(self, b, Q, XkV, Wb):
        if not isinstance(b, SparseBucket) and self._fused_ok(b, Q.shape[-1]):
            return self._fused.mode1_xkv_bucket(b, Q, XkV, Wb)
        return super().mode1_xkv_bucket(b, Q, XkV, Wb)

    def project_bucket(self, b, Q):
        if isinstance(b, SparseBucket):
            return self._sparse.project_bucket(b, Q)
        if self._fused_ok(b, Q.shape[-1]):
            return self._fused.project_bucket(b, Q)
        return super().project_bucket(b, Q)

    def ykv_bucket(self, b, proj, V):
        if isinstance(b, SparseBucket):
            return self._sparse.ykv_bucket(b, proj, V)
        if self._fused_ok(b, V.shape[-1]):
            return self._fused.ykv_bucket(b, proj, V)
        return super().ykv_bucket(b, proj, V)

    def mode1_bucket(self, b, proj, Wb, V=None, *, YkV=None):
        if isinstance(b, SparseBucket):
            return self._sparse.mode1_bucket(b, proj, Wb, V, YkV=YkV)
        if self._fused_ok(b, Wb.shape[-1]):
            return self._fused.mode1_bucket(b, proj, Wb, V, YkV=YkV)
        return super().mode1_bucket(b, proj, Wb, V, YkV=YkV)

    def mode2_bucket(self, b, proj, H, Wb):
        if isinstance(b, SparseBucket):
            return self._sparse.mode2_bucket(b, proj, H, Wb)
        if self._fused_ok(b, H.shape[0]):
            return self._fused.mode2_bucket(b, proj, H, Wb)
        return super().mode2_bucket(b, proj, H, Wb)

    def mode3_bucket(self, b, proj, H, V=None, *, YkV=None):
        if isinstance(b, SparseBucket):
            return self._sparse.mode3_bucket(b, proj, H, V, YkV=YkV)
        if self._fused_ok(b, H.shape[0]):
            return self._fused.mode3_bucket(b, proj, H, V, YkV=YkV)
        return super().mode3_bucket(b, proj, H, V, YkV=YkV)

    @staticmethod
    def _platform_ok(probe: Optional[jax.Array]) -> bool:
        return (probe is not None and jax.default_backend() == "tpu"
                and probe.dtype != jnp.float64)

    @classmethod
    def _kernel_friendly(cls, probe: Optional[jax.Array]) -> bool:
        """Full C-contraction kernels: want R on the sublane quantum and the
        kept-column count C on the lane quantum (col_align=128 default)."""
        if not cls._platform_ok(probe):
            return False
        R, C = probe.shape[-2], probe.shape[-1]
        return R % 8 == 0 and C % 128 == 0

    @classmethod
    def _reuse_friendly(cls, YkV: Optional[jax.Array]) -> bool:
        """YkV-cached kernels only touch [Kb,R,R] tiles (VPU reductions), so
        only the sublane quantum matters — Mosaic lane-pads the small R."""
        if not cls._platform_ok(YkV):
            return False
        return YkV.shape[-1] % 8 == 0

    def _pick(self, probe, *, reuse: bool = False) -> MttkrpBackend:
        ok = self._reuse_friendly(probe) if reuse else self._kernel_friendly(probe)
        return self._pallas if ok else self._jnp

    def ykv(self, Yc, Vg):
        return self._pick(Yc).ykv(Yc, Vg)

    def _mode1(self, Yc, Vg, Wb, subject_mask, *, YkV=None):
        if YkV is not None:
            return self._pick(YkV, reuse=True)._mode1(
                Yc, Vg, Wb, subject_mask, YkV=YkV)
        return self._pick(Yc)._mode1(Yc, Vg, Wb, subject_mask, YkV=None)

    def _mode2_compact(self, Yc, H, Wb, col_mask, subject_mask):
        return self._pick(Yc)._mode2_compact(Yc, H, Wb, col_mask, subject_mask)

    def _mode3(self, Yc, Vg, H, subject_mask, *, YkV=None):
        if YkV is not None:
            return self._pick(YkV, reuse=True)._mode3(
                Yc, Vg, H, subject_mask, YkV=YkV)
        return self._pick(Yc)._mode3(Yc, Vg, H, subject_mask, YkV=None)


BACKENDS = {"jnp": JnpBackend(), "pallas": PallasBackend(),
            "scoo": SparseBackend(), "fused": FusedBackend(),
            "auto": AutoBackend()}

# configured (non-f32 precision) instances, cached per (name, precision) so
# repeated get_backend calls hand jit the SAME backend object (stable tracing)
_CONFIGURED: Dict[Tuple[str, str], MttkrpBackend] = {}


def get_backend(name, precision: Optional[str] = None) -> MttkrpBackend:
    """Resolve a backend by name ("jnp" | "pallas" | "scoo" | "fused" |
    "auto") or pass an :class:`MttkrpBackend` instance through unchanged.
    ``precision`` (None/"f32" default) returns a configured instance that
    stages streamed operands at that compute precision (see the class docs);
    the f32 singletons in ``BACKENDS`` are untouched."""
    if isinstance(name, MttkrpBackend):
        return name
    if name not in BACKENDS:
        raise ValueError(
            f"unknown MTTKRP backend {name!r}; choose from {sorted(BACKENDS)}")
    if precision is None or precision == "f32":
        return BACKENDS[name]
    key = (name, precision)
    if key not in _CONFIGURED:
        _CONFIGURED[key] = type(BACKENDS[name])(precision=precision)
    return _CONFIGURED[key]
