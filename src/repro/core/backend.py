"""The MTTKRP router of the SPARTan ALS hot loop: one router, three routes.

The ALS algebra (``core/parafac2.py``) never touches a kernel directly: it
asks a backend for the per-bucket SPARTan contractions and the shared
stages. ``Parafac2Options.backend`` / ``--backend`` names one of two:

``auto``
    :class:`AutoBackend`, the default. It picks one route per bucket from
    what it can observe: the bucket's format and dtype, the rank R, C_pad
    and the platform. The decision is made in :meth:`AutoBackend.route`
    and nowhere else.
``jnp``
    :class:`MttkrpBackend` itself: every bucket on the ``jnp`` route. The
    reference, exact in f64, used by the algebra tests.

The three routes:

``scoo``
    :class:`SparseBackend`, every SCOO bucket
    (:class:`repro.core.irregular.SparseBucket`): each stage contracts the
    flat COO triplets directly via :mod:`repro.kernels.scoo` (jnp gathers
    and segment sums), and the projected slices Y_k are never materialized
    (``project_bucket`` carries Q itself).
``fused``
    :class:`FusedBackend`, a CC bucket on a TPU at f32/bf16 with R a
    multiple of 8 and C_pad a multiple of 128 (the MXU sublane/lane quanta
    the ``col_align=128`` bucketizer default produces): four fused Pallas
    slab passes per bucket per iteration (:mod:`repro.kernels.fused`), Y_k
    never materialized. Four dispatches, not one, because the Procrustes
    eigendecomposition and the H-/V-solves are global sync points.
``jnp``
    :class:`MttkrpBackend`, every other CC bucket: the :mod:`repro.core.
    spartan` math on the materialized compact Yc [Kb, R, C_pad].

Every backend also takes a ``precision`` knob ("f32" | "bf16" | "f16",
``Parafac2Options.precision`` / ``get_backend(name, precision)``): below
f32, the large streamed operands (the vals slab, Vg, and the staged Y_k)
are cast half-width before each contraction while every dot still
accumulates in f32 via ``kernels.common.accum_dtype`` — bf16 x bf16
products are exact in f32 (8-bit mantissas), so only the cast of the
inputs loses bits, and the streamed HBM bytes halve. ``precision="f32"``
is bitwise-identical to the historical paths.

Two API levels. The *bucket-level* stages (``xkv_bucket`` /
``procrustes_b_bucket`` / ``project_bucket`` / ``ykv_bucket`` /
``mode1_xkv_bucket`` / ``mode{1,2,3}_bucket``) are what ``als_step`` calls:
they take the bucket itself, which is where the router reads its format.
The *array-level* methods (``mode1`` / ``mode2_compact`` / ``mode3`` /
``ykv`` on explicit Yc/Vg arrays) are the jnp CC contraction contract, and
every route reduces its [Kb, R, R] Y_k V products through them.

The backend layer is also the single place the ``"subjects"`` logical-axis
sharding constraints (:func:`repro.dist.sharding.shard`) are applied: every
Kb-leading input and output passes through :meth:`MttkrpBackend.shard_subjects`
uniformly, instead of ad-hoc ``shard`` calls scattered through the math. The
memory-bound :meth:`MttkrpBackend.mode2_scatter` (the sum into J-space of
each column's compact rows, :mod:`repro.kernels.segsum`) is a shared stage
every route reuses. See docs/ARCHITECTURE.md (stage 4½ and the SCOO stage).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import spartan
from repro.core.irregular import SparseBucket
from repro.core.spans import _tick, dispatch_tally
from repro.dist.sharding import shard
from repro.kernels import fused, scoo
from repro.kernels.common import (PRECISIONS, accum_dtype, compute_cast,
                                  fold_subject_mask)

__all__ = [
    "MttkrpBackend",
    "SparseBackend",
    "FusedBackend",
    "AutoBackend",
    "BACKENDS",
    "get_backend",
    "dispatch_tally",
]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


class MttkrpBackend:
    """The three SPARTan MTTKRP contractions, per bucket: the ``jnp`` route,
    and the base the other routes override stage by stage.

    Per-bucket shapes (Kb subjects, C kept-cols padded, rank R):
      Yc [Kb, R, C] compressed slices; Vg [Kb, C, R] gathered V rows;
      Wb [Kb, R] W rows; masks 1.0 = real, 0.0 = padding.

    ``precision`` ("f32" default) below f32 stages the large streamed
    operands half-width via :func:`repro.kernels.common.compute_cast` while
    accumulating f32 (``accum_dtype``); "f32" keeps every path bitwise
    identical to the unconfigured backend.
    """

    name: str = "jnp"

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
        """Name of the route that runs bucket ``b``'s slab-streaming stages
        at rank ``R`` (the per-bucket route the drivers report)."""
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
    # These take the bucket itself so a route can pick its representation.
    # The jnp route materializes Yc [Kb,R,C] (on a SCOO bucket through
    # SparseBucket.project, an O(nnz) segment sum into the same compact
    # layout); the SCOO and fused routes carry Q instead and never build Yc.

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
        is xkv + a small einsum; the fused route forms both in one slab
        pass."""
        _tick("procrustes_b")
        XkV = self.xkv_bucket(b, V, Vg)
        B = jnp.einsum("kir,lr->kil", XkV * Wb[:, None, :], H)
        return XkV, B

    def mode1_xkv_bucket(self, b, Q: jax.Array, XkV: jax.Array,
                         Wb: jax.Array) -> jax.Array:
        """Partial M1 [R,R] via the mode-1 reuse identity
        Y_k V = Q_k^T (X_k V) — no slab pass, but the [Kb,I,R] operands
        stream. The fused route reduces M1 in the same dispatch that forms
        the per-subject YkV, which is never written back."""
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
        stages below: the compact Yc [Kb, R, C] on the jnp route (staged
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
        (mode1_reuse), Yc and Vg may be None and the gather+matmul is
        skipped."""
        Yc, Vg, Wb, subject_mask, YkV = map(
            self.shard_subjects, (Yc, Vg, Wb, subject_mask, YkV))
        return spartan.mode1_bucket(Yc, Vg, Wb, subject_mask, YkV=YkV)

    def mode2_compact(self, Yc, H, Wb, col_mask, subject_mask) -> jax.Array:
        """Compact A [Kb, C, R] = (Y_k^T H) * W(k,:); masked rows are 0."""
        Yc, Wb, col_mask, subject_mask = map(
            self.shard_subjects, (Yc, Wb, col_mask, subject_mask))
        return self.shard_subjects(spartan.mode2_bucket_compact(
            Yc, H, Wb, col_mask, subject_mask))

    def mode3(self, Yc, Vg, H, subject_mask, *, YkV=None) -> jax.Array:
        """Per-subject M3 rows [Kb, R] = coldot(H, Y_k V)."""
        Yc, Vg, subject_mask, YkV = map(
            self.shard_subjects, (Yc, Vg, subject_mask, YkV))
        return self.shard_subjects(
            spartan.mode3_bucket(Yc, Vg, H, subject_mask, YkV=YkV))

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
            M2 = M2 + self.mode2_scatter(A, b.scatter).astype(M2.dtype)
        return M2

    def mttkrp_mode3(self, buckets: Sequence, Ycs: Sequence[jax.Array],
                     V: jax.Array, H: jax.Array, K: int) -> jax.Array:
        """M3 [K, R]: per-subject rows scattered to global subject ids."""
        M3 = jnp.zeros((K, H.shape[0]), H.dtype)
        for b, Yc in zip(buckets, Ycs):
            rows = self.mode3(Yc, b.gather_v(V), H, b.subject_mask)
            M3 = M3.at[b.subject_ids].add(rows.astype(M3.dtype))
        return M3


class SparseBackend(MttkrpBackend):
    """The ``scoo`` route: the O(nnz) stages of a SCOO bucket
    (:mod:`repro.kernels.scoo`).

    The projected slices are never materialized: ``project_bucket`` returns
    Q itself and every downstream stage contracts the flat COO triplets
    directly (gather + segment-sum / outer-product accumulation). X_k V and
    the mode-1 reduction from it are the base stages: X_k V is
    ``SparseBucket.xk_times_v``, and Y_k V = Q_k^T (X_k V) is reduced by
    jnp, like mode-3's.
    """

    name = "scoo"

    def _ykv_native(self, b: SparseBucket, Q, V):
        # each triplet's V row straight from V: the kept-column gather
        # (gather_v, [Kb, C_pad, R]) would also fill every padded slot, and
        # on CHOA's largest bucket it set the compiled step's peak
        return scoo.ykv_scoo(self._pc(b.vals), b.rows, self.shard_subjects(Q),
                             self._pc(b.triplet_v(V)))

    def project_bucket(self, b, Q):
        return self.shard_subjects(Q)   # carry Q; Yc is never built

    def ykv_bucket(self, b, proj, V):
        _tick("ykv")
        return self._ykv_native(b, proj, V)

    def mode1_bucket(self, b, proj, Wb, V=None, *, YkV=None):
        if YkV is None:
            _tick("mode1")
            YkV = self._ykv_native(b, proj, V)
        # YkV in hand, the remaining Hadamard + subject reduction is the
        # shared R x R algebra (uniform shard constraints included)
        return self.mode1(None, None, Wb, b.subject_mask, YkV=YkV)

    def mode2_bucket(self, b, proj, H, Wb):
        _tick("mode2")
        Q, Wb, col_mask, smask = map(
            self.shard_subjects, (proj, Wb, b.col_mask, b.subject_mask))
        return self.shard_subjects(scoo.mode2_compact_scoo(
            self._pc(b.vals), b.rows, b.lcols, Q, H, Wb, col_mask, smask,
            cperm=b.cperm, col_ends=b.col_ends))

    def mode3_bucket(self, b, proj, H, V=None, *, YkV=None):
        if YkV is None:
            _tick("mode3")
            YkV = self._ykv_native(b, proj, V)
        return self.mode3(None, None, H, b.subject_mask, YkV=YkV)


class FusedBackend(MttkrpBackend):
    """The ``fused`` route: the fused ALS stages of a CC bucket
    (:mod:`repro.kernels.fused`).

    The four per-iteration streaming launches each pull the subject's
    [I_pad, C_pad] slab through VMEM with double-buffered DMA and write only
    the small results back; the projected slices are never materialized —
    ``project_bucket`` carries Q itself, exactly like the SCOO route (so the
    ``als_step`` contract is unchanged).

    f64 inputs accumulate f64 (``accum_dtype``), which the interpret-mode
    parity tests rely on. Real TPUs reject f64 Mosaic kernels —
    ``AutoBackend._fused_ok`` gives this route only f32/bf16 buckets there.
    """

    name = "fused"

    @staticmethod
    def _interp() -> bool:
        return fused._interpret()

    def procrustes_b_bucket(self, b, H, Wb, V, Vg=None):
        _tick("procrustes_b")
        Vg = b.gather_v(V) if Vg is None else Vg
        XkV, B = fused.fused_procrustes_b(
            self._pc(b.vals), self._pc(Vg), Wb, H, interpret=self._interp())
        return self.shard_subjects(XkV), self.shard_subjects(B)

    def project_bucket(self, b, Q):
        return self.shard_subjects(Q)   # carry Q; Yc is never built

    def mode1_xkv_bucket(self, b, Q, XkV, Wb):
        _tick("mode1")
        Wb = fold_subject_mask(Wb, b.subject_mask)
        return fused.fused_mode1_xkv(Q, XkV, Wb, interpret=self._interp())

    def ykv_bucket(self, b, proj, V):
        _tick("ykv")
        return self.shard_subjects(fused.fused_ykv(
            self._pc(b.vals), proj, self._pc(b.gather_v(V)),
            interpret=self._interp()))

    def mode1_bucket(self, b, proj, Wb, V=None, *, YkV=None):
        if YkV is None:
            YkV = self.ykv_bucket(b, proj, V)
        return self.mode1(None, None, Wb, b.subject_mask, YkV=YkV)

    def mode2_bucket(self, b, proj, H, Wb):
        _tick("mode2")
        Q, Wb_m, cm = map(self.shard_subjects,
                          (proj, fold_subject_mask(Wb, b.subject_mask),
                           b.col_mask))
        return self.shard_subjects(fused.fused_mode2_compact(
            self._pc(b.vals), Q, H, Wb_m, cm, interpret=self._interp()))

    def mode3_bucket(self, b, proj, H, V=None, *, YkV=None):
        if YkV is None:
            YkV = self.ykv_bucket(b, proj, V)
        # YkV in hand, mode-3 is the shared [R,R] coldot — no slab pass left
        return self.mode3(None, None, H, b.subject_mask, YkV=YkV)


class AutoBackend(MttkrpBackend):
    """The router: each bucket-level stage runs on the route
    :meth:`route` names for its bucket.

    The decision is made at trace time from the static bucket type, dtype
    and shapes, the rank and the platform, so under jit each bucket
    compiles against exactly one route, and every stage of an iteration
    makes the same decision — the projected representation (Q on the SCOO
    and fused routes, Yc on the jnp route) stays coherent across
    ``project_bucket`` and its consumers. The array-level contract and the
    whole-tensor helpers are the inherited jnp ones.
    """

    name = "auto"

    def __init__(self, precision: str = "f32"):
        super().__init__(precision)
        self._routes = {be.name: be for be in (
            MttkrpBackend(precision), SparseBackend(precision),
            FusedBackend(precision))}

    @staticmethod
    def _fused_ok(b, R: int) -> bool:
        """A CC bucket takes the fused route on a TPU, at f32/bf16 (Mosaic
        rejects f64), with MXU-quantized geometry."""
        return (not isinstance(b, SparseBucket) and _on_tpu()
                and b.vals.dtype != jnp.float64
                and R % 8 == 0 and b.c_pad % 128 == 0)

    def route(self, b, R: int) -> str:
        if isinstance(b, SparseBucket):
            return "scoo"
        return "fused" if self._fused_ok(b, R) else "jnp"

    def _route(self, b, R: int) -> MttkrpBackend:
        return self._routes[self.route(b, R)]

    def xkv_bucket(self, b, V, Vg=None):
        return self._route(b, V.shape[-1]).xkv_bucket(b, V, Vg)

    def procrustes_b_bucket(self, b, H, Wb, V, Vg=None):
        return self._route(b, H.shape[0]).procrustes_b_bucket(b, H, Wb, V, Vg)

    def mode1_xkv_bucket(self, b, Q, XkV, Wb):
        return self._route(b, Q.shape[-1]).mode1_xkv_bucket(b, Q, XkV, Wb)

    def project_bucket(self, b, Q):
        return self._route(b, Q.shape[-1]).project_bucket(b, Q)

    def ykv_bucket(self, b, proj, V):
        return self._route(b, V.shape[-1]).ykv_bucket(b, proj, V)

    def mode1_bucket(self, b, proj, Wb, V=None, *, YkV=None):
        return self._route(b, Wb.shape[-1]).mode1_bucket(b, proj, Wb, V,
                                                         YkV=YkV)

    def mode2_bucket(self, b, proj, H, Wb):
        return self._route(b, H.shape[0]).mode2_bucket(b, proj, H, Wb)

    def mode3_bucket(self, b, proj, H, V=None, *, YkV=None):
        return self._route(b, H.shape[0]).mode3_bucket(b, proj, H, V, YkV=YkV)


BACKENDS = {"jnp": MttkrpBackend(), "auto": AutoBackend()}

# configured (non-f32 precision) instances, cached per (name, precision) so
# repeated get_backend calls hand jit the SAME backend object (stable tracing)
_CONFIGURED: Dict[Tuple[str, str], MttkrpBackend] = {}


def get_backend(name, precision: Optional[str] = None) -> MttkrpBackend:
    """Resolve a backend by name ("jnp" | "auto") or pass an
    :class:`MttkrpBackend` instance through unchanged. ``precision``
    (None/"f32" default) returns a configured instance that stages streamed
    operands at that compute precision (see the class docs); the f32
    singletons in ``BACKENDS`` are untouched."""
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
