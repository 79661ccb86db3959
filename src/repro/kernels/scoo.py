"""O(nnz) SCOO contractions: segment-sum references + Pallas TPU kernels.

The SCOO device format (repro.core.irregular.SparseBucket) stores each
subject's slice as sorted flat COO triplets padded to the bucket-wide N_pad:

  vals  f[Kb, N]   nonzero values (pad entries 0 — they vanish in every sum)
  rows  i32[Kb, N] local row index into the I_pad row space (pad: 0)
  lcols i32[Kb, N] local kept-column slot into the C_pad column space (pad: 0)

Every per-iteration contraction on this layout is a *gather + segment-sum*:
pick factor rows by index, scale by the nonzero values, and sum the
contributions that share a destination row/column. FLOPs and HBM traffic are
O(nnz * R) — independent of the densified I_pad * C_pad rectangle the CC
format pays for (docs/ARCHITECTURE.md, SCOO stage):

  xk_times_v   (X_k V)[i,:]   = sum_{n: rows[n]=i}  vals[n] * Vg[lcols[n], :]
  project      (Q^T X_k)[:,c] = sum_{n: lcols[n]=c} vals[n] * Q[rows[n], :]
  ykv          (Y_k V)[r,l]   = sum_n vals[n] * Q[rows[n], r] * Vg[lcols[n], l]
  mode2        A[c,:]         = sum_{n: lcols[n]=c} vals[n] * (Q H)[rows[n], :]

The jnp path exploits the *sorted* in "sorted flat COO": with precomputed
CSR/CSC-style segment boundaries (``row_ends`` for the row-major view;
``cperm``/``col_ends`` for the column-sorted view — host-side artifacts of
``bucketize``), a segment-sum is ``diff(cumsum(contrib)[ends])`` — pure
gathers and a prefix sum, no scatter at all (XLA scatter-add serializes on
CPU and is the difference between O(nnz) on paper and O(nnz) in wall-clock).
Pad entries carry value 0 and sit past every boundary, so they vanish from
every segment. Passing ``ends=None`` falls back to batched scatter-adds
(``.at[].add``) — the order-independent oracle the boundary path is tested
against. The Pallas variants (behind the same ``use_pallas`` / ``interpret``
switch as the CC kernels in :mod:`repro.kernels.ops`) tile the nnz axis and
run each gather/segment-sum as a one-hot matmul on the MXU — indices become
``iota == index`` masks, so the irregular memory access pattern turns into
dense [BN, C] / [BN, I] matmuls that Mosaic can lower, and the per-subject
true nonzero counts are *scalar-prefetched* so blocks past a subject's nnz
are skipped entirely.

Accumulation is f32 for sub-f32 inputs (half-precision segment-sums lose
mass; same policy as ``repro.core.spartan._f``), cast back to the input
dtype on the way out; f64 stays f64 so the algebra tests stay exact.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "xk_times_v", "project", "ykv_scoo", "mode1_scoo", "mode2_compact_scoo",
    "mode3_scoo", "xk_times_v_pallas", "project_pallas",
]


from repro.kernels.common import accum_dtype as _acc  # shared accumulation policy


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# jnp segment-sum path (sorted-boundary cumsum, scatter-add oracle fallback)
# ---------------------------------------------------------------------------

def _gather_n(M: jax.Array, idx: jax.Array) -> jax.Array:
    """Batched row gather: M [Kb, S, R], idx i32 [Kb, N] -> [Kb, N, R]."""
    return jnp.take_along_axis(M, idx[..., None], axis=1)


def segment_sum_sorted(contrib: jax.Array, ends: jax.Array) -> jax.Array:
    """Segment-sum of sorted contributions via prefix-sum differencing.

    contrib [Kb, N, R] sorted by destination segment; ends i32 [Kb, S] with
    ``ends[k, s]`` = index one past segment s's last entry (CSR-style row
    pointers, monotone, all <= true nnz so trailing pads never land in any
    segment) -> [Kb, S, R]. No scatter: cumsum + gather + diff.
    """
    csum = jnp.cumsum(contrib, axis=1)                    # [Kb, N, R]
    csum = jnp.pad(csum, ((0, 0), (1, 0), (0, 0)))        # prepend zero row
    e = jnp.take_along_axis(csum, ends[..., None], axis=1)  # [Kb, S, R]
    return jnp.diff(e, axis=1, prepend=jnp.zeros_like(e[:, :1]))


def _segsum(contrib, idx, ends, n_out: int):
    """Boundary path when ``ends`` is given, scatter-add oracle otherwise."""
    if ends is not None:
        return segment_sum_sorted(contrib, ends)
    Kb = contrib.shape[0]
    out = jnp.zeros((Kb, n_out, contrib.shape[-1]), contrib.dtype)
    return out.at[jnp.arange(Kb)[:, None], idx].add(contrib)


def xk_times_v(vals, rows, lcols, Vg, i_pad: int, *, row_ends=None,
               nnz_counts=None, use_pallas: bool = False,
               interpret: Optional[bool] = None):
    """X_k V from SCOO triplets: gather-from-Vg + segment-sum over rows.

    vals [Kb,N], rows/lcols i32 [Kb,N], Vg [Kb,C,R] (V rows for the bucket's
    kept columns, already masked) -> [Kb, I_pad, R]. ``row_ends``
    (i32 [Kb, I_pad], from bucketize) selects the scatter-free sorted path;
    ``nnz_counts`` (i32 [Kb], true per-subject counts) lets the Pallas
    variant skip all-padding nnz blocks.
    """
    if use_pallas:
        return xk_times_v_pallas(
            vals, rows, lcols, Vg, i_pad, nnz_counts=nnz_counts,
            interpret=_interpret() if interpret is None else interpret,
        ).astype(vals.dtype)
    acc = _acc(vals.dtype)
    g = _gather_n(Vg.astype(acc), lcols)                  # [Kb, N, R]
    contrib = g * vals.astype(acc)[..., None]
    return _segsum(contrib, rows, row_ends, i_pad).astype(vals.dtype)


def project(vals, rows, lcols, Q, c_pad: int, *, cperm=None, col_ends=None,
            nnz_counts=None, use_pallas: bool = False,
            interpret: Optional[bool] = None):
    """Y_k = Q_k^T X_k from SCOO triplets: gather-from-Q + segment-sum over
    kept columns -> compact [Kb, R, C_pad] (the CC ``Yc`` layout, bitwise
    column-compatible with the bucket's shared ``cols`` ids). ``cperm`` /
    ``col_ends`` (the column-sorted view from bucketize) select the
    scatter-free sorted path; ``nnz_counts`` feeds the Pallas block-skip."""
    if use_pallas:
        return project_pallas(
            vals, rows, lcols, Q, c_pad, nnz_counts=nnz_counts,
            interpret=_interpret() if interpret is None else interpret,
        ).astype(vals.dtype)
    acc = _acc(vals.dtype)
    if cperm is not None and col_ends is not None:
        rows = jnp.take_along_axis(rows, cperm, axis=1)
        vals_c = jnp.take_along_axis(vals, cperm, axis=1)
        idx = None
    else:
        vals_c, col_ends, idx = vals, None, lcols
    qg = _gather_n(Q.astype(acc), rows)                   # [Kb, N, R]
    contrib = qg * vals_c.astype(acc)[..., None]
    out = _segsum(contrib, idx, col_ends, c_pad)          # [Kb, C, R]
    return jnp.swapaxes(out, 1, 2).astype(vals.dtype)     # [Kb, R, C]


def ykv_scoo(vals, rows, lcols, Q, Vg):
    """Y_k V [Kb, R, R] natively from the triplets (never materializing Yc):
    sum_n vals[n] * Q[rows[n], :] (x) Vg[lcols[n], :] — O(nnz * R^2)."""
    acc = _acc(vals.dtype)
    qg = _gather_n(Q.astype(acc), rows)                   # [Kb, N, R]
    vg = _gather_n(Vg.astype(acc), lcols)                 # [Kb, N, R]
    return jnp.einsum("knr,knl->krl", qg * vals.astype(acc)[..., None], vg)


def mode1_scoo(vals, rows, lcols, Q, Vg, Wb, subject_mask):
    """Partial M1 [R, R] natively: the ykv outer-product sum, row-Hadamard
    with W(k,:), reduced over real subjects (matches spartan.mode1_bucket)."""
    YkV = ykv_scoo(vals, rows, lcols, Q, Vg)
    scaled = YkV * Wb.astype(YkV.dtype)[:, None, :]
    return jnp.einsum("krl,k->rl", scaled, subject_mask.astype(YkV.dtype))


def mode2_compact_scoo(vals, rows, lcols, Q, H, Wb, col_mask, subject_mask,
                       *, cperm=None, col_ends=None):
    """Compact mode-2 A [Kb, C, R] natively: A[k,c,:] = (Y_k(:,c)^T H) * W(k,:)
    = segment-sum over kept columns of vals[n] * (Q_k H)[rows[n], :], then the
    same W/col/subject masking as spartan.mode2_bucket_compact. ``cperm`` /
    ``col_ends`` select the scatter-free column-sorted path."""
    acc = _acc(vals.dtype)
    QH = jnp.einsum("kir,rl->kil", Q.astype(acc), H.astype(acc))
    if cperm is not None and col_ends is not None:
        rows = jnp.take_along_axis(rows, cperm, axis=1)
        vals = jnp.take_along_axis(vals, cperm, axis=1)
        idx = None
    else:
        col_ends, idx = None, lcols
    g = _gather_n(QH, rows)                               # [Kb, N, R]
    contrib = g * vals.astype(acc)[..., None]
    c_pad = col_mask.shape[-1]
    A = _segsum(contrib, idx, col_ends, c_pad)            # [Kb, C, R]
    A = A * Wb.astype(acc)[:, None, :]
    return A * (col_mask * subject_mask[:, None]).astype(acc)[..., None]


def mode3_scoo(vals, rows, lcols, Q, Vg, H, subject_mask):
    """Per-subject M3 rows [Kb, R] natively: coldot(H, Y_k V) with Y_k V from
    the triplet outer-product sum (matches spartan.mode3_bucket)."""
    YkV = ykv_scoo(vals, rows, lcols, Q, Vg)
    rows_out = jnp.einsum("rl,krl->kl", H.astype(YkV.dtype), YkV)
    return rows_out * subject_mask.astype(YkV.dtype)[:, None]


# ---------------------------------------------------------------------------
# Pallas TPU kernels: one-hot MXU gathers + scalar-prefetched nnz skipping
# ---------------------------------------------------------------------------
#
# Grid (Kb, nnz blocks of BN). Per step the kernel sees one subject's vals/
# rows/lcols block plus that subject's whole Vg (or Q) panel in VMEM, and
# accumulates into the revisited [I_pad, R] (or [C_pad, R]) output window.
# The gather `Vg[lcols]` is a one-hot matmul (lcols == iota) on the MXU —
# Mosaic has no per-element dynamic gather, but the segment-matrix trick
# turns it into a dense [BN, C] @ [C, R] product whose HBM traffic is still
# O(nnz + C*R) per subject. The per-subject true nonzero count is a
# scalar-prefetch operand: blocks entirely past it are skipped with pl.when
# (the subject-aligned padding guarantees they contribute nothing).

_BLOCK_N = 512


def _block_skip_counts(nnz_counts, vals) -> jax.Array:
    """Per-subject count for the scalar-prefetched block-skip guard. Without
    the bucket's true ``nnz_counts``, skip nothing (every entry counts):
    explicit zero-VALUED triplets are legal, so inferring counts from
    ``vals != 0`` would drop real entries that follow a stored zero."""
    if nnz_counts is not None:
        return nnz_counts.astype(jnp.int32)
    Kb, N = vals.shape
    return jnp.full((Kb,), N, jnp.int32)


def _xkv_kernel(nnz_ref, vals_ref, rows_ref, lcols_ref, vg_ref, out_ref,
                *, block_n: int, acc):
    k, b = pl.program_id(0), pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(b * block_n < nnz_ref[k])
    def _accum():
        vals = vals_ref[0, 0].astype(acc)                 # [BN]
        lc = lcols_ref[0, 0]                              # [BN] i32
        r = rows_ref[0, 0]                                # [BN] i32
        C = vg_ref.shape[1]
        I = out_ref.shape[1]
        BN = vals.shape[0]
        onehot_c = (lc[:, None] ==
                    lax.broadcasted_iota(jnp.int32, (BN, C), 1))
        g = jnp.dot(onehot_c.astype(acc), vg_ref[0].astype(acc),
                    preferred_element_type=acc)           # [BN, R]
        contrib = g * vals[:, None]
        onehot_r = (r[:, None] ==
                    lax.broadcasted_iota(jnp.int32, (BN, I), 1))
        out_ref[0] += jnp.dot(onehot_r.astype(acc).T, contrib,
                              preferred_element_type=acc)


@functools.partial(jax.jit, static_argnames=("i_pad", "block_n", "interpret"))
def xk_times_v_pallas(vals, rows, lcols, Vg, i_pad: int, *, nnz_counts=None,
                      block_n: int = _BLOCK_N, interpret: bool = False):
    """Pallas X_k V: [Kb,N] triplets + Vg [Kb,C,R] -> [Kb, I_pad, R] (f32)."""
    Kb, N = vals.shape
    R = Vg.shape[-1]
    acc = _acc(vals)
    if Kb == 0:
        return jnp.zeros((Kb, i_pad, R), acc)
    nnz = _block_skip_counts(nnz_counts, vals)
    bn = min(block_n, N)
    nb = pl.cdiv(N, bn)
    if N % bn:
        padn = nb * bn - N
        vals = jnp.pad(vals, ((0, 0), (0, padn)))
        rows = jnp.pad(rows, ((0, 0), (0, padn)))
        lcols = jnp.pad(lcols, ((0, 0), (0, padn)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Kb, nb),
        in_specs=[
            pl.BlockSpec((1, 1, bn), lambda k, b, nnz: (k, 0, b)),
            pl.BlockSpec((1, 1, bn), lambda k, b, nnz: (k, 0, b)),
            pl.BlockSpec((1, 1, bn), lambda k, b, nnz: (k, 0, b)),
            pl.BlockSpec((1,) + Vg.shape[1:], lambda k, b, nnz: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, i_pad, R), lambda k, b, nnz: (k, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_xkv_kernel, block_n=bn, acc=acc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Kb, i_pad, R), acc),
        interpret=interpret,
    )(nnz, vals[:, None], rows[:, None], lcols[:, None], Vg)


def _project_kernel(nnz_ref, vals_ref, rows_ref, lcols_ref, q_ref, out_ref,
                    *, block_n: int, acc):
    k, b = pl.program_id(0), pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(b * block_n < nnz_ref[k])
    def _accum():
        vals = vals_ref[0, 0].astype(acc)                 # [BN]
        lc = lcols_ref[0, 0]
        r = rows_ref[0, 0]
        I = q_ref.shape[1]
        C = out_ref.shape[2]
        BN = vals.shape[0]
        onehot_r = (r[:, None] ==
                    lax.broadcasted_iota(jnp.int32, (BN, I), 1))
        qg = jnp.dot(onehot_r.astype(acc), q_ref[0].astype(acc),
                     preferred_element_type=acc)          # [BN, R]
        contrib = qg * vals[:, None]                      # [BN, R]
        onehot_c = (lc[:, None] ==
                    lax.broadcasted_iota(jnp.int32, (BN, C), 1))
        # out [R, C] += contrib^T @ onehot_c
        out_ref[0] += jnp.dot(contrib.T, onehot_c.astype(acc),
                              preferred_element_type=acc)


@functools.partial(jax.jit, static_argnames=("c_pad", "block_n", "interpret"))
def project_pallas(vals, rows, lcols, Q, c_pad: int, *, nnz_counts=None,
                   block_n: int = _BLOCK_N, interpret: bool = False):
    """Pallas Y_k = Q_k^T X_k: triplets + Q [Kb,I,R] -> [Kb, R, c_pad],
    accumulated in the shared accum dtype (f32 for sub-f64 inputs)."""
    Kb, N = vals.shape
    R = Q.shape[-1]
    acc = _acc(vals)
    if Kb == 0:
        return jnp.zeros((Kb, R, c_pad), acc)
    nnz = _block_skip_counts(nnz_counts, vals)
    bn = min(block_n, N)
    nb = pl.cdiv(N, bn)
    if N % bn:
        padn = nb * bn - N
        vals = jnp.pad(vals, ((0, 0), (0, padn)))
        rows = jnp.pad(rows, ((0, 0), (0, padn)))
        lcols = jnp.pad(lcols, ((0, 0), (0, padn)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Kb, nb),
        in_specs=[
            pl.BlockSpec((1, 1, bn), lambda k, b, nnz: (k, 0, b)),
            pl.BlockSpec((1, 1, bn), lambda k, b, nnz: (k, 0, b)),
            pl.BlockSpec((1, 1, bn), lambda k, b, nnz: (k, 0, b)),
            pl.BlockSpec((1,) + Q.shape[1:], lambda k, b, nnz: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, c_pad), lambda k, b, nnz: (k, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_project_kernel, block_n=bn, acc=acc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Kb, R, c_pad), acc),
        interpret=interpret,
    )(nnz, vals[:, None], rows[:, None], lcols[:, None], Q)
