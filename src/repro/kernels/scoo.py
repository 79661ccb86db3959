"""O(nnz) SCOO contractions: jnp gathers and segment sums.

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
against.

Accumulation is f32 for sub-f32 inputs (half-precision segment-sums lose
mass; same policy as ``repro.core.spartan._f``), cast back to the input
dtype on the way out; f64 stays f64 so the algebra tests stay exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "xk_times_v", "project", "ykv_scoo", "mode1_scoo", "mode2_compact_scoo",
    "mode3_scoo",
]


from repro.kernels.common import accum_dtype as _acc  # shared accumulation policy


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


def xk_times_v(vals, rows, lcols, Vg, i_pad: int, *, row_ends=None):
    """X_k V from SCOO triplets: gather-from-Vg + segment-sum over rows.

    vals [Kb,N], rows/lcols i32 [Kb,N], Vg [Kb,C,R] (V rows for the bucket's
    kept columns, already masked) -> [Kb, I_pad, R]. ``row_ends``
    (i32 [Kb, I_pad], from bucketize) selects the scatter-free sorted path.
    """
    acc = _acc(vals.dtype)
    g = _gather_n(Vg.astype(acc), lcols)                  # [Kb, N, R]
    contrib = g * vals.astype(acc)[..., None]
    return _segsum(contrib, rows, row_ends, i_pad).astype(vals.dtype)


def project(vals, rows, lcols, Q, c_pad: int, *, cperm=None, col_ends=None):
    """Y_k = Q_k^T X_k from SCOO triplets: gather-from-Q + segment-sum over
    kept columns -> compact [Kb, R, C_pad] (the CC ``Yc`` layout, bitwise
    column-compatible with the bucket's shared ``cols`` ids). ``cperm`` /
    ``col_ends`` (the column-sorted view from bucketize) select the
    scatter-free sorted path."""
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

