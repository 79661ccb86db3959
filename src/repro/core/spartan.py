"""SPARTan MTTKRP — the paper's core contribution, on the CC bucketed format.

All three modes operate directly on the frontal slices Y_k (never forming the
R x J x K intermediate tensor), are batched over subjects inside a bucket, and
exploit column sparsity via the CC gather. Partial sums over subjects are
plain adds — under pjit with subjects sharded over the mesh (the "subjects"
rule in repro.dist.sharding) they lower to all-reduces, which is the paper's
"sum partial results in parallel".

This module is pure math: the functions here are the ``jnp`` implementation
behind :class:`repro.core.backend.MttkrpBackend`. Route selection, the
whole-tensor per-mode helpers, and the uniform subject-axis sharding
constraints all live in :mod:`repro.core.backend` — the one layer the ALS
driver talks to. See docs/ARCHITECTURE.md for the end-to-end data flow.

Shapes per bucket (Kb subjects, I rows padded, C kept-cols padded, rank R):
  Yc  [Kb, R, C]   compressed slices  Y_k = Q_k^T X_k
  Vg  [Kb, C, R]   gathered V rows for kept columns
  Wb  [Kb, R]      W rows for this bucket's subjects
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import _tick
from repro.kernels.common import accum_dtype
from repro.kernels.segsum import sorted_segment_sum

__all__ = [
    "mode1_bucket",
    "mode2_bucket_compact",
    "mode2_scatter",
    "mode3_bucket",
]


def _f(x):
    """Promote to the shared accumulation dtype (``kernels.common.accum_dtype``):
    bf16/f16 slice values feed subject-axis reductions, which lose mass in half
    precision, so they widen to f32. f32/f64 pass through unchanged (the f64
    algebra tests must stay exact)."""
    return x.astype(accum_dtype(x))


# ---------------------------------------------------------------------------
# Mode 1:  M1 = sum_k (Y_k V) * W(k,:)  (row-wise Hadamard)  -> [R, R]
# ---------------------------------------------------------------------------

def mode1_bucket(
    Yc: jax.Array,
    Vg: jax.Array,
    Wb: jax.Array,
    subject_mask: jax.Array,
    *,
    YkV: Optional[jax.Array] = None,
) -> jax.Array:
    """Partial M1 for one bucket. If ``YkV`` ([Kb,R,R], = Y_k V) is provided
    (mode1_reuse optimization: Y_k V = Q_k^T (X_k V) cached from the Procrustes
    step), the gather+matmul is skipped entirely."""
    if YkV is None:
        YkV = jnp.einsum("krc,kcl->krl", _f(Yc), _f(Vg))  # [Kb, R, R]
    scaled = _f(YkV) * _f(Wb)[:, None, :]         # row-wise Hadamard with W(k,:)
    return jnp.einsum("krl,k->rl", scaled, subject_mask)


# ---------------------------------------------------------------------------
# Mode 2:  temp(j,:) = (Y_k(:,j)^T H) * W(k,:) for nonzero cols j; scatter-add
# ---------------------------------------------------------------------------

def mode2_bucket_compact(
    Yc: jax.Array,
    H: jax.Array,
    Wb: jax.Array,
    col_mask: jax.Array,
    subject_mask: jax.Array,
) -> jax.Array:
    """Compact per-column results A[Kb, C, R]; rows for padded columns are 0.

    This is the compute stage of mode-2 (the paper's Fig. 3): one small matmul
    per subject over its kept columns only, then Hadamard with W(k,:).
    The scatter to M2 in R^{J x R} is a separate, memory-bound stage.
    """
    A = jnp.einsum("krc,rl->kcl", _f(Yc), H)                   # (Y_k(:,j)^T H)
    A = A * _f(Wb)[:, None, :]                                 # * W(k,:)
    return A * (col_mask * subject_mask[:, None])[..., None]


def mode2_scatter(A: jax.Array, cols: jax.Array, J: int) -> jax.Array:
    """Sum the compact rows A [Kb, C, R] into M2 [J, R] by column id ``cols``
    [Kb, C]. Padded entries are zero, so their landing in column 0 (or any
    segment) is harmless; a column with no entries gets exact zeros.

    The flat column ids are stably sorted (``sort``), the rows are gathered
    into that order (``gather``), and each column's run of sorted rows is
    summed on its own (``segsum``:
    :func:`repro.kernels.segsum.sorted_segment_sum`, a Pallas segmented
    prefix sum on a TPU). Each M2 row is accumulated from its own terms
    only, so its rounding error is bounded by c * eps * sum|its terms| for
    a small c, however heavy the columns sorted before it. A difference of
    one running total over all rows would be bounded by eps times that
    total instead, and on a cohort with Zipf-popular columns a rare
    column's row would lose most of its digits. On a TPU no XLA
    scatter-add: on a v5e it made the whole scatter 9-12% slower at the
    benchmark cohorts' bucket shapes (``benchmarks/segsum_micro.py``).

    The sort depends only on the data, but the compiled engine loops run it
    every iteration. Under shard_map each shard sorts its own subjects and
    the partial M2 is psum'd like any other. Ticks ``scatter.segsum`` in
    :func:`repro.core.spans.dispatch_tally` once per call.
    """
    R = A.shape[-1]
    _tick("scatter.segsum")
    flat_cols = cols.reshape(-1)                               # [Kb*C]
    with jax.named_scope("sort"):
        perm = jnp.argsort(flat_cols, stable=True)
        ids = flat_cols[perm]
    with jax.named_scope("gather"):
        g = A.reshape(-1, R)[perm].astype(accum_dtype(A))
    with jax.named_scope("segsum"):
        M2 = sorted_segment_sum(g, ids, J)                     # [J, R]
    return M2.astype(A.dtype)


# ---------------------------------------------------------------------------
# Mode 3:  M3(k,:) = coldot(H, Y_k V)   -> [K, R] rows per subject
# ---------------------------------------------------------------------------

def mode3_bucket(
    Yc: jax.Array,
    Vg: jax.Array,
    H: jax.Array,
    subject_mask: jax.Array,
    *,
    YkV: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-subject rows of M3 for one bucket: [Kb, R]."""
    if YkV is None:
        YkV = jnp.einsum("krc,kcl->krl", _f(Yc), _f(Vg))
    rows = jnp.einsum("rl,krl->kl", H, _f(YkV))   # column-wise inner products
    return rows * subject_mask[:, None]
