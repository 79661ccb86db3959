"""SPARTan MTTKRP — the paper's core contribution, on the CC bucketed format.

All three modes operate directly on the frontal slices Y_k (never forming the
R x J x K intermediate tensor), are batched over subjects inside a bucket, and
exploit column sparsity via the CC gather. Partial sums over subjects are
plain adds — under pjit with subjects sharded over the mesh (the "subjects"
rule in repro.dist.sharding) they lower to all-reduces, which is the paper's
"sum partial results in parallel".

This module is pure math: the functions here are the ``jnp`` implementation
behind :class:`repro.core.backend.JnpBackend`. Backend selection, the
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

from repro.kernels.common import accum_dtype

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
    """Scatter-add compact results into M2 [J, R]. Padded entries are zero so
    scattering them to column id 0 (or any segment) is harmless.

    No XLA scatter-add (scalar-serialized on CPU, ~2.5x the cost of this
    path at benchmark scale; compiled for v5e it also needs more temporary
    HBM than this path): the flat column ids are stably sorted, and M2 is a
    permutation gather + cumsum-diff segment sum over the sorted rows. The
    sort depends only on the data, but the compiled engine loops still run
    it every iteration. Under shard_map each shard sorts its own subjects
    and the partial M2 is psum'd like any other.
    """
    Kb, C, R = A.shape
    flat_cols = cols.reshape(-1)                               # [Kb*C]
    flat_A = A.reshape(-1, R)
    perm = jnp.argsort(flat_cols, stable=True)
    ends = jnp.searchsorted(flat_cols[perm],
                            jnp.arange(1, J + 1, dtype=flat_cols.dtype))
    # accumulate in f64 when x64 is on (canonicalized back to f32 otherwise)
    # — the running cumsum spans every kept column, so give the partial sums
    # the wider accumulator when one is available
    acc = jnp.result_type(A.dtype, jnp.float64)
    g = flat_A[perm].astype(acc)
    cs = jnp.concatenate([jnp.zeros((1, R), acc), jnp.cumsum(g, 0)], 0)
    seg = cs[ends]                                             # [J, R]
    return jnp.diff(seg, axis=0,
                    prepend=jnp.zeros((1, R), acc)).astype(A.dtype)


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
