"""Randomized range-finder stages for the rsvd preprocessing pass.

The DPar2-style compression (:mod:`repro.core.compress`) needs, per bucket,
an orthonormal basis P_k for the row space of every slice X_k [I_pad, J].
The classical randomized QB recipe (Halko/Martinsson/Tropp) is three stages,
and each one is already a bucket-level contraction this repo has fast paths
for:

  1. **sketch**   Y_k = X_k Ω with a shared Gaussian test matrix Ω [J, S]:
     exactly :meth:`Bucket.xk_times_v` — a gather of Ω's kept-column rows
     plus one tall-skinny [I_pad, C_pad] x [C_pad, S] matmul per subject, an
     MXU-friendly shape. On SCOO buckets
     the same call routes through the O(nnz) segment-sum kernels
     (:mod:`repro.kernels.scoo`), so sparse buckets are sketched WITHOUT ever
     densifying — the "SCOO-aware sketch".
  2. **power iteration** (q rounds, optional): Y <- X_k (X_k^T Y) sharpens
     the captured spectrum for slowly decaying singular values. Both halves
     are again existing stages: X_k^T Y is :meth:`Bucket.project` (landing in
     the compact kept-column layout) and the outer product is another
     ``xk_times_v`` with the gathered factor supplied directly.
  3. **orthonormalize** P_k = polar(Y_k) via the batched Gram-eigh polar
     factor (:func:`repro.core.procrustes.polar_gram_eigh`) — rank-deficient
     directions (padding subjects, slices with fewer than S independent
     rows) get exactly-zero basis columns instead of NaNs, which is the
     correct limit for the degenerate-slice case.

All stages are jit-compatible and batched over the bucket's Kb axis.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.procrustes import polar_gram_eigh

__all__ = ["gaussian_sketch", "sketch_bucket", "power_iterate", "range_basis"]


def gaussian_sketch(key: jax.Array, n_cols: int, sketch_dim: int,
                    dtype=jnp.float32) -> jax.Array:
    """Shared Gaussian test matrix Ω [J, S] (one draw for every bucket, so
    CC and SCOO buckets of the same data sketch against identical noise)."""
    return jax.random.normal(key, (n_cols, sketch_dim), dtype) / jnp.sqrt(
        jnp.asarray(sketch_dim, dtype))


def sketch_bucket(b, Omega: jax.Array,
                  Og: Optional[jax.Array] = None) -> jax.Array:
    """Y_k = X_k Ω for every subject in the bucket: [Kb, I_pad, S].

    ``b`` may be a CC :class:`~repro.core.irregular.Bucket` (dense tall-skinny
    matmul over kept columns) or a SCOO ``SparseBucket`` (gather + sorted
    segment-sum, O(nnz * S)) — the call is format-agnostic because only Ω
    rows of kept columns participate either way.
    """
    return b.xk_times_v(Omega, Og)


def power_iterate(b, Y: jax.Array, q: int) -> jax.Array:
    """q rounds of Y <- X_k (X_k^T Y), all in the compact kept-column space."""
    for _ in range(q):
        Z = b.project(Y)                           # [Kb, S, C_pad] compact
        Y = b.xk_times_v(None, Vg=jnp.swapaxes(Z, 1, 2))
    return Y


def range_basis(b, Omega: jax.Array, *, q: int = 1) -> jax.Array:
    """Orthonormal range basis P_k [Kb, I_pad, S] for every slice in ``b``.

    Columns beyond a slice's true rank come back exactly zero (pseudo-polar),
    and padding subjects get an all-zero basis via the subject mask.
    """
    Y = sketch_bucket(b, Omega)
    Y = power_iterate(b, Y, q)
    P = polar_gram_eigh(Y)
    return P * b.subject_mask[:, None, None]
