"""Batched orthogonal-Procrustes solvers for the PARAFAC2 Q_k step.

The paper (Kiers et al.) computes, per subject, the rank-R truncated SVD of
F_k = H S_k V^T X_k^T and sets Q_k = Z_k P_k^T. Observe F_k = B_k^T with
B_k = X_k V S_k H^T (I_k x R), and Q_k is then exactly the **orthogonal polar
factor** of B_k. Three batched solvers, trading generality for MXU-friendliness:

* ``polar_svd``          — jnp.linalg.svd of B_k (reference; O(I R^2) but LAPACK-style)
* ``polar_gram_eigh``    — eigh of the smaller Gram of B (default; O(I R min(I,R))
                           matmul + O(min(I,R)^3) eigh, batched, TPU-native)
* ``polar_newton_schulz``— pure-matmul Newton–Schulz iteration (no eigh at all)

All accept B of shape [Kb, I, R] and return Q of the same shape with
Q^T Q = I_R per subject where B has full column rank (rows of padding are
zero and stay zero in gram/NS).

The polar factor is a spectral function of the Gram on either side,
Q = B (B^T B)^{+1/2} = (B B^T)^{+1/2} B, and both Grams have the same nonzero
eigenvalues. ``polar_gram_eigh`` picks the side from B's static shape: the
I x I row Gram when I < R (a bucket of short subjects), the R x R column Gram
otherwise. One cutoff relative to the largest eigenvalue keeps the same
directions on both sides.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.spans import _tick

__all__ = ["polar_svd", "polar_gram_eigh", "polar_newton_schulz", "solve_q"]


def polar_svd(B: jax.Array) -> jax.Array:
    """Reference batched polar factor via full SVD."""
    U, _, Vt = jnp.linalg.svd(B, full_matrices=False)
    return jnp.einsum("kir,krl->kil", U, Vt)


def polar_gram_eigh(B: jax.Array, *, eps: float = 1e-12) -> jax.Array:
    """Polar factor via eigendecomposition of the smaller Gram of B.

    Column side (I >= R): Q = B (B^T B)^{+1/2} = B E diag(1/sqrt(lam)) E^T.
    Row side (I < R, read from B's static shape): Q = (B B^T)^{+1/2} B, an
    I x I eigh in place of an R x R one. Each side runs under its own
    ``jax.named_scope`` (``gram_rows``, ``gram_cols``) and ticks
    ``polar.gram_rows`` or ``polar.gram_cols`` in
    :func:`repro.core.spans.dispatch_tally`.

    The Gram squares B's condition number, so the eigh's roundoff reaches
    Q amplified: Q's singular values miss 1 by up to roundoff x cond(B)^2,
    and the fit, which takes Q^T Q = I, drifts with them. One Newton–Schulz
    step, Q <- 1.5 Q - 0.5 Q Q^T Q on the Gram's side, squares that miss
    (singular values 0 and 1 are its fixed points, so the pseudo-polar's
    zero directions stay zero).

    Rank-deficient directions get a zero inverse root (pseudo-polar), which is
    the correct limit for padded/empty subjects. On both sides a direction
    counts as rank-deficient below ``max(eps, R * roundoff of B's dtype) *
    lam_max``, R being B's column count, so both keep the same directions: a
    Gram formed in floating point resolves its eigenvalues no finer, and the
    inverse root of a noise eigenvalue (a column Gram of a subject with fewer
    rows than R has R - I of them) would put noise of order sqrt(roundoff)
    into Q. A zero (padded) row of B gives a zero row of Q on both sides.
    """
    I, R = B.shape[-2:]
    rel_tol = max(eps, R * float(jnp.finfo(B.dtype).eps))
    if I < R:
        _tick("polar.gram_rows")
        return _polar_rows(B, rel_tol)
    _tick("polar.gram_cols")
    return _polar_cols(B, rel_tol)


def _pinv_root(G: jax.Array, rel_tol: float) -> jax.Array:
    """Pseudo-inverse square root of a batch of PSD Grams: eigenvalues at or
    below ``rel_tol * lam_max`` get a zero inverse root."""
    lam, E = jnp.linalg.eigh(G)                               # ascending eigs
    scale = jnp.maximum(lam, 0.0)
    max_lam = jnp.max(scale, axis=-1, keepdims=True)
    tol = max_lam * rel_tol
    inv_root = jnp.where(scale > tol, 1.0 / jnp.sqrt(jnp.maximum(scale, tol)), 0.0)
    return jnp.einsum("krl,kl,kml->krm", E, inv_root, E)      # E diag E^T


@jax.named_scope("gram_rows")
def _polar_rows(B: jax.Array, rel_tol: float) -> jax.Array:
    """Q = (B B^T)^{+1/2} B, from the I x I row Gram, then one
    Newton–Schulz step on the same side."""
    G = jnp.einsum("kir,kjr->kij", B, B)                     # [Kb, I, I]
    Q = jnp.einsum("kij,kjr->kir", _pinv_root(G, rel_tol), B)
    QQt = jnp.einsum("kir,kjr->kij", Q, Q)
    return 1.5 * Q - 0.5 * jnp.einsum("kij,kjr->kir", QQt, Q)


@jax.named_scope("gram_cols")
def _polar_cols(B: jax.Array, rel_tol: float) -> jax.Array:
    """Q = B (B^T B)^{+1/2}, from the R x R column Gram, then one
    Newton–Schulz step on the same side."""
    G = jnp.einsum("kir,kil->krl", B, B)                     # [Kb, R, R]
    Q = jnp.einsum("kir,krm->kim", B, _pinv_root(G, rel_tol))
    QtQ = jnp.einsum("kir,kil->krl", Q, Q)
    return 1.5 * Q - 0.5 * jnp.einsum("kir,krl->kil", Q, QtQ)


def polar_newton_schulz(B: jax.Array, *, iters: int = 12) -> jax.Array:
    """Pure-matmul polar via Newton–Schulz: X <- 1.5 X - 0.5 X X^T X.

    Converges for ||B||_2 < sqrt(3); we pre-scale by the Frobenius norm.
    Matmul-only → maps to the MXU with no host fallback; good for large R.
    """
    norm = jnp.sqrt(jnp.einsum("kir,kir->k", B, B)) + 1e-30
    X = B / norm[:, None, None]

    def body(X, _):
        XtX = jnp.einsum("kir,kil->krl", X, X)
        X = 1.5 * X - 0.5 * jnp.einsum("kir,krl->kil", X, XtX)
        return X, None

    X, _ = jax.lax.scan(body, X, None, length=iters)
    return X


_SOLVERS = {
    "svd": polar_svd,
    "gram_eigh": polar_gram_eigh,
    "newton_schulz": polar_newton_schulz,
}


def solve_q(B: jax.Array, method: str = "gram_eigh", **kw) -> jax.Array:
    """Dispatch: batched Q_k = polar(B_k)."""
    try:
        fn = _SOLVERS[method]
    except KeyError:
        raise ValueError(f"unknown procrustes method {method!r}; options {sorted(_SOLVERS)}")
    return fn(B, **kw)
