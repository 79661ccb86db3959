"""Batched orthogonal-Procrustes solvers for the PARAFAC2 Q_k step.

The paper (Kiers et al.) computes, per subject, the rank-R truncated SVD of
F_k = H S_k V^T X_k^T and sets Q_k = Z_k P_k^T. Observe F_k = B_k^T with
B_k = X_k V S_k H^T (I_k x R), and Q_k is then exactly the **orthogonal polar
factor** of B_k. Three batched solvers, trading generality for MXU-friendliness:

* ``polar_svd``          — jnp.linalg.svd of B_k (reference; O(I R^2) but LAPACK-style)
* ``polar_gram_eigh``    — eigh of the R x R Gram B^T B (default; O(I R^2) matmul
                           + O(R^3) eigh, batched, TPU-native)
* ``polar_newton_schulz``— pure-matmul Newton–Schulz iteration (no eigh at all)

All accept B of shape [Kb, I, R] and return Q of the same shape with
Q^T Q = I_R per subject (rows of padding are zero and stay zero in gram/NS).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["polar_svd", "polar_gram_eigh", "polar_newton_schulz", "solve_q"]


def polar_svd(B: jax.Array) -> jax.Array:
    """Reference batched polar factor via full SVD."""
    U, _, Vt = jnp.linalg.svd(B, full_matrices=False)
    return jnp.einsum("kir,krl->kil", U, Vt)


def polar_gram_eigh(B: jax.Array, *, eps: float = 1e-12) -> jax.Array:
    """Polar factor via eigendecomposition of the R x R Gram matrix.

    B = Q P with P = (B^T B)^{1/2};  Q = B P^{-1} = B E diag(1/sqrt(lam)) E^T.
    Rank-deficient directions get a zero inverse root (pseudo-polar), which is
    the correct limit for padded/empty subjects. A direction counts as
    rank-deficient below ``max(eps, R * roundoff of B's dtype) * lam_max``:
    a Gram formed in floating point resolves its eigenvalues no finer, and
    the inverse root of a noise eigenvalue (a subject with fewer rows than R
    has R - I of them) would put noise of order sqrt(roundoff) into Q.
    """
    R = B.shape[-1]
    G = jnp.einsum("kir,kil->krl", B, B)                     # [Kb, R, R]
    lam, E = jnp.linalg.eigh(G)                               # ascending eigs
    scale = jnp.maximum(lam, 0.0)
    max_lam = jnp.max(scale, axis=-1, keepdims=True)
    tol = max_lam * max(eps, R * float(jnp.finfo(B.dtype).eps))
    inv_root = jnp.where(scale > tol, 1.0 / jnp.sqrt(jnp.maximum(scale, tol)), 0.0)
    P_inv = jnp.einsum("krl,kl,kml->krm", E, inv_root, E)     # E diag E^T
    return jnp.einsum("kir,krm->kim", B, P_inv)


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
