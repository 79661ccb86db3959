"""CP-ALS pieces shared by the PARAFAC2 step: Gram utilities and column
normalization.

The PARAFAC2 inner step runs exactly ONE CP-ALS iteration on the intermediate
tensor Y (Kiers et al.) — that iteration lives in repro.core.parafac2 and uses
the SPARTan MTTKRPs; here we keep the shared algebra.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["normalize_columns", "cp_gram"]


def normalize_columns(X: jax.Array, *, eps: float = 1e-12) -> Tuple[jax.Array, jax.Array]:
    """Unit-normalize columns; return (normalized, norms)."""
    norms = jnp.sqrt(jnp.sum(X * X, axis=0))
    safe = jnp.maximum(norms, eps)
    return X / safe, norms


def cp_gram(*factors: jax.Array) -> jax.Array:
    """Hadamard product of factor Grams: prod_i (F_i^T F_i)."""
    G = None
    for F in factors:
        FtF = F.T @ F
        G = FtF if G is None else G * FtF
    return G

