"""Plain PARAFAC2-ALS reference, read from the generated flat COO.

One iteration, as SPARTan's Algorithm 2 states it, with this repository's
documented choices (H unconstrained by a ridge solve; V, W nonnegative by
five HALS sweeps; column normalisation that moves scale into W; the fit from
the Q_k of the iteration's start):

1. B_k = X_k V diag(w_k) H^T; Q_k = the (pseudo-)polar factor of B_k. A
   direction of B_k whose squared singular value is at most
   ``max(1e-12, R * eps)`` of the largest gets no share of Q_k, with eps
   that of the dtype the configuration states (float32).
   That keeps Q_k to the rows a subject has when it has fewer rows than R.
2. M1 = sum_k Q_k^T X_k V diag(w_k); H = M1 (A1 + lam I)^-1 with
   A1 = W^T W * V^T V and lam = max(1e-10 tr(A1) / R, 128 * tiny);
   normalise H's columns, W takes their norms.
3. M2 = sum_k X_k^T Q_k H diag(w_k); V = HALS(M2, W^T W * H^T H, V);
   normalise V's columns, W takes their norms.
4. M3[k] = diag(H^T Q_k^T X_k V); W = HALS(M3, V^T V * H^T H, W).
5. fit = 1 - sqrt(||X||^2 - 2 sum_k w_k . M3[k] + sum_k w_k^T (H^T H * V^T V) w_k)
   / ||X||.

It imports nothing of the program under test. Every product of two arrays
goes through :class:`Arith`, which computes in float64 (the reference) or,
for the control, in float32 with matmuls in three bfloat16 passes, as a TPU
computes an f32 matmul at ``precision=HIGH``.
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

import ml_dtypes
import numpy as np
import scipy.sparse as sp

__all__ = ["Arith", "Factors", "Prepared", "als_step", "init_factors",
           "prepare", "run"]

class Arith:
    """Array arithmetic in one precision: ``"f64"`` or ``"high"``.
    ``stated`` is the dtype the configuration states, whose roundoff sets
    the Procrustes step's null-space cutoff."""

    def __init__(self, mode: str = "f64", stated=np.float32):
        if mode not in ("f64", "high"):
            raise ValueError(f"unknown precision {mode!r}")
        self.mode = mode
        self.dtype = np.float64 if mode == "f64" else np.float32
        self.stated_eps = float(np.finfo(stated).eps)

    def cast(self, x):
        return x.astype(self.dtype)

    def _split(self, x):
        hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
        lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
        return hi, lo

    def mm(self, a, b):
        """a @ b (any batch dims, or a scipy sparse ``a``)."""
        if self.mode == "f64":
            return a @ b
        if sp.issparse(a):
            # the data are small whole numbers, exact in bfloat16
            hi, lo = self._split(b)
            return a @ hi + a @ lo
        ah, al = self._split(a)
        bh, bl = self._split(b)
        return ah @ bh + (ah @ bl + al @ bh)


class Prepared(NamedTuple):
    """The cohort as the reference uses it."""

    X: sp.csr_matrix          # [sum I_k, J] stacked subject slices
    Xt: sp.csr_matrix         # its transpose
    row_subj: np.ndarray      # [sum I_k] subject of each stacked row
    groups: List[Tuple[np.ndarray, np.ndarray]]  # (subjects, rows [n, I])
    n_subjects: int
    norm_sq: float


class Factors(NamedTuple):
    H: np.ndarray
    V: np.ndarray
    W: np.ndarray
    fit: float


def prepare(cohort, arith: Arith) -> Prepared:
    """Stack the cohort's slices into one sparse matrix, and group the
    subjects by row count so that the per-subject algebra runs batched."""
    I = cohort.n_rows.astype(np.int64)
    K = I.size
    row_off = np.concatenate([[0], np.cumsum(I)])
    grow = row_off[cohort.subj] + cohort.row
    X = sp.csr_matrix((arith.cast(cohort.val), (grow, cohort.col)),
                      shape=(int(row_off[-1]), cohort.n_cols))
    groups = []
    for m in np.unique(I):
        subs = np.nonzero(I == m)[0]
        groups.append((subs, row_off[subs][:, None] + np.arange(m)[None, :]))
    return Prepared(X=X, Xt=X.T.tocsr(), row_subj=np.repeat(np.arange(K), I),
                    groups=groups, n_subjects=K,
                    norm_sq=float(np.sum(np.square(cohort.val, dtype=np.float64))))


def init_factors(V0: np.ndarray, K: int, arith: Arith) -> Factors:
    """H = I, W = 1 and the given V (the program's Kiers-style start)."""
    R = V0.shape[1]
    return Factors(H=np.eye(R, dtype=arith.dtype), V=arith.cast(V0),
                   W=np.ones((K, R), arith.dtype), fit=float("-inf"))


def polar(B: np.ndarray, arith: Arith) -> np.ndarray:
    """Batched (pseudo-)polar factor of B [n, I, R], from the eigenpairs of
    the smaller of B B^T and B^T B."""
    n, m, R = B.shape
    tol = max(1e-12, R * arith.stated_eps)
    if m <= R:
        lam, U = np.linalg.eigh(arith.mm(B, B.transpose(0, 2, 1)))
    else:
        lam, U = np.linalg.eigh(arith.mm(B.transpose(0, 2, 1), B))
    lam = np.maximum(lam, 0.0)
    keep = lam > tol * lam.max(axis=-1, keepdims=True)
    inv_root = np.where(keep, 1.0 / np.sqrt(np.where(keep, lam, 1.0)), 0.0)
    P = arith.mm(U * inv_root[:, None, :].astype(arith.dtype),
                 U.transpose(0, 2, 1))
    # Q = P B = U diag(1/s) U^T B (B B^T side), or B P (B^T B side)
    return arith.mm(P, B) if m <= R else arith.mm(B, P)


def ridge(M: np.ndarray, A: np.ndarray, arith: Arith) -> np.ndarray:
    R = A.shape[0]
    lam = max(1e-10 * float(np.trace(A)) / R,
              float(np.finfo(arith.dtype).tiny) * 128)
    return np.linalg.solve(A + lam * np.eye(R, dtype=A.dtype), M.T).T


def hals(M: np.ndarray, A: np.ndarray, X0: np.ndarray, arith: Arith,
         sweeps: int = 5) -> np.ndarray:
    """min_{X >= 0} in the normal form X A = M, by HALS column sweeps."""
    diag = np.maximum(np.diag(A), 1e-12)
    X = np.maximum(X0, 0.0)
    for _ in range(sweeps):
        for r in range(A.shape[0]):
            numer = M[:, r] - arith.mm(X, A[:, r]) + X[:, r] * A[r, r]
            X[:, r] = np.maximum(numer / diag[r], 0.0)
    return X


def normalize(X: np.ndarray):
    norms = np.sqrt(np.sum(X * X, axis=0))
    return X / np.maximum(norms, 1e-12), norms


def _per_subject_gram(T: Prepared, Q, Y, arith: Arith):
    """Yields (subjects, Q_k^T Y_k [n, R, R]) per row-count group."""
    for subs, rows in T.groups:
        yield subs, arith.mm(Q[rows].transpose(0, 2, 1), Y[rows])


def als_step(T: Prepared, f: Factors, arith: Arith) -> Factors:
    H, V, W = f.H, f.V, f.W
    K, R = W.shape
    mm = arith.mm
    # 1. Procrustes
    XV = mm(T.X, V)
    B = mm(XV * W[T.row_subj], H.T)
    Q = np.zeros_like(B)
    for _, rows in T.groups:
        Q[rows] = polar(B[rows], arith)
    # 2. H
    M1 = np.zeros((R, R), arith.dtype)
    for subs, G in _per_subject_gram(T, Q, XV, arith):
        M1 += np.sum(G * W[subs][:, None, :], axis=0)
    H = ridge(M1, mm(W.T, W) * mm(V.T, V), arith)
    H, h_norms = normalize(H)
    W = W * h_norms[None, :]
    # 3. V
    M2 = mm(T.Xt, mm(Q, H) * W[T.row_subj])
    V = hals(M2, mm(W.T, W) * mm(H.T, H), V.copy(), arith)
    V, v_norms = normalize(V)
    W = W * v_norms[None, :]
    # 4. W
    M3 = np.zeros((K, R), arith.dtype)
    for subs, G in _per_subject_gram(T, Q, mm(T.X, V), arith):
        M3[subs] = np.sum(H[None] * G, axis=1)
    HtH, VtV = mm(H.T, H), mm(V.T, V)
    W = hals(M3, VtV * HtH, W, arith)
    # 5. fit
    cross = float(np.sum(W * M3))
    model = float(np.sum(mm(W, HtH * VtV) * W))
    resid = T.norm_sq - 2.0 * cross + model
    fit = 1.0 - np.sqrt(max(resid, 0.0)) / np.sqrt(T.norm_sq)
    return Factors(H=H, V=V, W=W, fit=float(fit))


def run(cohort, V0: np.ndarray, steps: int, mode: str = "f64",
        stated=np.float32) -> List[Factors]:
    """The factors after each of the first ``steps`` iterations from the
    program's start (H = I, W = 1, V = V0)."""
    arith = Arith(mode, stated)
    T = prepare(cohort, arith)
    f = init_factors(V0, T.n_subjects, arith)
    out = []
    for _ in range(steps):
        f = als_step(T, f, arith)
        out.append(f)
    return out
