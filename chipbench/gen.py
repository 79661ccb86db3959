"""One general generator of irregular sparse cohorts, driven by a config file.

A cohort is K subjects; subject k has I_k rows (visits, years) over J shared
columns (codes, movies). Everything the fit computes is drawn from the
configuration's own ``geometry.seed``: every subject's row count, the (row,
slot) pattern of its nonzeros, which distinct columns its slots stand for
(drawn without replacement by a Zipf popularity over J) and its values.
``--seed`` draws only the order of the subjects, and only among subjects of
the same shape (row count, distinct columns, nonzeros): subject positions
keep their shapes, so a bucketizer that sorts by shape makes the same
buckets, and the same compiled program, for every seed. Every seed so runs
the same set of per-subject problems: the batched ``eigh`` of the
Procrustes step iterates until its hardest matrix converges, so a cohort
drawn anew for every seed would change the work an iteration does.

Two pattern kinds:

``visits``  (EHR, CHOA): I_k ~ lognormal capped at ``max_rows``; each subject
            has A_k candidate codes; each visit records 1 + Poisson(c - 1)
            distinct codes of them, capped at A_k. Values are counts,
            1 + Poisson(``value_lam``).
``ratings`` (MovieLens): I_k ~ 1 + geometric, capped at ``max_rows``; N_k
            ratings ~ lognormal clipped to [``min_nnz``, ``max_nnz``], each of
            a distinct movie, the first I_k in rows 0..I_k-1 so that no row is
            empty and the rest in uniform rows. Values are stars 1..5.

Everything is vectorised over subjects. The output is a flat COO sorted by
(subject, row, column), which the plain reference reads directly and the
harness hands to the program as its own ``IrregularCOO``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Cohort", "generate", "geometry"]


@dataclasses.dataclass(frozen=True)
class Cohort:
    """Flat COO of an irregular cohort, sorted by (subject, row, column)."""

    subj: np.ndarray      # int32 [nnz]
    row: np.ndarray       # int32 [nnz] row within the subject
    col: np.ndarray       # int32 [nnz] global column
    val: np.ndarray       # float64 [nnz]
    n_rows: np.ndarray    # int32 [K] I_k
    n_cols: int           # J

    @property
    def n_subjects(self) -> int:
        return int(self.n_rows.size)

    @property
    def nnz(self) -> int:
        return int(self.val.size)

    def subject_offsets(self) -> np.ndarray:
        """[K + 1] start of each subject's run of nonzeros."""
        counts = np.bincount(self.subj, minlength=self.n_subjects)
        return np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)

    def distinct_cols(self) -> np.ndarray:
        """[K] number of distinct columns per subject (c_k)."""
        uniq = np.unique(self.subj.astype(np.int64) * self.n_cols + self.col)
        return np.bincount(uniq // self.n_cols, minlength=self.n_subjects)


@dataclasses.dataclass(frozen=True)
class Geometry:
    n_rows: np.ndarray    # int32 [K]
    subj: np.ndarray      # int32 [nnz]
    row: np.ndarray       # int32 [nnz]
    slot: np.ndarray      # int32 [nnz] in [0, n_slots[subj])
    n_slots: np.ndarray   # int64 [K] c_k
    values: np.ndarray    # float64 [nnz], the multiset of values


def _zipf(J: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, J + 1, dtype=np.float64) ** exponent
    return p / p.sum()


def _values(g: dict, n: int, rng) -> np.ndarray:
    if g["values"] == "counts":
        return 1.0 + rng.poisson(g["value_lam"], n)
    if g["values"] == "stars":
        cdf = np.cumsum(g["star_probs"])
        return 1.0 + np.searchsorted(cdf / cdf[-1], rng.random(n), side="right")
    raise ValueError(f"unknown values kind {g['values']!r}")


def _visits_geometry(g: dict, K: int, rng) -> Geometry:
    max_rows = int(g["max_rows"])
    I = np.exp(rng.normal(g["rows_log_mean"], g["rows_log_sd"], K))
    I = np.clip(np.ceil(I), 1, max_rows).astype(np.int64)
    A = np.minimum(g["codes_min"] + rng.poisson(g["codes_extra"], K),
                   g["n_cols"]).astype(np.int64)
    row_subj = np.repeat(np.arange(K), I)
    n = np.minimum(1 + rng.poisson(g["codes_per_visit"] - 1.0, row_subj.size),
                   A[row_subj])
    # n distinct candidate slots per visit: the first n of a random order
    a_max = int(A.max())
    keys = rng.random((row_subj.size, a_max))
    keys[np.arange(a_max)[None, :] >= A[row_subj][:, None]] = 2.0
    order = np.argsort(keys, axis=1)
    pick = np.arange(a_max)[None, :] < n[:, None]
    slot = order[pick]                                     # row-major
    row_idx = np.repeat(np.arange(row_subj.size), n)
    subj = row_subj[row_idx]
    offs = np.concatenate([[0], np.cumsum(I)])[:-1]
    row = row_idx - offs[subj]
    # relabel the slots a subject used to 0..c_k-1: every kept column is used
    skey = subj * a_max + slot
    uniq, inv = np.unique(skey, return_inverse=True)
    usubj = uniq // a_max
    start = np.searchsorted(usubj, np.arange(K))
    slot = inv - start[subj]
    n_slots = np.bincount(usubj, minlength=K)
    return Geometry(I.astype(np.int32), subj.astype(np.int32),
                    row.astype(np.int32), slot.astype(np.int32), n_slots,
                    _values(g, subj.size, rng))


def _ratings_geometry(g: dict, K: int, rng) -> Geometry:
    max_rows = int(g["max_rows"])
    I = np.minimum(rng.geometric(g["rows_geometric_p"], K), max_rows)
    N = np.exp(rng.normal(g["nnz_log_mean"], g["nnz_log_sd"], K))
    N = np.clip(np.round(N), g["min_nnz"], g["max_nnz"]).astype(np.int64)
    N = np.maximum(N, I)
    subj = np.repeat(np.arange(K), N)
    offs = np.concatenate([[0], np.cumsum(N)])[:-1]
    slot = np.arange(subj.size) - offs[subj]
    row = np.where(slot < I[subj], slot,
                   (rng.random(subj.size) * I[subj]).astype(np.int64))
    return Geometry(I.astype(np.int32), subj.astype(np.int32),
                    row.astype(np.int32), slot.astype(np.int32), N,
                    _values(g, subj.size, rng))


_PATTERNS = {"visits": _visits_geometry, "ratings": _ratings_geometry}


def geometry(cfg: dict) -> Geometry:
    """The configuration's fixed geometry (independent of ``--seed``)."""
    g = dict(cfg["geometry"], n_cols=cfg["n_cols"], max_rows=cfg["max_rows"])
    rng = np.random.default_rng(int(g["seed"]))
    return _PATTERNS[g["pattern"]](g, int(cfg["n_subjects"]), rng)


def _distinct_draws(rng, need: np.ndarray, p: np.ndarray) -> np.ndarray:
    """For each subject k, ``need[k]`` distinct columns drawn by popularity
    ``p`` without replacement, in draw order. Returns the [sum(need)] flat
    columns, subject-major."""
    K, J = need.size, p.size
    if need.max(initial=0) > J:
        raise ValueError(f"a subject needs {need.max()} distinct of {J} columns")
    cdf = np.cumsum(p)
    cdf[-1] = 1.0
    out = np.zeros(int(need.sum()), np.int64)
    out_start = np.concatenate([[0], np.cumsum(need)])[:-1]
    # draws of the subjects still short of their need, grouped by subject in
    # ascending order and, within a subject, in draw order
    pool_s = np.zeros(0, np.int64)
    pool_c = np.zeros(0, np.int64)
    pending = np.arange(K)
    have = np.zeros(K, np.int64)
    factor = 1.25
    while pending.size:
        m = np.ceil((need[pending] - have[pending]) * factor).astype(np.int64) + 4
        s = np.repeat(pending, m)
        c = np.minimum(np.searchsorted(cdf, rng.random(s.size), side="right"),
                       J - 1)
        if pool_s.size:
            order = np.argsort(np.concatenate([pool_s, s]), kind="stable")
            s = np.concatenate([pool_s, s])[order]
            c = np.concatenate([pool_c, c])[order]
        # keep the first draw of each (subject, column)
        _, first = np.unique(s * J + c, return_index=True)
        first.sort()
        s, c = s[first], c[first]
        have = np.bincount(s, minlength=K)
        start = np.concatenate([[0], np.cumsum(have)])[:-1]
        rank = np.arange(s.size) - start[s]
        done = have[s] >= need[s]
        keep = done & (rank < need[s])
        out[out_start[s[keep]] + rank[keep]] = c[keep]
        pool_s, pool_c = s[~done], c[~done]
        pending = np.unique(pool_s)
        factor *= 2.0
    return out


def _shape_preserving_order(geo: Geometry, rng) -> np.ndarray:
    """[K] new index of each subject: a random permutation within each class
    of subjects with the same (rows, distinct columns, nonzeros), each class
    keeping its positions."""
    K = geo.n_rows.size
    nnz = np.bincount(geo.subj, minlength=K)
    _, cls = np.unique(np.stack([geo.n_rows, geo.n_slots, nnz], axis=1),
                       axis=0, return_inverse=True)
    cls = cls.reshape(-1)
    positions = np.lexsort((np.arange(K), cls))
    subjects = np.lexsort((rng.random(K), cls))
    perm = np.empty(K, np.int64)
    perm[subjects] = positions
    return perm


def generate(cfg: dict, seed: int, geo: Geometry | None = None) -> Cohort:
    """The cohort of configuration ``cfg``, its subjects in the order that
    ``seed`` draws."""
    geo = geometry(cfg) if geo is None else geo
    g = cfg["geometry"]
    J = int(cfg["n_cols"])
    K = int(geo.n_rows.size)
    rng = np.random.default_rng([int(g["seed"]), 1])
    codes = _distinct_draws(rng, geo.n_slots, _zipf(J, g["popularity_exponent"]))
    slot_start = np.concatenate([[0], np.cumsum(geo.n_slots)])[:-1]
    col = codes[slot_start[geo.subj] + geo.slot]
    perm = _shape_preserving_order(geo, np.random.default_rng([int(seed), 1]))
    subj = perm[geo.subj]                       # subject k becomes perm[k]
    n_rows = np.empty_like(geo.n_rows)
    n_rows[perm] = geo.n_rows
    key = (subj.astype(np.int64) * int(cfg["max_rows"]) + geo.row) * J + col
    order = np.argsort(key)
    return Cohort(subj=subj[order].astype(np.int32), row=geo.row[order],
                  col=col[order].astype(np.int32),
                  val=geo.values[order].astype(np.float64),
                  n_rows=n_rows, n_cols=J)
