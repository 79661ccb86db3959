"""The generator matches each configuration's published geometry."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import gen

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    with open(CONFIGS / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["choa-r40", "movielens-r40"])
def test_geometry_matches_published(name):
    cfg = _cfg(name)
    pub = cfg["published"]
    c = gen.generate(cfg, seed=2**33 + 5)
    assert c.n_subjects == cfg["n_subjects"]
    assert c.n_cols == pub["n_cols"]
    assert c.n_rows.max() <= pub["max_rows"]
    # the cap is reached in a cohort of this size
    assert c.n_rows.max() >= 0.9 * pub["max_rows"]
    assert c.nnz / c.n_subjects == pytest.approx(pub["nnz_per_subject"],
                                                 rel=0.02)
    # a row of every subject holds a nonzero; no (subject, row, col) twice
    key = (c.subj.astype(np.int64) * pub["max_rows"] + c.row) * c.n_cols + c.col
    assert np.all(np.diff(key) > 0)
    rows_used = np.unique(c.subj.astype(np.int64) * pub["max_rows"] + c.row)
    assert rows_used.size == int(c.n_rows.sum())
    assert c.col.min() >= 0 and c.col.max() < c.n_cols
    assert np.all(c.val >= 1)


@pytest.mark.parametrize("name", ["choa-r40", "movielens-r40"])
def test_seed_draws_only_the_subject_order(name):
    cfg = dict(_cfg(name), n_subjects=500)
    geo = gen.geometry(cfg)
    a = gen.generate(cfg, 1, geo)
    b = gen.generate(cfg, 2**40 + 3, geo)
    a2 = gen.generate(cfg, 1, geo)
    assert np.array_equal(a.col, a2.col) and np.array_equal(a.val, a2.val)
    assert not np.array_equal(a.col, b.col)
    # every position keeps its shape, so a bucketizer makes the same buckets
    assert np.array_equal(a.n_rows, b.n_rows)
    assert np.array_equal(a.subj, b.subj)
    assert np.array_equal(a.distinct_cols(), b.distinct_cols())

    def subjects(c):
        offs = c.subject_offsets()
        return sorted((int(c.n_rows[k]), tuple(c.row[x:y]), tuple(c.col[x:y]),
                       tuple(c.val[x:y]))
                      for k, (x, y) in enumerate(zip(offs[:-1], offs[1:])))

    # the same subjects, so the same work
    assert subjects(a) == subjects(b)


def test_popularity_is_skewed():
    cfg = dict(_cfg("choa-r40"), n_subjects=5000)
    c = gen.generate(cfg, 3)
    counts = np.sort(np.bincount(c.col, minlength=c.n_cols))[::-1]
    # Zipf: the top 1% of codes hold far more than 1% of the nonzeros
    top = counts[: c.n_cols // 100].sum() / counts.sum()
    assert top > 0.08
