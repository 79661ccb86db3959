"""The mode-2 scatter's index (``core/irregular.py::scatter_index``): the
column order of a bucket's real compact rows, built once by the layout.

On a CPU the scatter over the index is bitwise equal to the in-loop path it
replaced (a stable argsort of every compact slot's column id, a gather of
all the slots, ``segment_sum``), which the tests keep a copy of; the index
keeps exactly the real rows, in that path's order."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Parafac2Options, bucketize, cc_bucket_like, init_state
from repro.core import engine as als_engine
from repro.core import spartan
from repro.core.irregular import scatter_index
from repro.kernels import segsum
from repro.launch import decompose
from repro.sparse import random_irregular

J, R = 60, 5


def _argsort_scatter(A, cols):
    """The scatter as the compiled loops ran it before the index: sort every
    compact slot by column id, gather all of them, sum each column's run."""
    flat = cols.reshape(-1)
    perm = jnp.argsort(flat, stable=True)
    g = A.reshape(-1, A.shape[-1])[perm]
    return jax.ops.segment_sum(g, flat[perm], J, indices_are_sorted=True)


def _argsort_scatter_per_shard(A, cols, n_shards):
    """That path on each subject shard's own rows, the partials added as
    the shards' psum adds them."""
    ks = A.shape[0] // n_shards
    return jnp.stack([_argsort_scatter(A[s * ks:(s + 1) * ks],
                                       cols[s * ks:(s + 1) * ks])
                      for s in range(n_shards)]).sum(0)


def _data():
    return random_irregular(n_subjects=37, n_cols=J, max_rows=9,
                            avg_nnz_per_subject=20, seed=2)


def _compact_rows(b, seed=0):
    """Compact mode-2 rows A [Kb, C_pad, R] as the compute stage leaves
    them: exactly zero where ``col_mask`` is 0."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((b.kb, b.c_pad, R)).astype(np.float32)
    return jnp.asarray(A * np.asarray(b.col_mask)[..., None])


def _all_padding(b):
    """``b`` with every subject made padding: no real compact row."""
    mask = jnp.zeros_like(b.col_mask)
    runs = b.scatter.perm.shape[0]
    index = scatter_index(np.asarray(b.cols), np.asarray(mask), J, runs)
    return dataclasses.replace(
        b, col_mask=mask, subject_mask=jnp.zeros_like(b.subject_mask),
        scatter=jax.tree_util.tree_map(jnp.asarray, index))


@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("case", ["cc", "scoo", "all_padding"])
def test_scatter_is_bitwise_the_argsort_path(case, n_shards):
    fmt = "cc" if case == "all_padding" else case
    bt = bucketize(_data(), max_buckets=3, format=fmt, col_align=8,
                   subject_align=n_shards, dtype=jnp.float32)
    buckets = bt.buckets
    if case == "all_padding":
        buckets = [_all_padding(buckets[0])]
    for i, b in enumerate(buckets):
        A = _compact_rows(b, seed=i)
        got = np.asarray(spartan.mode2_scatter(A, b.scatter))
        want = np.asarray(_argsort_scatter_per_shard(A, b.cols, n_shards))
        assert got.shape == (J, R)
        np.testing.assert_array_equal(got, want)
        if case == "all_padding":
            assert not got.any()


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_index_order_is_the_sorted_real_rows(n_shards):
    """Per shard, the index lists exactly the real compact slots in the
    order a stable argsort of all the shard's column ids puts them, then
    padding past the last column."""
    bt = bucketize(_data(), max_buckets=3, col_align=8,
                   subject_align=n_shards)
    for b in bt.buckets:
        cols = np.asarray(b.cols).reshape(n_shards, -1)
        real = (np.asarray(b.col_mask) > 0).reshape(n_shards, -1)
        perm = np.asarray(b.scatter.perm)
        starts = np.asarray(b.scatter.starts).reshape(n_shards, -1)
        ends = np.asarray(b.scatter.ends)
        assert perm.shape[0] == n_shards and ends.shape == (n_shards, J)
        for s in range(n_shards):
            order = np.argsort(cols[s], kind="stable")
            want = order[real[s][order]]
            n = want.size
            np.testing.assert_array_equal(perm[s, :n], want)
            ids = cols[s][want]
            np.testing.assert_array_equal(
                ends[s], np.searchsorted(ids, np.arange(1, J + 1)))
            np.testing.assert_array_equal(starts[s, :n],
                                          segsum.segment_starts(ids))
            assert ends[s, -1] == n


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_pallas_route_sums_each_shard_run(monkeypatch, n_shards):
    """The TPU's kernel (in interpret mode) over the shards' runs laid end
    to end matches the f64 sum within f32 rounding of each column's own
    terms."""
    monkeypatch.setattr(segsum, "_on_tpu", lambda: True)
    bt = bucketize(_data(), max_buckets=2, col_align=8,
                   subject_align=n_shards, dtype=jnp.float32)
    for i, b in enumerate(bt.buckets):
        A = _compact_rows(b, seed=i)
        got = np.asarray(spartan.mode2_scatter(A, b.scatter))
        flat = np.asarray(b.cols).reshape(-1)
        rows = np.asarray(A, np.float64).reshape(-1, R)
        want, mass = np.zeros((J, R)), np.zeros((J, R))
        np.add.at(want, flat, rows)
        np.add.at(mass, flat, np.abs(rows))
        eps = float(np.finfo(np.float32).eps)
        assert np.all(np.abs(got - want) <= 16 * eps * mass)


@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("route", ["segment_sum", "pallas"])
def test_padded_index_sums_the_same(monkeypatch, route, n_shards):
    """An index padded to every compact slot of a shard (as the stream
    pins it) gives the same M2: bitwise through ``segment_sum``; through
    the kernel within f32 rounding of each column's own terms, since a
    later shard's run then starts in another of its tiles."""
    monkeypatch.setattr(segsum, "_on_tpu", lambda: route == "pallas")
    bt = bucketize(_data(), max_buckets=2, col_align=8,
                   subject_align=n_shards, dtype=jnp.float32)
    for i, b in enumerate(bt.buckets):
        slots = b.kb // n_shards * b.c_pad
        padded = b.scatter.padded(slots)
        assert padded.perm.shape == (n_shards, slots)
        assert padded.starts.shape == (n_shards * slots,)
        A = _compact_rows(b, seed=i)
        got = np.asarray(spartan.mode2_scatter(A, padded))
        want = np.asarray(spartan.mode2_scatter(A, b.scatter))
        if route == "segment_sum":
            np.testing.assert_array_equal(got, want)
            continue
        mass = np.zeros((J, R))
        np.add.at(mass, np.asarray(b.cols).reshape(-1),
                  np.abs(np.asarray(A, np.float64).reshape(-1, R)))
        eps = float(np.finfo(np.float32).eps)
        assert np.all(np.abs(got - want) <= 32 * eps * mass)


def test_build_buckets_counts_scatter_rows_and_slots():
    data = _data()
    bt, stats, _ = decompose.build_buckets(data, format="auto",
                                           engine="scan", max_buckets=3)
    for rec, b in zip(stats, bt.buckets):
        assert rec["scatter_rows"] == int(np.asarray(b.col_mask).sum())
        assert rec["scatter_slots"] == b.kb * b.c_pad
        assert b.scatter.perm.shape == (1, rec["scatter_rows"])
    assert sum(r["scatter_rows"] for r in stats) == int(
        data.col_counts().sum())


def test_cc_bucket_like_shares_the_index():
    b = bucketize(_data(), max_buckets=1, format="scoo").buckets[0]
    core = cc_bucket_like(b, jnp.zeros((b.kb, 3, b.c_pad)))
    assert core.scatter is b.scatter


def test_mesh_check_wants_the_index_laid_out_per_shard():
    """A bucket whose subject count divides the shards, but whose index is
    one run, is refused with the re-bucketize hint."""
    data = random_irregular(n_subjects=16, n_cols=J, max_rows=4,
                            avg_nnz_per_subject=8, seed=1)
    bt = bucketize(data, max_buckets=1, dtype=jnp.float32)
    assert bt.buckets[0].kb % 2 == 0
    state = init_state(bt, Parafac2Options(rank=2), seed=0)
    with pytest.raises(ValueError, match="scatter index"):
        als_engine._check_divisible(bt, state, 2)
    aligned = bucketize(data, max_buckets=1, dtype=jnp.float32,
                        subject_align=2)
    als_engine._check_divisible(aligned, state, 2)
