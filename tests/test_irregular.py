"""CC/SCOO format correctness: round-trips, bucketing invariants."""
import numpy as np
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.sparse import random_irregular, plan_buckets, from_dense_slices
from repro.core import bucketize


def test_cc_roundtrip_dense():
    data = random_irregular(n_subjects=8, n_cols=17, max_rows=9,
                            avg_nnz_per_subject=20, seed=0)
    bt = bucketize(data, max_buckets=2, dtype=jnp.float64)
    seen = {}
    for b in bt.buckets:
        dense = b.scatter_cols_to_dense(jnp.transpose(b.vals, (0, 2, 1)).transpose(0, 2, 1), data.n_cols)
        # vals [Kb, I, C] -> dense [Kb, I, J]
        dense = b.scatter_cols_to_dense(b.vals, data.n_cols)
        for slot in range(b.kb):
            if float(b.subject_mask[slot]) > 0:
                k = int(b.subject_ids[slot])
                seen[k] = np.asarray(dense[slot, : int(b.row_counts[slot]), :])
    assert len(seen) == data.n_subjects
    for k, sub in enumerate(data.subjects):
        np.testing.assert_allclose(seen[k], sub.to_dense(), atol=1e-12)


def test_bucket_plan_partition():
    rc = [3, 5, 9, 2, 14, 7, 7]
    cc = [4, 4, 8, 2, 16, 8, 4]
    plan = plan_buckets(rc, cc, max_buckets=3, row_align=4, col_align=4)
    all_members = np.concatenate(plan.members)
    assert sorted(all_members.tolist()) == list(range(7))
    for (ip, cp), mem in zip(plan.shapes, plan.members):
        assert ip % 4 == 0 and cp % 4 == 0
        for k in mem:
            assert rc[k] <= ip and cc[k] <= cp


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 30),
    max_buckets=st.integers(1, 6),
    seed=st.integers(0, 1000),
)
def test_property_bucket_partition(n, max_buckets, seed):
    rng = np.random.default_rng(seed)
    rc = rng.integers(1, 50, n)
    cc = rng.integers(1, 30, n)
    plan = plan_buckets(rc, cc, max_buckets=max_buckets)
    members = np.concatenate(plan.members)
    assert sorted(members.tolist()) == list(range(n))
    waste = plan.padding_waste(rc, cc)
    assert 0.0 <= waste < 1.0


def test_from_dense_slices():
    rng = np.random.default_rng(5)
    slices = [rng.random((4, 6)) * (rng.random((4, 6)) < 0.5) for _ in range(3)]
    data = from_dense_slices(slices)
    for s, X in zip(data.subjects, slices):
        np.testing.assert_allclose(s.to_dense(), X)


def test_bucketize_dtype_sweep():
    """Staging-buffer dtype: f64 only for f64 requests; bf16/f16 stage in
    f32 and cast once (the old check silently staged them in f64). Output
    dtypes and values must match the f32-staged reference for every float."""
    from repro.core.irregular import _staging_dtype

    assert _staging_dtype(jnp.float64) == np.float64
    for dt in (jnp.float32, jnp.bfloat16, jnp.float16):
        assert _staging_dtype(dt) == np.float32

    data = random_irregular(n_subjects=6, n_cols=23, max_rows=7,
                            avg_nnz_per_subject=14, seed=4)
    # one shared plan so cc/scoo buckets align with the reference
    plan = plan_buckets(data.row_counts(), data.col_counts(),
                        nnz_counts=data.nnz_counts(), max_buckets=2,
                        col_align=4)
    ref = bucketize(data, dtype=jnp.float32, plan=plan)
    for fmt in ("cc", "scoo"):
        for dt in (jnp.float16, jnp.bfloat16, jnp.float32, jnp.float64):
            bt = bucketize(data, dtype=dt, plan=plan,
                           formats=[fmt] * plan.n_buckets)
            for b, rb in zip(bt.buckets, ref.buckets):
                assert b.vals.dtype == jnp.dtype(dt)
                assert b.col_mask.dtype == jnp.dtype(dt)
                # values survive the round-trip at the dtype's precision
                dense = (b.vals if fmt == "cc"
                         else b.dense_vals()).astype(jnp.float64)
                ref_vals = np.asarray(rb.vals, dtype=np.float64)
                # the reference itself is f32, so never expect better than f32
                tol = max(float(jnp.finfo(dt).eps),
                          float(jnp.finfo(jnp.float32).eps))
                np.testing.assert_allclose(
                    np.asarray(dense), ref_vals,
                    rtol=2 * tol, atol=2 * tol * max(abs(ref_vals).max(), 1))
