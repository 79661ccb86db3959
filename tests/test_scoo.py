"""SCOO-vs-CC parity suite: the O(nnz) sparse execution path must be a pure
performance/memory knob. The same bucket plan is materialized in both device
formats and every per-iteration stage — X_k V, the projection Y_k = Q^T X_k,
all three MTTKRP modes, ykv, and whole decompositions under every engine and
an ADMM-routed constraint — must agree (f64, tight tolerances; host-vs-scan
bitwise on SCOO). Also covers the CC-vs-SCOO auto-router's decisions."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    Parafac2Options, SparseBucket, Bucket, bucket_format, bucketize, fit)
from repro.core import spartan
from repro.kernels import scoo as kscoo
from repro.data import choa_like
from repro.sparse import (
    IrregularCOO, SubjectCOO, plan_buckets, random_irregular, route_formats)

TOL = dict(rtol=0, atol=1e-10)


def _subject(rng, n_rows, n_cols, nnz):
    """One subject with exactly `nnz` distinct nonzero cells."""
    cells = rng.choice(n_rows * n_cols, size=nnz, replace=False)
    return SubjectCOO(
        rows=(cells // n_cols).astype(np.int32),
        cols=(cells % n_cols).astype(np.int32),
        vals=rng.standard_normal(nnz),
        n_rows=n_rows, n_cols=n_cols)


def _edge_data(n_cols=29):
    """Odd/unaligned geometry with an empty subject, a single-nnz subject,
    and an ultra-sparse tall subject alongside ordinary ones."""
    rng = np.random.default_rng(7)
    subs = [
        _subject(rng, 9, n_cols, 25),
        SubjectCOO(rows=np.zeros(0, np.int32), cols=np.zeros(0, np.int32),
                   vals=np.zeros(0), n_rows=3, n_cols=n_cols),   # empty
        _subject(rng, 1, n_cols, 1),                             # single nnz
        _subject(rng, 200, n_cols, 5),                           # ultra-sparse
        _subject(rng, 13, n_cols, 40),
        _subject(rng, 6, n_cols, 11),
    ]
    return IrregularCOO(subjects=subs, n_cols=n_cols)


def _r40_data(n_rows, n_cols, nnz, n_subjects, seed):
    """Subjects of the benchmark plans' extreme SCOO buckets: tall (CHOA's
    168-row, 648-triplet bucket), wide (MovieLens's kept-column spans of
    768 and more) or a single subject (Kb = 1)."""
    rng = np.random.default_rng(seed)
    return IrregularCOO(subjects=[_subject(rng, n_rows, n_cols, nnz)
                                  for _ in range(n_subjects)], n_cols=n_cols)


DATASETS = {
    "edge": _edge_data,
    "random-odd": lambda: random_irregular(
        n_subjects=13, n_cols=37, max_rows=9, avg_nnz_per_subject=18,
        seed=0, nonneg=False),
    "random-padded": lambda: random_irregular(
        n_subjects=11, n_cols=50, max_rows=12, avg_nnz_per_subject=25,
        seed=3),
    # rank-40 stage parity at the plans' extreme bucket shapes
    "tall-r40": lambda: _r40_data(166, 300, 650, 3, seed=11),
    "wide-r40": lambda: _r40_data(19, 2_000, 1_300, 2, seed=12),
    "single-r40": lambda: _r40_data(24, 400, 120, 1, seed=13),
}


def _pair(data, *, max_buckets=3, col_align=4, subject_align=1,
          dtype=jnp.float64):
    """The SAME bucket plan in both formats -> aligned bucket pairs."""
    plan = plan_buckets(data.row_counts(), data.col_counts(),
                        nnz_counts=data.nnz_counts(), max_buckets=max_buckets,
                        col_align=col_align)
    kw = dict(dtype=dtype, plan=plan, subject_align=subject_align)
    cc = bucketize(data, formats=["cc"] * plan.n_buckets, **kw)
    sc = bucketize(data, formats=["scoo"] * plan.n_buckets, **kw)
    return cc, sc


@pytest.mark.parametrize("name", sorted(DATASETS))
def test_stage_parity(name):
    """Every SCOO contraction against its CC counterpart, bucket by bucket."""
    data = DATASETS[name]()
    cc, sc = _pair(data)
    rng = np.random.default_rng(1)
    R = 40 if name.endswith("-r40") else 5
    bs0 = sc.buckets[0]
    if name == "tall-r40":
        assert bs0.n_pad >= 648 and bs0.i_pad >= 166
    elif name == "wide-r40":
        assert bs0.c_pad >= 768
    elif name == "single-r40":
        assert len(sc.buckets) == 1 and bs0.kb == 1
    V = jnp.asarray(rng.standard_normal((data.n_cols, R)))
    H = jnp.asarray(rng.standard_normal((R, R)))
    for bc, bs in zip(cc.buckets, sc.buckets):
        assert isinstance(bs, SparseBucket) and isinstance(bc, Bucket)
        assert (bs.kb, bs.i_pad, bs.c_pad) == (bc.kb, bc.i_pad, bc.c_pad)
        np.testing.assert_array_equal(np.asarray(bs.cols), np.asarray(bc.cols))
        np.testing.assert_allclose(np.asarray(bs.dense_vals()),
                                   np.asarray(bc.vals), **TOL)
        Q = jnp.asarray(rng.standard_normal((bc.kb, bc.i_pad, R)))
        Wb = jnp.asarray(rng.standard_normal((bc.kb, R)))
        # formation stages
        np.testing.assert_allclose(np.asarray(bs.xk_times_v(V)),
                                   np.asarray(bc.xk_times_v(V)), **TOL)
        Yc_cc = bc.project(Q)
        np.testing.assert_allclose(np.asarray(bs.project(Q)),
                                   np.asarray(Yc_cc), **TOL)
        # native (Yc-free) MTTKRP stages vs the spartan reference on CC's Yc
        Vg = bc.gather_v(V)
        ykv_ref = jnp.einsum("krc,kcl->krl", Yc_cc, Vg)
        np.testing.assert_allclose(
            np.asarray(kscoo.ykv_scoo(bs.vals, bs.rows, bs.lcols, Q, Vg)),
            np.asarray(ykv_ref), **TOL)
        np.testing.assert_allclose(
            np.asarray(kscoo.mode1_scoo(bs.vals, bs.rows, bs.lcols, Q, Vg,
                                        Wb, bs.subject_mask)),
            np.asarray(spartan.mode1_bucket(Yc_cc, Vg, Wb, bc.subject_mask)),
            **TOL)
        np.testing.assert_allclose(
            np.asarray(kscoo.mode2_compact_scoo(
                bs.vals, bs.rows, bs.lcols, Q, H, Wb, bs.col_mask,
                bs.subject_mask, cperm=bs.cperm, col_ends=bs.col_ends)),
            np.asarray(spartan.mode2_bucket_compact(
                Yc_cc, H, Wb, bc.col_mask, bc.subject_mask)), **TOL)
        np.testing.assert_allclose(
            np.asarray(kscoo.mode3_scoo(bs.vals, bs.rows, bs.lcols, Q, Vg, H,
                                        bs.subject_mask)),
            np.asarray(spartan.mode3_bucket(Yc_cc, Vg, H, bc.subject_mask)),
            **TOL)


def test_sorted_boundary_matches_scatter_oracle():
    """The cumsum/boundary segment-sums against the order-independent
    scatter-add fallback (ends=None)."""
    data = DATASETS["edge"]()
    _, sc = _pair(data)
    rng = np.random.default_rng(2)
    R = 4
    V = jnp.asarray(rng.standard_normal((data.n_cols, R)))
    for b in sc.buckets:
        Vg = b.gather_v(V)
        Q = jnp.asarray(rng.standard_normal((b.kb, b.i_pad, R)))
        np.testing.assert_allclose(
            np.asarray(kscoo.xk_times_v(b.vals, b.rows, b.lcols, Vg, b.i_pad,
                                        row_ends=b.row_ends)),
            np.asarray(kscoo.xk_times_v(b.vals, b.rows, b.lcols, Vg,
                                        b.i_pad)), rtol=0, atol=1e-12)
        np.testing.assert_allclose(
            np.asarray(kscoo.project(b.vals, b.rows, b.lcols, Q, b.c_pad,
                                     cperm=b.cperm, col_ends=b.col_ends)),
            np.asarray(kscoo.project(b.vals, b.rows, b.lcols, Q, b.c_pad)),
            rtol=0, atol=1e-12)


@pytest.fixture(scope="module")
def choa_small():
    return choa_like(scale=5e-5, seed=0)


@pytest.mark.parametrize("backend", ["jnp", "auto"])
def test_fit_parity_backends(choa_small, backend):
    """Whole-decomposition parity: SCOO final fit within 1e-8 of the CC/jnp
    reference (f64, the acceptance-criterion command shape)."""
    cc, sc = _pair(choa_small, max_buckets=2, col_align=128)
    opts_cc = Parafac2Options(rank=5, dtype=jnp.float64,
                              backend="jnp")
    opts_sc = Parafac2Options(rank=5, dtype=jnp.float64,
                              backend=backend)
    _, h_cc = fit(cc, opts_cc, max_iters=20, tol=0.0, seed=0)
    _, h_sc = fit(sc, opts_sc, max_iters=20, tol=0.0, seed=0)
    np.testing.assert_allclose(h_sc, h_cc, rtol=0, atol=1e-8)


def test_fit_parity_admm_constraint(choa_small):
    """One ADMM-routed constraint through the SCOO path (dual state carried
    in aux, engines untouched)."""
    cc, sc = _pair(choa_small, max_buckets=2, col_align=128)
    kw = dict(rank=3, dtype=jnp.float64,
              constraints={"v": "nonneg_admm", "w": "nonneg_admm"})
    _, h_cc = fit(cc, Parafac2Options(backend="jnp", **kw),
                  max_iters=15, tol=0.0, seed=0)
    for backend in ("jnp", "auto"):
        _, h_sc = fit(sc, Parafac2Options(backend=backend, **kw),
                      max_iters=15, tol=0.0, seed=0)
        np.testing.assert_allclose(h_sc, h_cc, rtol=0, atol=1e-8)


@pytest.mark.parametrize("engine,atol", [("scan", 0.0), ("mesh", 1e-8)])
def test_engine_parity_scoo(choa_small, engine, atol):
    """host vs scan bitwise on SCOO (scan takes the data as an argument like the
    host jit); mesh to eps (shard_map compiles the step differently)."""
    _, sc = _pair(choa_small, max_buckets=2, col_align=128,
                  subject_align=len(jax.devices()))
    kw = dict(rank=3, dtype=jnp.float64, backend="auto",
              check_every=4)
    _, h_host = fit(sc, Parafac2Options(engine="host", **kw),
                    max_iters=12, tol=0.0, seed=0)
    _, h_dev = fit(sc, Parafac2Options(engine=engine, **kw),
                   max_iters=12, tol=0.0, seed=0)
    if atol == 0.0:
        np.testing.assert_array_equal(h_dev, h_host)
    else:
        np.testing.assert_allclose(h_dev, h_host, rtol=0, atol=atol)


def test_fit_parity_bucketed_w(choa_small):
    """The bucketed W layout rides the SCOO path unchanged."""
    cc, sc = _pair(choa_small, max_buckets=2, col_align=128)
    kw = dict(rank=3, dtype=jnp.float64, w_layout="bucketed")
    _, h_cc = fit(cc, Parafac2Options(backend="jnp", **kw),
                  max_iters=10, tol=0.0, seed=0)
    _, h_sc = fit(sc, Parafac2Options(backend="auto", **kw),
                  max_iters=10, tol=0.0, seed=0)
    np.testing.assert_allclose(h_sc, h_cc, rtol=0, atol=1e-8)


# ---------------------------------------------------------------------------
# auto-router decisions
# ---------------------------------------------------------------------------

def test_route_formats_by_density():
    rng = np.random.default_rng(0)
    # fully dense 8x8 blocks (density 1.0 over their kept columns) +
    # ultra-sparse tall subjects: the router must split them
    def dense_block():
        r, c = np.meshgrid(np.arange(8), np.arange(8), indexing="ij")
        return SubjectCOO(rows=r.ravel().astype(np.int32),
                          cols=c.ravel().astype(np.int32),
                          vals=rng.standard_normal(64), n_rows=8, n_cols=64)
    subs = ([dense_block() for _ in range(4)]
            + [_subject(rng, 180, 64, 6) for _ in range(4)])
    data = IrregularCOO(subjects=subs, n_cols=64)
    plan = plan_buckets(data.row_counts(), data.col_counts(),
                        nnz_counts=data.nnz_counts(), max_buckets=2,
                        col_align=4)
    dens = plan.bucket_densities(data.nnz_counts())
    fmts = route_formats(plan, data.nnz_counts(), format="auto",
                         density_threshold=0.25)
    for d, f in zip(dens, fmts):
        assert f == ("scoo" if d < 0.25 else "cc")
    assert set(fmts) == {"cc", "scoo"}   # the geometry really is mixed
    # forcing wins over density
    assert route_formats(plan, data.nnz_counts(), format="cc") == ["cc"] * 2
    assert route_formats(plan, data.nnz_counts(), format="scoo") == ["scoo"] * 2
    with pytest.raises(ValueError, match="unknown format"):
        route_formats(plan, data.nnz_counts(), format="bogus")
    # bucketize(format="auto") materializes exactly the routed classes
    bt = bucketize(data, max_buckets=2, col_align=4, format="auto")
    assert [bucket_format(b) for b in bt.buckets] == fmts


def test_plan_nnz_stats():
    data = DATASETS["random-odd"]()
    nnzc = data.nnz_counts()
    plan = plan_buckets(data.row_counts(), data.col_counts(),
                        nnz_counts=nnzc, max_buckets=3, col_align=4,
                        sort_by="nnz")
    assert plan.nnz_pads is not None
    for npad, mem in zip(plan.nnz_pads, plan.members):
        assert npad >= int(nnzc[mem].max())
        assert npad % 8 == 0
    assert 0.0 <= plan.nnz_waste(nnzc) < 1.0
    assert sum(plan.bucket_nnz(nnzc)) == data.nnz
    stats = plan.stats(data.row_counts(), data.col_counts(), nnzc,
                       formats=["scoo"] * plan.n_buckets)
    assert all(s["format"] == "scoo" and "density" in s and "nnz_pad" in s
               for s in stats)
    with pytest.raises(ValueError, match="sort_by='nnz'"):
        plan_buckets(data.row_counts(), data.col_counts(), sort_by="nnz")


def test_mixed_format_fit_runs(choa_small):
    """A Bucketed that genuinely mixes CC and SCOO buckets fits fine."""
    data = choa_small
    plan = plan_buckets(data.row_counts(), data.col_counts(),
                        nnz_counts=data.nnz_counts(), max_buckets=2)
    bt = bucketize(data, dtype=jnp.float64, plan=plan, formats=["cc", "scoo"])
    assert [bucket_format(b) for b in bt.buckets] == ["cc", "scoo"]
    _, hist = fit(bt, Parafac2Options(rank=3, dtype=jnp.float64,
                                      backend="auto"),
                  max_iters=5, tol=0.0, seed=0)
    assert len(hist) == 5 and np.isfinite(hist).all()


def test_nnz_offsets_uniform():
    _, sc = _pair(DATASETS["edge"]())
    for b in sc.buckets:
        np.testing.assert_array_equal(
            np.asarray(b.nnz_offsets),
            np.arange(b.kb, dtype=np.int32) * b.n_pad)


# ---------------------------------------------------------------------------
# property tests (hypothesis when available, seeded replay otherwise)
# ---------------------------------------------------------------------------

from _hypothesis_compat import given, settings, st  # noqa: E402
from repro.sparse import SCOO_DENSITY_THRESHOLD, fixed_plan  # noqa: E402


def _random_geometry(seed):
    """A random ragged dataset spanning dense-ish and ultra-sparse subjects
    so the auto-router sees both sides of the threshold."""
    rng = np.random.default_rng(seed)
    n_cols = int(rng.integers(8, 60))
    subs = []
    for _ in range(int(rng.integers(3, 12))):
        n_rows = int(rng.integers(1, 40))
        cap = n_rows * n_cols
        nnz = int(rng.integers(1, min(cap, 200) + 1))
        subs.append(_subject(rng, n_rows, n_cols, nnz))
    return IrregularCOO(subjects=subs, n_cols=n_cols)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_route_formats_respects_density_threshold_property(seed):
    """For ANY geometry, the auto-router's per-bucket decision is exactly
    the 0.25 density rule (density measured over the padded CC cells)."""
    data = _random_geometry(seed)
    plan = plan_buckets(data.row_counts(), data.col_counts(),
                        nnz_counts=data.nnz_counts(),
                        max_buckets=int(np.random.default_rng(seed).integers(1, 5)),
                        row_align=4, col_align=4)
    dens = plan.bucket_densities(data.nnz_counts())
    fmts = route_formats(plan, data.nnz_counts(), format="auto")
    assert len(fmts) == plan.n_buckets
    for d, f in zip(dens, fmts):
        assert f == ("scoo" if d < SCOO_DENSITY_THRESHOLD else "cc")
    # forcing a format always overrides the density rule
    assert route_formats(plan, data.nnz_counts(), format="cc") == \
        ["cc"] * plan.n_buckets
    assert route_formats(plan, data.nnz_counts(), format="scoo") == \
        ["scoo"] * plan.n_buckets


def _device_nnz_and_sum(b):
    vals = np.asarray(b.vals, dtype=np.float64)
    return int(np.count_nonzero(vals)), float(vals.sum())


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_mixed_bucketize_roundtrips_nnz_property(seed):
    """bucketize(format="auto") over ANY geometry materializes every
    nonzero exactly once across its mixed CC/SCOO buckets — no drops, no
    duplicates (value sums match in both formats' staging paths)."""
    data = _random_geometry(seed)
    bt = bucketize(data, max_buckets=3, row_align=4, col_align=4,
                   format="auto", dtype=jnp.float64)
    assert bt.n_subjects == data.n_subjects
    got_nnz = 0
    got_sum = 0.0
    for b in bt.buckets:
        n, s = _device_nnz_and_sum(b)
        got_nnz += n
        got_sum += s
    want_sum = float(sum(s.vals.sum() for s in data.subjects))
    assert got_nnz == data.nnz
    np.testing.assert_allclose(got_sum, want_sum, rtol=1e-12)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_fixed_plan_roundtrips_nnz_property(seed):
    """The streaming service's pinned-geometry bucketize (fixed_plan) is
    also drop-free for any batch that fits the rectangle, in both formats."""
    data = _random_geometry(seed)
    i_pad = max(s.n_rows for s in data.subjects)
    c_pad = max(s.nonzero_cols().size for s in data.subjects)
    n_pad = max(s.nnz for s in data.subjects)
    for fmt in ("cc", "scoo"):
        plan = fixed_plan(data.n_subjects, i_pad, c_pad,
                          nnz_pad=n_pad if fmt == "scoo" else None)
        bt = bucketize(data, plan=plan, formats=[fmt], dtype=jnp.float64)
        got = sum(_device_nnz_and_sum(b)[0] for b in bt.buckets)
        assert got == data.nnz
