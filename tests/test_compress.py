"""Randomized compression stage (repro.core.compress) — ISSUE-8 layer.

Covers the four correctness claims of the DPar2-style rsvd pass:

* the spec registry parses through the same fail-fast grammar machinery as
  the constraint layer (unknown names list the registered preprocessors);
* the compressed fit reproduces the uncompressed fit on the fixed
  choa/0.002/rank-5/20-iter parity command — the documented tolerance is
  1e-3 relative (measured gap ~4e-5: the sketch captures >99.9% of the
  energy at the default sketch_dim 2*rank+8, and the residual-corrected
  final fit is EXACT at the expanded factors, so the gap is pure ALS-path
  divergence, not approximation bias);
* every engine runs the cores unchanged: host/scan/while bitwise-identical,
  mesh to collective-reduction tolerance;
* the SCOO path sketches without densifying yet agrees with CC to
  numerical precision (shared Ω), and rank-deficient slices produce
  exactly-zero basis columns, not NaNs.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    Parafac2Options, bucketize, fit, parse_preprocess_spec,
    preprocess_summary, register_preprocess)
from repro.core import compress as cmp_mod
from repro.core.compress import PreprocessDef
from repro.data import choa_like
from repro.sparse import plan_buckets, random_irregular

f64 = jnp.float64


@pytest.fixture(scope="module")
def choa_bt():
    data = choa_like(scale=0.002, seed=0)
    return bucketize(data, max_buckets=4, dtype=f64)


@pytest.fixture(scope="module")
def small_data():
    return random_irregular(n_subjects=24, n_cols=96, max_rows=64,
                            avg_nnz_per_subject=200, seed=3)


# ---------------------------------------------------------------------------
# spec parsing + registry (the constraint layer's grammar, fail-fast)
# ---------------------------------------------------------------------------

def test_parse_spec_canonicalizes():
    pp = parse_preprocess_spec("rsvd")
    assert (pp.name, pp.spec, pp.params) == ("rsvd", "rsvd", (0, 8, 1))
    pp = parse_preprocess_spec(" rsvd:12 ")
    assert pp.spec == "rsvd:12" and pp.params == (12, 8, 1)
    pp = parse_preprocess_spec("rsvd:12:4:2")
    assert pp.spec == "rsvd:12:4:2" and pp.params == (12, 4, 2)
    assert pp.param("q") == 2
    # identity terms drop out of a composition
    assert parse_preprocess_spec("none+rsvd:12").spec == "rsvd:12"
    assert parse_preprocess_spec("none").identity
    assert parse_preprocess_spec("").identity


def test_parse_spec_fail_fast_lists_registered():
    with pytest.raises(ValueError) as ei:
        parse_preprocess_spec("bogus:3")
    msg = str(ei.value)
    assert "registered preprocessors" in msg
    assert "rsvd" in msg and "none" in msg
    with pytest.raises(ValueError, match="integer expected"):
        parse_preprocess_spec("rsvd:abc")
    with pytest.raises(ValueError, match="negative"):
        parse_preprocess_spec("rsvd:-1")
    with pytest.raises(ValueError, match="at most"):
        parse_preprocess_spec("rsvd:1:2:3:4")
    with pytest.raises(ValueError, match="compose"):
        parse_preprocess_spec("rsvd:8+rsvd:9")


def test_sketch_dim_resolution_and_floor():
    assert parse_preprocess_spec("rsvd").sketch_dim(5) == 18      # 2*5 + 8
    assert parse_preprocess_spec("rsvd:12:4").sketch_dim(5) == 16
    with pytest.raises(ValueError, match="below the model rank"):
        parse_preprocess_spec("rsvd:3").sketch_dim(5)


def test_options_parse_compress_eagerly():
    with pytest.raises(ValueError, match="registered preprocessors"):
        Parafac2Options(rank=3, compress="bogus")
    assert Parafac2Options(rank=3).compress == "none"


def test_register_preprocess_roundtrip():
    register_preprocess("idtest", PreprocessDef())
    try:
        assert "idtest" in cmp_mod.available()
        assert parse_preprocess_spec("idtest").identity
    finally:
        cmp_mod._REGISTRY.pop("idtest", None)
        parse_preprocess_spec.cache_clear()


def test_preprocess_summary_block():
    assert preprocess_summary("none") == {"spec": "none"}
    assert preprocess_summary("rsvd:12:4:2", rank=5) == {
        "spec": "rsvd:12:4:2", "sketch_dim": 16, "power_iters": 2}


def test_fit_device_refuses_compressed_opts(choa_bt):
    from repro.core.engine import fit_device

    opts = Parafac2Options(rank=3, engine="scan", compress="rsvd", dtype=f64)
    with pytest.raises(ValueError, match="core ALS only"):
        fit_device(choa_bt, opts)


# ---------------------------------------------------------------------------
# the parity command: compressed vs uncompressed fit (documented tolerance)
# ---------------------------------------------------------------------------

def test_compressed_fit_matches_uncompressed_choa(choa_bt):
    """The fixed parity command: choa scale 0.002, rank 5, 20 iters.

    Tolerance: 1e-3 RELATIVE (measured ~4e-5). The default sketch
    (S = 2*rank + 8, one power iteration) captures >99.9% of the choa
    energy, and the final fit is residual-corrected on the original data,
    so any gap is ALS trajectory divergence — bounded well below the 1%
    acceptance bar."""
    opts = Parafac2Options(rank=5, dtype=f64)
    s_un, h_un = fit(choa_bt, opts, max_iters=20, tol=0.0, seed=0)
    opts_c = dataclasses.replace(opts, compress="rsvd")
    s_c, h_c = fit(choa_bt, opts_c, max_iters=20, tol=0.0, seed=0)
    assert len(h_c) == len(h_un) == 20
    rel = abs(h_c[-1] - h_un[-1]) / abs(h_un[-1])
    assert rel < 1e-3, f"compressed fit off by {rel:.2e} relative"
    # full-space factor shapes (H/V/W never lived in core coordinates)
    assert s_c.H.shape == s_un.H.shape
    assert s_c.V.shape == s_un.V.shape
    assert jax.tree_util.tree_structure(s_c.W) == \
        jax.tree_util.tree_structure(s_un.W)


def test_pass_through_when_sketch_not_smaller(small_data):
    """r + p >= every bucket's row pad: every bucket passes through and the
    core dataset IS the original data — the trajectory matches the
    uncompressed fit exactly (identical engine, identical inputs)."""
    bt = bucketize(small_data, max_buckets=2, dtype=f64)
    opts = Parafac2Options(rank=3, dtype=f64)
    pp = parse_preprocess_spec("rsvd:64:64")
    comp = pp.apply(bt, opts, seed=0)
    assert not any(cb.compressed for cb in comp.buckets)
    _, h_un = fit(bt, opts, max_iters=6, tol=0.0, seed=0)
    _, h_c = fit(bt, dataclasses.replace(opts, compress="rsvd:64:64"),
                 max_iters=6, tol=0.0, seed=0)
    np.testing.assert_allclose(h_c[:-1], h_un[:-1], rtol=0, atol=0)
    # the last entry is residual-corrected with a FRESH Procrustes Q (the
    # engine's history uses the step-start Q): it can only improve the fit,
    # and only by a one-step margin
    assert h_c[-1] >= h_un[-1] - 1e-12
    assert abs(h_c[-1] - h_un[-1]) < 5e-3


def test_expand_q_partial_isometry_and_exact_fit(choa_bt):
    """Expanded Q_k = P_k Q̃_k is a partial isometry on live subjects
    (QᵀQ idempotent — identity when the slice has full row rank, a 0/1
    projector otherwise), and exact_fit at the expanded factors equals the
    engine-reported core fit (the module's norm_sq identity, end to end)."""
    opts = Parafac2Options(rank=4, dtype=f64)
    pp = parse_preprocess_spec("rsvd")
    comp = pp.apply(choa_bt, opts, seed=0)
    state, hist = fit(comp.data, opts, max_iters=8, tol=0.0, seed=0)
    Qs = cmp_mod.expand_q(comp, state, opts)
    for b, Q in zip(choa_bt.buckets, Qs):
        QtQ = np.einsum("kir,kil->krl", np.asarray(Q), np.asarray(Q))
        live = np.asarray(b.subject_mask) > 0
        # atol 1e-4: polar_gram_eigh's eps-regularized inverse root leaves
        # near-null directions a hair between 0 and 1
        np.testing.assert_allclose(
            np.einsum("krl,klm->krm", QtQ[live], QtQ[live]), QtQ[live],
            atol=1e-4)
        # trace(QtQ) = number of orthonormal columns, never above the rank
        tr = np.einsum("krr->k", QtQ)
        assert (tr[live] <= opts.rank + 1e-4).all()
    # the norm_sq identity at identical factors: the core-space fit (small
    # cores, ORIGINAL norm) equals the full-space fit at the expanded Q
    from repro.core import parafac2 as p2
    from repro.core.backend import get_backend

    be = get_backend(opts.backend)
    Qcs = [p2._procrustes_project(cb.core, state.H, state.V, state.W,
                                  opts, i, be)[2]
           for i, cb in enumerate(comp.buckets)]
    core_fit = float(cmp_mod.exact_fit(comp.data, state, opts, Qcs))
    exact = float(cmp_mod.exact_fit(choa_bt, state, opts, Qs))
    assert abs(exact - core_fit) < 1e-10
    # fresh Q can only improve on the engine's step-start-Q history entry
    assert exact >= hist[-2] - 1e-12


# ---------------------------------------------------------------------------
# engine parity on the cores
# ---------------------------------------------------------------------------

def test_engine_parity_on_cores(choa_bt):
    """host / scan / while(check_every=0) are bitwise-identical on the
    compressed path (same data argument, same program); mesh agrees to
    collective-reduction tolerance."""
    base = Parafac2Options(rank=4, dtype=f64, compress="rsvd:10:6:1")
    s_host, h_host = fit(choa_bt, base, max_iters=8, tol=0.0, seed=0)
    for engine, check_every in (("scan", 4), ("scan", 0)):
        o = dataclasses.replace(base, engine=engine, check_every=check_every)
        s_e, h_e = fit(choa_bt, o, max_iters=8, tol=0.0, seed=0)
        assert np.asarray(s_e.V).tobytes() == np.asarray(s_host.V).tobytes(), \
            f"{engine}/ce={check_every} diverged from host on cores"
        np.testing.assert_allclose(h_e[-1], h_host[-1], rtol=1e-12)
    o = dataclasses.replace(base, engine="mesh", check_every=4)
    s_m, h_m = fit(choa_bt, o, max_iters=8, tol=0.0, seed=0)
    np.testing.assert_allclose(np.asarray(s_m.V), np.asarray(s_host.V),
                               atol=1e-8)
    np.testing.assert_allclose(h_m[-1], h_host[-1], atol=1e-8)


# ---------------------------------------------------------------------------
# SCOO-vs-CC sketch agreement + degenerate slices
# ---------------------------------------------------------------------------

def test_scoo_sketch_agrees_with_cc(small_data):
    """One shared Ω, one shared bucket plan: the SCOO segment-sum sketch and
    the CC dense sketch produce the same Y_k (and the same cores) to
    numerical precision — the sparse path never densifies yet loses
    nothing."""
    from repro.core.backend import get_backend
    from repro.kernels.sketch import gaussian_sketch

    rc, cc, nnzc = (small_data.row_counts(), small_data.col_counts(),
                    small_data.nnz_counts())
    plan = plan_buckets(rc, cc, max_buckets=2, nnz_counts=nnzc)
    bt_cc = bucketize(small_data, plan=plan, dtype=f64,
                      formats=["cc"] * plan.n_buckets)
    bt_scoo = bucketize(small_data, plan=plan, dtype=f64,
                        formats=["scoo"] * plan.n_buckets)
    be = get_backend("auto")
    key = jax.random.PRNGKey(7)
    Omega = gaussian_sketch(key, small_data.n_cols, 12, f64)
    for b_cc, b_scoo in zip(bt_cc.buckets, bt_scoo.buckets):
        np.testing.assert_array_equal(np.asarray(b_cc.subject_ids),
                                      np.asarray(b_scoo.subject_ids))
        Y_cc = np.asarray(be.sketch_bucket(b_cc, Omega))
        Y_scoo = np.asarray(be.sketch_bucket(b_scoo, Omega))
        np.testing.assert_allclose(Y_scoo, Y_cc[:, : Y_scoo.shape[1]],
                                   atol=1e-10)
    # end-to-end: same compressed fit from either layout
    opts = Parafac2Options(rank=3, dtype=f64, compress="rsvd:8:4:1")
    _, h_cc = fit(bt_cc, opts, max_iters=6, tol=0.0, seed=0)
    _, h_scoo = fit(bt_scoo, opts, max_iters=6, tol=0.0, seed=0)
    np.testing.assert_allclose(h_scoo[-1], h_cc[-1], atol=1e-8)


def test_degenerate_rank_deficient_slices():
    """Subjects with fewer independent rows than the sketch width get
    exactly-zero basis columns (polar_gram_eigh's degenerate limit) — no
    NaNs anywhere, basis columns orthonormal-or-zero, finite fit."""
    data = random_irregular(n_subjects=16, n_cols=64, max_rows=48,
                            avg_nnz_per_subject=60, seed=5)
    bt = bucketize(data, max_buckets=1, dtype=f64)
    opts = Parafac2Options(rank=3, dtype=f64)
    pp = parse_preprocess_spec("rsvd:10:6:2")     # S=16 < i_pad, > thin rows
    comp = pp.apply(bt, opts, seed=0)
    (cb,) = comp.buckets
    assert cb.compressed
    P = np.asarray(cb.basis)
    assert np.isfinite(P).all() and np.isfinite(np.asarray(cb.core.vals)).all()
    # PtP is an orthogonal projector of rank = the slice's effective row
    # rank: idempotent, trace bounded by the true row count — degenerate
    # slices shrink it instead of producing NaNs
    PtP = np.einsum("kis,kit->kst", P, P)
    np.testing.assert_allclose(
        np.einsum("kst,ktu->ksu", PtP, PtP), PtP, atol=1e-4)
    tr = np.einsum("kss->k", PtP)
    live = np.asarray(bt.buckets[0].subject_mask) > 0
    rows = np.asarray(bt.buckets[0].row_counts)
    assert (tr[live] <= rows[live] + 1e-6).all()
    assert (tr[~live] < 1e-12).all()           # padding subjects: zero basis
    state, hist = fit(bt, dataclasses.replace(opts, compress="rsvd:10:6:2"),
                      max_iters=5, tol=0.0, seed=0)
    assert np.isfinite(hist).all() and np.isfinite(float(state.fit))
