"""Backend-parity suite: the jnp and pallas(interpret=True) MTTKRP backends
must agree to f32 tolerance for all three modes, across odd/unaligned shapes,
empty buckets, padded subjects, and the mode1_reuse path — the contract that
makes ``Parafac2Options(backend=...)`` a pure performance knob."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.sparse import random_irregular, random_parafac2
from repro.core import Parafac2Options, bucketize, fit, init_state, als_step
from repro.core.backend import (
    AutoBackend, BACKENDS, FusedBackend, JnpBackend, PallasBackend,
    dispatch_tally, get_backend)
from repro.core.spans import launches

JNP = get_backend("jnp")
PAL = get_backend("pallas")
FUSED = get_backend("fused")

TOL = dict(rtol=1e-4, atol=1e-4)


def _setup(seed=0, K=13, J=37, R=5, col_align=4, subject_align=1, buckets=2,
           max_rows=9):
    """f32 bucketed data + factors; small-align geometry exercises odd C."""
    data = random_irregular(n_subjects=K, n_cols=J, max_rows=max_rows,
                            avg_nnz_per_subject=18, seed=seed)
    bt = bucketize(data, max_buckets=buckets, dtype=jnp.float32,
                   col_align=col_align, subject_align=subject_align)
    rng = np.random.default_rng(seed)
    H = jnp.asarray(rng.standard_normal((R, R)), jnp.float32)
    V = jnp.asarray(rng.standard_normal((J, R)), jnp.float32)
    W = jnp.asarray(rng.standard_normal((K, R)), jnp.float32)
    Ycs = [b.project(jnp.asarray(rng.standard_normal((b.kb, b.i_pad, R)),
                                 jnp.float32)) for b in bt.buckets]
    return bt, Ycs, H, V, W


# geometry sweep: odd/unaligned (R=5, col_align=4), kernel-aligned
# (R=8, col_align=128), rank-1, and subject padding inside buckets
GEOMETRIES = [
    dict(seed=0, K=13, J=37, R=5, col_align=4),
    dict(seed=1, K=9, J=200, R=8, col_align=128),
    dict(seed=2, K=7, J=21, R=1, col_align=8),
    dict(seed=3, K=11, J=50, R=6, col_align=4, subject_align=8),
]


@pytest.mark.parametrize("geom", GEOMETRIES)
def test_mode_parity(geom):
    bt, Ycs, H, V, W = _setup(**geom)
    K, J = bt.n_subjects, bt.n_cols
    np.testing.assert_allclose(PAL.mttkrp_mode1(bt.buckets, Ycs, V, W),
                               JNP.mttkrp_mode1(bt.buckets, Ycs, V, W), **TOL)
    np.testing.assert_allclose(PAL.mttkrp_mode2(bt.buckets, Ycs, H, W, J),
                               JNP.mttkrp_mode2(bt.buckets, Ycs, H, W, J), **TOL)
    np.testing.assert_allclose(PAL.mttkrp_mode3(bt.buckets, Ycs, V, H, K),
                               JNP.mttkrp_mode3(bt.buckets, Ycs, V, H, K), **TOL)


@pytest.mark.parametrize("geom", GEOMETRIES[:2])
def test_mode1_reuse_parity(geom):
    """YkV pre-computed (mode1_reuse) path: both backends must match each
    other AND their own non-reuse path."""
    bt, Ycs, H, V, W = _setup(**geom)
    for b, Yc in zip(bt.buckets, Ycs):
        Vg = b.gather_v(V)
        Wb = jnp.take(W, b.subject_ids, 0)
        YkV = JNP.ykv(Yc, Vg)
        # the shared Y_k V product itself must agree across backends
        np.testing.assert_allclose(PAL.ykv(Yc, Vg), YkV, **TOL)
        want = JNP.mode1(Yc, Vg, Wb, b.subject_mask)
        np.testing.assert_allclose(
            JNP.mode1(Yc, None, Wb, b.subject_mask, YkV=YkV), want, **TOL)
        np.testing.assert_allclose(
            PAL.mode1(Yc, None, Wb, b.subject_mask, YkV=YkV), want, **TOL)
        # mode-3 reuse entry point ties to the same contract
        want3 = JNP.mode3(Yc, Vg, H, b.subject_mask)
        np.testing.assert_allclose(
            PAL.mode3(Yc, None, H, b.subject_mask, YkV=YkV), want3, **TOL)


def test_empty_bucket_contributes_nothing():
    """A bucket whose subjects are all padding (mask 0) must contribute zero
    in every mode, for both backends."""
    bt, Ycs, H, V, W = _setup(seed=4, K=6, J=30, R=4, col_align=4)
    b = bt.buckets[0]
    empty = dataclasses.replace(
        b, subject_mask=jnp.zeros_like(b.subject_mask),
        col_mask=jnp.zeros_like(b.col_mask))
    Yc = Ycs[0]
    for be in (JNP, PAL):
        Wb = jnp.take(W, empty.subject_ids, 0)
        np.testing.assert_allclose(
            be.mode1(Yc, empty.gather_v(V), Wb, empty.subject_mask),
            np.zeros((4, 4)), atol=1e-6)
        np.testing.assert_allclose(
            be.mode2_compact(Yc, H, Wb, empty.col_mask, empty.subject_mask),
            np.zeros(Yc.shape).transpose(0, 2, 1), atol=1e-6)
        np.testing.assert_allclose(
            be.mode3(Yc, empty.gather_v(V), H, empty.subject_mask),
            np.zeros((empty.kb, 4)), atol=1e-6)


def test_padded_subjects_do_not_leak():
    """subject_align padding inside a bucket must not change whole-tensor
    results: compare against the same data bucketized without padding."""
    kw = dict(seed=5, K=10, J=40, R=4, col_align=4)
    bt_pad, Ycs_pad, H, V, W = _setup(subject_align=8, **kw)
    # corrupt the padded slots' Yc rows: masked slots must be ignored
    Ycs_pad = [
        jnp.where(b.subject_mask[:, None, None] > 0, Yc, 7.7)
        for b, Yc in zip(bt_pad.buckets, Ycs_pad)]
    K, J = bt_pad.n_subjects, bt_pad.n_cols
    for be in (JNP, PAL):
        m1 = be.mttkrp_mode1(bt_pad.buckets, Ycs_pad, V, W)
        m1_masked = be.mttkrp_mode1(
            bt_pad.buckets,
            [Yc * b.subject_mask[:, None, None]
             for b, Yc in zip(bt_pad.buckets, Ycs_pad)], V, W)
        np.testing.assert_allclose(m1, m1_masked, **TOL)
        m3 = be.mttkrp_mode3(bt_pad.buckets, Ycs_pad, V, H, K)
        assert m3.shape == (K, 4)


def test_auto_backend_matches_jnp_off_tpu():
    """On CPU the auto backend must dispatch every call to jnp."""
    bt, Ycs, H, V, W = _setup(seed=6)
    auto = get_backend("auto")
    if jax.default_backend() == "tpu":
        pytest.skip("auto dispatches to pallas on TPU")
    np.testing.assert_array_equal(
        np.asarray(auto.mttkrp_mode1(bt.buckets, Ycs, V, W)),
        np.asarray(JNP.mttkrp_mode1(bt.buckets, Ycs, V, W)))
    np.testing.assert_array_equal(
        np.asarray(auto.mttkrp_mode2(bt.buckets, Ycs, H, W, bt.n_cols)),
        np.asarray(JNP.mttkrp_mode2(bt.buckets, Ycs, H, W, bt.n_cols)))


def test_auto_dispatch_predicates(monkeypatch):
    """The auto backend's shape/dtype predicates, exercised for the TPU
    branch too (CI is CPU-only, so patch the platform probe)."""
    import repro.core.backend as backend_mod

    auto = AutoBackend()
    aligned = jnp.zeros((4, 8, 128), jnp.float32)
    ykv = jnp.zeros((4, 8, 8), jnp.float32)
    # off-TPU: everything dispatches to jnp regardless of geometry
    assert auto._pick(aligned) is auto._jnp
    assert auto._pick(ykv, reuse=True) is auto._jnp

    monkeypatch.setattr(backend_mod.jax, "default_backend", lambda: "tpu")
    assert auto._kernel_friendly(aligned)
    assert auto._pick(aligned) is auto._pallas
    assert not auto._kernel_friendly(jnp.zeros((4, 5, 128), jnp.float32))  # odd R
    assert not auto._kernel_friendly(jnp.zeros((4, 8, 96), jnp.float32))   # C % 128
    assert not auto._kernel_friendly(jnp.zeros((4, 8, 128), jnp.float64))  # f64
    assert not auto._kernel_friendly(None)
    # reuse entry points only need the sublane quantum on R
    assert auto._reuse_friendly(ykv)
    assert auto._pick(ykv, reuse=True) is auto._pallas
    assert not auto._reuse_friendly(jnp.zeros((4, 5, 5), jnp.float32))
    assert auto._kernel_friendly(jnp.zeros((4, 16, 256), jnp.bfloat16))


def test_get_backend_resolution():
    assert get_backend("jnp") is BACKENDS["jnp"]
    assert isinstance(get_backend("pallas"), PallasBackend)
    assert isinstance(get_backend("auto"), AutoBackend)
    be = JnpBackend()
    assert get_backend(be) is be
    with pytest.raises(ValueError, match="unknown MTTKRP backend"):
        get_backend("cuda")


def _fit_data(seed=7):
    data, _ = random_parafac2(n_subjects=12, n_cols=24, max_rows=16, rank=3,
                              density=0.8, seed=seed)
    return bucketize(data, max_buckets=2, dtype=jnp.float32, col_align=4)


@pytest.mark.parametrize("mode1_reuse", [True, False])
def test_fit_smoke_backend_trajectories(mode1_reuse):
    """fit() must run end-to-end through each backend with (near-)identical
    fit trajectories — backend="pallas" exercises kernels/ops.py throughout."""
    bt = _fit_data()
    hists = {}
    for backend in ("jnp", "pallas"):
        opts = Parafac2Options(rank=3, dtype=jnp.float32,
                               backend=backend, mode1_reuse=mode1_reuse)
        state, hist = fit(bt, opts, max_iters=5, tol=0.0, seed=0)
        assert np.isfinite(hist).all()
        hists[backend] = np.asarray(hist)
    np.testing.assert_allclose(hists["pallas"], hists["jnp"],
                               rtol=2e-3, atol=2e-3)


def test_als_step_auto_backend_runs():
    """auto backend end-to-end through als_step (picks jnp off-TPU, pallas
    on TPU — either way the step must be finite and jit-compatible)."""
    bt = _fit_data(seed=8)
    opts = Parafac2Options(rank=3, dtype=jnp.float32,
                           backend="auto")
    s0 = init_state(bt, opts, seed=0)
    s1 = jax.jit(lambda s: als_step(bt, s, opts))(s0)
    assert np.isfinite(float(s1.fit))


# ---------------------------------------------------------------------------
# fused megakernel backend: stage parity, dispatch count, mixed precision
# ---------------------------------------------------------------------------

def _setup_t(dtype, **geom):
    """Like _setup but with a selectable factor/value dtype (f64 parity)."""
    geom = dict(geom)
    seed, K, J, R = geom.pop("seed"), geom.pop("K"), geom.pop("J"), geom.pop("R")
    data = random_irregular(n_subjects=K, n_cols=J, max_rows=geom.pop("max_rows", 9),
                            avg_nnz_per_subject=18, seed=seed)
    bt = bucketize(data, max_buckets=geom.pop("buckets", 2), dtype=dtype, **geom)
    rng = np.random.default_rng(seed)
    H = jnp.asarray(rng.standard_normal((R, R)), dtype)
    V = jnp.asarray(rng.standard_normal((J, R)), dtype)
    W = jnp.asarray(rng.standard_normal((K, R)), dtype)
    Qs = [jnp.asarray(rng.standard_normal((b.kb, b.i_pad, R)), dtype)
          for b in bt.buckets]
    return bt, Qs, H, V, W


FUSED_TOLS = {jnp.float32: dict(rtol=1e-6, atol=1e-6),
              jnp.float64: dict(rtol=1e-12, atol=1e-12)}


@pytest.mark.parametrize("geom", GEOMETRIES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_stage_parity(geom, dtype):
    """Every fused ALS stage must reproduce the staged (jnp) pipeline exactly
    — f32 to 1e-6, f64 to 1e-12 — over odd/unaligned/padded geometries. The
    fused backend carries Q (never materializing Yc), so staged stages get
    Yc = b.project(Q) while fused stages get Q itself."""
    bt, Qs, H, V, W = _setup_t(dtype, **geom)
    tol = FUSED_TOLS[dtype]
    for b, Q in zip(bt.buckets, Qs):
        Vg = b.gather_v(V)
        Wb = jnp.take(W, b.subject_ids, 0)
        Yc = b.project(Q)
        # F1: X_k V + Procrustes input B in one slab pass
        XkV_s, B_s = JNP.procrustes_b_bucket(b, H, Wb, V, Vg)
        XkV_f, B_f = FUSED.procrustes_b_bucket(b, H, Wb, V, Vg)
        np.testing.assert_allclose(XkV_f, XkV_s, **tol)
        np.testing.assert_allclose(B_f, B_s, **tol)
        # F2: YkV-from-XkV + the M1 partial reduced in-dispatch
        np.testing.assert_allclose(
            FUSED.mode1_xkv_bucket(b, Q, XkV_s, Wb),
            JNP.mode1_xkv_bucket(b, Q, XkV_s, Wb), **tol)
        # F3: mode-2 compact directly from the slab (no Yc round-trip)
        np.testing.assert_allclose(
            FUSED.mode2_bucket(b, Q, H, Wb),
            JNP.mode2_bucket(b, Yc, H, Wb), **tol)
        # F4: G = Y_k V; mode-1/3 from it are the shared R x R algebra
        np.testing.assert_allclose(
            FUSED.ykv_bucket(b, Q, V), JNP.ykv_bucket(b, Yc, V), **tol)
        np.testing.assert_allclose(
            FUSED.mode1_bucket(b, Q, Wb, V), JNP.mode1_bucket(b, Yc, Wb, V),
            **tol)
        np.testing.assert_allclose(
            FUSED.mode3_bucket(b, Q, H, V), JNP.mode3_bucket(b, Yc, H, V),
            **tol)


def test_fused_empty_bucket_contributes_nothing():
    """All-padding subjects (mask 0) contribute zero through every fused
    stage, exactly like the staged backends."""
    bt, Qs, H, V, W = _setup_t(jnp.float32, seed=4, K=6, J=30, R=4, col_align=4)
    b, Q = bt.buckets[0], Qs[0]
    empty = dataclasses.replace(
        b, subject_mask=jnp.zeros_like(b.subject_mask),
        col_mask=jnp.zeros_like(b.col_mask))
    Wb = jnp.take(W, empty.subject_ids, 0)
    np.testing.assert_allclose(
        FUSED.mode1_xkv_bucket(empty, Q, Q, Wb), np.zeros((4, 4)), atol=1e-6)
    np.testing.assert_allclose(
        FUSED.mode2_bucket(empty, Q, H, Wb),
        np.zeros((empty.kb, empty.c_pad, 4)), atol=1e-6)
    np.testing.assert_allclose(
        FUSED.mode3_bucket(empty, Q, H, V), np.zeros((empty.kb, 4)), atol=1e-6)


@pytest.mark.parametrize("backend,per_bucket", [
    ("jnp", 5.0), ("pallas", 5.0), ("fused", 4.0)])
def test_dispatch_tally_per_iteration(backend, per_bucket):
    """The fused route must collapse the staged 5 bucket-stage dispatches per
    ALS iteration to 4 — the exact-parity fusion floor (eigh and the H/V
    solves are global sync points; see kernels/fused.py). Ticks fire at trace
    time, so eval_shape counts one full als_step without running it."""
    bt = _fit_data()
    opts = Parafac2Options(rank=3, dtype=jnp.float32, backend=backend)
    s0 = init_state(bt, opts, seed=0)
    with dispatch_tally() as tally:
        jax.eval_shape(lambda s: als_step(bt, s, opts), s0)
    assert launches(tally) / len(bt.buckets) == per_bucket
    if backend == "fused":
        # the separate projection dispatch is gone: Q is carried, Yc never
        # materialized
        assert "project" not in tally
    else:
        assert tally["project"] == len(bt.buckets)


@pytest.mark.parametrize("backend", ["jnp", "pallas", "fused"])
def test_precision_fit_parity_choa(backend):
    """bf16/f16 compute with f32 accumulation must land within 0.1pp of the
    f32 fit on the CHOA-like workload (rank 5, 20 iterations) — the mixed
    precision contract that makes ``precision`` a pure performance knob."""
    from repro.data import choa_like

    data = choa_like(scale=0.001, seed=0)
    bt = bucketize(data, max_buckets=2, dtype=jnp.float32)
    fits = {}
    for prec in ("f32", "bf16", "f16"):
        opts = Parafac2Options(rank=5, dtype=jnp.float32, backend=backend,
                               precision=prec)
        _, hist = fit(bt, opts, max_iters=20, tol=0.0, seed=0)
        assert np.isfinite(hist).all()
        fits[prec] = float(hist[-1])
    assert abs(fits["bf16"] - fits["f32"]) < 1e-3, fits
    assert abs(fits["f16"] - fits["f32"]) < 1e-3, fits


def test_precision_option_validation():
    with pytest.raises(ValueError, match="precision"):
        Parafac2Options(rank=3, precision="f8")
    with pytest.raises(ValueError, match="precision"):
        Parafac2Options(rank=3, precision="bf16", dtype=jnp.float64)
    # f64 data keeps the f64 accumulator: precision="f32" is the identity
    Parafac2Options(rank=3, precision="f32", dtype=jnp.float64)


def test_get_backend_precision_instances():
    """get_backend(name, precision) returns configured, cached instances;
    the f32 default stays the shared singleton."""
    assert get_backend("fused") is BACKENDS["fused"]
    assert isinstance(get_backend("fused"), FusedBackend)
    be = get_backend("jnp", "bf16")
    assert isinstance(be, JnpBackend) and be.precision == "bf16"
    assert get_backend("jnp", "bf16") is be          # cached
    assert get_backend("jnp", "f32") is BACKENDS["jnp"]
    assert get_backend("fused", "f16").precision == "f16"
    with pytest.raises(ValueError):
        JnpBackend(precision="int8")


def test_auto_fused_routing(monkeypatch):
    """AutoBackend's _fused_ok predicate: fused only on TPU, CC buckets,
    sub-f64 dtype, and kernel-aligned (R % 8, C_pad % 128) geometry."""
    import repro.core.backend as backend_mod

    auto = AutoBackend()
    bt_al, _, H, V, W = _setup(seed=1, K=9, J=200, R=8, col_align=128)
    b_al = bt_al.buckets[0]
    bt_odd, _, *_ = _setup(seed=0, K=13, J=37, R=5, col_align=4)
    b_odd = bt_odd.buckets[0]
    # off-TPU: never fused (interpret-mode DMA emulation is not a win)
    assert not auto._fused_ok(b_al, 8)
    monkeypatch.setattr(backend_mod.jax, "default_backend", lambda: "tpu")
    assert auto._fused_ok(b_al, 8)
    assert not auto._fused_ok(b_al, 5)       # odd rank
    assert not auto._fused_ok(b_odd, 8)      # C_pad not lane-aligned
    bt64 = bucketize(random_irregular(n_subjects=9, n_cols=200, max_rows=9,
                                      avg_nnz_per_subject=18, seed=1),
                     max_buckets=2, dtype=jnp.float64, col_align=128)
    assert not auto._fused_ok(bt64.buckets[0], 8)   # f64 stays staged


@pytest.mark.parametrize("engine", ["host", "scan"])
def test_fused_fit_matches_staged_trajectory(engine):
    """End-to-end: the fused backend's fit trajectory tracks jnp under both
    the host and the device-resident scan engines."""
    bt = _fit_data()
    hists = {}
    for backend in ("jnp", "fused"):
        opts = Parafac2Options(rank=3, dtype=jnp.float32, backend=backend,
                               engine=engine, check_every=5)
        _, hist = fit(bt, opts, max_iters=5, tol=0.0, seed=0)
        assert np.isfinite(hist).all()
        hists[backend] = np.asarray(hist)
    np.testing.assert_allclose(hists["fused"], hists["jnp"],
                               rtol=2e-3, atol=2e-3)
