"""Router suite: the MTTKRP router (``auto``) and its three routes (jnp,
SCOO, fused Pallas kernels in interpret mode) against the jnp reference —
every stage across odd/unaligned shapes, empty buckets, padded subjects and
the mode1_reuse path, whole fits, dispatch counts and mixed precision, and
the pin of which route each bucket's stages run on. Off a TPU the fused
route is reached by patching the router's platform test; its kernels then
run in interpret mode."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.sparse import random_irregular, random_parafac2
from repro.core import Parafac2Options, bucketize, fit, init_state, als_step
import repro.core.backend as backend_mod
from repro.core.backend import (
    AutoBackend, BACKENDS, FusedBackend, MttkrpBackend, SparseBackend,
    dispatch_tally, get_backend)
from repro.core.spans import launches

JNP = get_backend("jnp")
FUSED = FusedBackend()

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


@pytest.fixture
def on_tpu(monkeypatch):
    """The router's platform test answers as on a TPU; the fused kernels
    still see the CPU and run in interpret mode."""
    monkeypatch.setattr(backend_mod, "_on_tpu", lambda: True)


# geometry sweep: odd/unaligned (R=5, col_align=4), kernel-aligned
# (R=8, col_align=128), rank-1, and subject padding inside buckets
GEOMETRIES = [
    dict(seed=0, K=13, J=37, R=5, col_align=4),
    dict(seed=1, K=9, J=200, R=8, col_align=128),
    dict(seed=2, K=7, J=21, R=1, col_align=8),
    dict(seed=3, K=11, J=50, R=6, col_align=4, subject_align=8),
]


@pytest.mark.parametrize("geom", GEOMETRIES[:2])
def test_mode1_reuse_parity(geom, on_tpu):
    """The SCOO route's mode-1 reuse identity Y_k V = Q_k^T (X_k V): its
    ``mode1_xkv_bucket`` must match its own non-reuse ``mode1_bucket``, and
    mode-3 from the cached Y_k V its non-reuse ``mode3_bucket`` — through
    the router, with the platform test answering as on a TPU."""
    seed, K, J, R = (geom[k] for k in ("seed", "K", "J", "R"))
    data = random_irregular(n_subjects=K, n_cols=J, max_rows=9,
                            avg_nnz_per_subject=18, seed=seed)
    bt = bucketize(data, max_buckets=2, dtype=jnp.float32, format="scoo",
                   col_align=geom["col_align"])
    rng = np.random.default_rng(seed)
    H = jnp.asarray(rng.standard_normal((R, R)), jnp.float32)
    V = jnp.asarray(rng.standard_normal((J, R)), jnp.float32)
    W = jnp.asarray(rng.standard_normal((K, R)), jnp.float32)
    auto = get_backend("auto")
    for b in bt.buckets:
        assert auto.route(b, R) == "scoo"
        Q = jnp.asarray(rng.standard_normal((b.kb, b.i_pad, R)), jnp.float32)
        Wb = jnp.take(W, b.subject_ids, 0)
        XkV = auto.xkv_bucket(b, V)
        proj = auto.project_bucket(b, Q)
        want = auto.mode1_bucket(b, proj, Wb, V)
        np.testing.assert_allclose(auto.mode1_xkv_bucket(b, Q, XkV, Wb),
                                   want, **TOL)
        # the dense jnp reference ties both to the same contract
        np.testing.assert_allclose(
            JNP.mode1_bucket(b, JNP.project_bucket(b, Q), Wb, V), want, **TOL)
        YkV = auto.ykv_bucket(b, proj, V)
        np.testing.assert_allclose(auto.mode3_bucket(b, proj, H, YkV=YkV),
                                   auto.mode3_bucket(b, proj, H, V), **TOL)


def test_empty_bucket_contributes_nothing():
    """A bucket whose subjects are all padding (mask 0) must contribute zero
    in every mode."""
    bt, Ycs, H, V, W = _setup(seed=4, K=6, J=30, R=4, col_align=4)
    b = bt.buckets[0]
    empty = dataclasses.replace(
        b, subject_mask=jnp.zeros_like(b.subject_mask),
        col_mask=jnp.zeros_like(b.col_mask))
    Yc = Ycs[0]
    for be in (JNP,):
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
    for be in (JNP,):
        m1 = be.mttkrp_mode1(bt_pad.buckets, Ycs_pad, V, W)
        m1_masked = be.mttkrp_mode1(
            bt_pad.buckets,
            [Yc * b.subject_mask[:, None, None]
             for b, Yc in zip(bt_pad.buckets, Ycs_pad)], V, W)
        np.testing.assert_allclose(m1, m1_masked, **TOL)
        m3 = be.mttkrp_mode3(bt_pad.buckets, Ycs_pad, V, H, K)
        assert m3.shape == (K, 4)


def test_auto_backend_matches_jnp_off_tpu():
    """The auto backend's array-level contract is the jnp one."""
    bt, Ycs, H, V, W = _setup(seed=6)
    auto = get_backend("auto")
    np.testing.assert_array_equal(
        np.asarray(auto.mttkrp_mode1(bt.buckets, Ycs, V, W)),
        np.asarray(JNP.mttkrp_mode1(bt.buckets, Ycs, V, W)))
    np.testing.assert_array_equal(
        np.asarray(auto.mttkrp_mode2(bt.buckets, Ycs, H, W, bt.n_cols)),
        np.asarray(JNP.mttkrp_mode2(bt.buckets, Ycs, H, W, bt.n_cols)))


def test_auto_dispatch_predicates(monkeypatch):
    """The router names one route per bucket from what it can observe:
    SCOO buckets always take ``scoo``; CC buckets take ``fused`` only on a
    TPU at f32 with R % 8 == 0 and C_pad % 128 == 0, ``jnp`` otherwise."""
    auto = AutoBackend()
    data = random_irregular(n_subjects=9, n_cols=200, max_rows=9,
                            avg_nnz_per_subject=18, seed=1)
    cc = bucketize(data, max_buckets=1, dtype=jnp.float32).buckets[0]
    sc = bucketize(data, max_buckets=1, dtype=jnp.float32,
                   format="scoo").buckets[0]
    cc64 = bucketize(data, max_buckets=1, dtype=jnp.float64).buckets[0]
    odd = bucketize(data, max_buckets=1, dtype=jnp.float32,
                    col_align=4).buckets[0]
    assert cc.c_pad % 128 == 0 and odd.c_pad % 128 != 0
    # off a TPU: every CC bucket on jnp, every SCOO bucket on scoo
    assert [auto.route(b, 8) for b in (cc, sc, cc64, odd)] == [
        "jnp", "scoo", "jnp", "jnp"]
    monkeypatch.setattr(backend_mod, "_on_tpu", lambda: True)
    assert [auto.route(b, 8) for b in (cc, sc, cc64, odd)] == [
        "fused", "scoo", "jnp", "jnp"]
    assert auto.route(cc, 5) == "jnp"           # odd rank
    assert auto.route(sc, 5) == "scoo"


def test_get_backend_resolution():
    assert sorted(BACKENDS) == ["auto", "jnp"]
    assert get_backend("jnp") is BACKENDS["jnp"]
    assert type(get_backend("jnp")) is MttkrpBackend
    assert isinstance(get_backend("auto"), AutoBackend)
    be = MttkrpBackend()
    assert get_backend(be) is be
    with pytest.raises(ValueError, match="unknown MTTKRP backend"):
        get_backend("cuda")
    # the routes are not backends a caller can name
    for name in ("pallas", "scoo", "fused"):
        with pytest.raises(ValueError, match=r"choose from \['auto', 'jnp'\]"):
            get_backend(name)


def _fit_data(seed=7, col_align=4):
    data, _ = random_parafac2(n_subjects=12, n_cols=24, max_rows=16, rank=3,
                              density=0.8, seed=seed)
    return bucketize(data, max_buckets=2, dtype=jnp.float32,
                     col_align=col_align)


# the fused route's geometry: C_pad a multiple of 128 and R a multiple of 8
FUSED_RANK = 8


def _fused_fit_data(seed=7):
    return _fit_data(seed, col_align=128)


@pytest.mark.parametrize("mode1_reuse", [True, False])
def test_fit_smoke_backend_trajectories(mode1_reuse, on_tpu):
    """fit() must run end-to-end through the router with the fit trajectory
    of the jnp reference — with the platform test answering as on a TPU,
    every bucket on the fused route, its kernels in interpret mode."""
    bt = _fused_fit_data()
    assert {get_backend("auto").route(b, FUSED_RANK)
            for b in bt.buckets} == {"fused"}
    hists = {}
    for backend in ("jnp", "auto"):
        opts = Parafac2Options(rank=FUSED_RANK, dtype=jnp.float32,
                               backend=backend, mode1_reuse=mode1_reuse)
        state, hist = fit(bt, opts, max_iters=5, tol=0.0, seed=0)
        assert np.isfinite(hist).all()
        hists[backend] = np.asarray(hist)
    np.testing.assert_allclose(hists["auto"], hists["jnp"],
                               rtol=2e-3, atol=2e-3)


def test_als_step_auto_backend_runs():
    """auto backend end-to-end through als_step (jnp off-TPU, the fused
    route on TPU — either way the step must be finite and jit-compatible)."""
    bt = _fit_data(seed=8)
    opts = Parafac2Options(rank=3, dtype=jnp.float32,
                           backend="auto")
    s0 = init_state(bt, opts, seed=0)
    s1 = jax.jit(lambda s: als_step(bt, s, opts))(s0)
    assert np.isfinite(float(s1.fit))


# ---------------------------------------------------------------------------
# fused route: stage parity, dispatch count, mixed precision
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
    fused route carries Q (never materializing Yc), so staged stages get
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
    stage, exactly like the jnp route."""
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
    ("jnp", 5.0), ("fused", 4.0)])
def test_dispatch_tally_per_iteration(backend, per_bucket, monkeypatch):
    """The fused route must collapse the staged 5 bucket-stage dispatches per
    ALS iteration to 4 — the exact-parity fusion floor (eigh and the H/V
    solves are global sync points; see kernels/fused.py). Ticks fire at trace
    time, so eval_shape counts one full als_step without running it."""
    bt = _fused_fit_data()
    if backend == "fused":
        monkeypatch.setattr(backend_mod, "_on_tpu", lambda: True)
    opts = Parafac2Options(rank=FUSED_RANK, dtype=jnp.float32,
                           backend="jnp" if backend == "jnp" else "auto")
    be = get_backend(opts.backend)
    assert {be.route(b, FUSED_RANK) for b in bt.buckets} == {backend}
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


@pytest.mark.parametrize("backend", ["jnp", "fused"])
def test_precision_fit_parity_choa(backend, monkeypatch):
    """bf16/f16 compute with f32 accumulation must land within 0.1pp of the
    f32 fit on the CHOA-like workload (20 iterations; rank 5 on jnp, rank 8
    on the fused route, whose geometry wants R % 8 == 0) — the mixed
    precision contract that makes ``precision`` a pure performance knob."""
    from repro.data import choa_like

    data = choa_like(scale=0.001, seed=0)
    bt = bucketize(data, max_buckets=2, dtype=jnp.float32)
    rank = 5
    if backend == "fused":
        monkeypatch.setattr(backend_mod, "_on_tpu", lambda: True)
        rank = FUSED_RANK
    fits = {}
    for prec in ("f32", "bf16", "f16"):
        opts = Parafac2Options(rank=rank, dtype=jnp.float32,
                               backend="jnp" if backend == "jnp" else "auto",
                               precision=prec)
        be = get_backend(opts.backend, prec)
        assert {be.route(b, rank) for b in bt.buckets} == {backend}
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
    the f32 default stays the shared singleton, and a configured router
    hands its precision to every route."""
    assert get_backend("auto") is BACKENDS["auto"]
    assert isinstance(get_backend("auto"), AutoBackend)
    be = get_backend("jnp", "bf16")
    assert type(be) is MttkrpBackend and be.precision == "bf16"
    assert get_backend("jnp", "bf16") is be          # cached
    assert get_backend("jnp", "f32") is BACKENDS["jnp"]
    auto16 = get_backend("auto", "f16")
    assert auto16.precision == "f16"
    assert {r.precision for r in auto16._routes.values()} == {"f16"}
    with pytest.raises(ValueError):
        MttkrpBackend(precision="int8")


def test_auto_fused_routing(monkeypatch):
    """AutoBackend's _fused_ok predicate: fused only on TPU, CC buckets,
    sub-f64 dtype, and kernel-aligned (R % 8, C_pad % 128) geometry."""
    auto = AutoBackend()
    bt_al, _, H, V, W = _setup(seed=1, K=9, J=200, R=8, col_align=128)
    b_al = bt_al.buckets[0]
    bt_odd, _, *_ = _setup(seed=0, K=13, J=37, R=5, col_align=4)
    b_odd = bt_odd.buckets[0]
    # off-TPU: never fused (interpret-mode DMA emulation is not a win)
    assert not auto._fused_ok(b_al, 8)
    monkeypatch.setattr(backend_mod, "_on_tpu", lambda: True)
    assert auto._fused_ok(b_al, 8)
    assert not auto._fused_ok(b_al, 5)       # odd rank
    assert not auto._fused_ok(b_odd, 8)      # C_pad not lane-aligned
    bt64 = bucketize(random_irregular(n_subjects=9, n_cols=200, max_rows=9,
                                      avg_nnz_per_subject=18, seed=1),
                     max_buckets=2, dtype=jnp.float64, col_align=128)
    assert not auto._fused_ok(bt64.buckets[0], 8)   # f64 stays staged


@pytest.mark.parametrize("engine", ["host", "scan"])
def test_fused_fit_matches_staged_trajectory(engine, on_tpu):
    """End-to-end: the fused route's fit trajectory tracks jnp under both
    the host and the device-resident scan engines."""
    bt = _fused_fit_data()
    hists = {}
    for backend in ("jnp", "auto"):
        opts = Parafac2Options(rank=FUSED_RANK, dtype=jnp.float32,
                               backend=backend, engine=engine, check_every=5)
        _, hist = fit(bt, opts, max_iters=5, tol=0.0, seed=0)
        assert np.isfinite(hist).all()
        hists[backend] = np.asarray(hist)
    np.testing.assert_allclose(hists["auto"], hists["jnp"],
                               rtol=2e-3, atol=2e-3)


# every bucket-level stage als_step calls, with the arguments it takes
_STAGES = ("xkv_bucket", "procrustes_b_bucket", "project_bucket",
           "mode1_xkv_bucket", "ykv_bucket", "mode1_bucket", "mode2_bucket",
           "mode3_bucket")


@pytest.mark.parametrize("fmt,route", [("cc", "fused"), ("scoo", "scoo")])
def test_route_pin(fmt, route, on_tpu, monkeypatch):
    """With the platform test answering as on a TPU, ``route(b)`` names one
    route per bucket and every bucket-level stage of that bucket runs on
    it: no stage lands on another route. The mode-1 reduction of a SCOO
    bucket (``mode1_xkv_bucket``) is jnp, like its mode-3: no Pallas call
    in its trace."""
    bt = bucketize(random_irregular(n_subjects=9, n_cols=200, max_rows=9,
                                    avg_nnz_per_subject=18, seed=1),
                   max_buckets=2, dtype=jnp.float32, format=fmt)
    R = FUSED_RANK
    ran = []

    def spy(cls, stage):
        inner = getattr(cls, stage)

        def call(self, b, *args, **kw):
            ran.append((self.name, stage))
            return inner(self, b, *args, **kw)
        return call

    for cls in (MttkrpBackend, SparseBackend, FusedBackend):
        for stage in _STAGES:
            monkeypatch.setattr(cls, stage, spy(cls, stage))
    auto = AutoBackend()
    rng = np.random.default_rng(0)
    H = jnp.asarray(rng.standard_normal((R, R)), jnp.float32)
    V = jnp.asarray(rng.standard_normal((bt.n_cols, R)), jnp.float32)
    for b in bt.buckets:
        assert auto.route(b, R) == route
        Q = jnp.asarray(rng.standard_normal((b.kb, b.i_pad, R)), jnp.float32)
        Wb = jnp.ones((b.kb, R), jnp.float32)
        ran.clear()
        XkV, _ = auto.procrustes_b_bucket(b, H, Wb, V)
        proj = auto.project_bucket(b, Q)
        auto.xkv_bucket(b, V)
        auto.mode1_bucket(b, proj, Wb, V)
        auto.mode2_bucket(b, proj, H, Wb)
        G = auto.ykv_bucket(b, proj, V)
        auto.mode3_bucket(b, proj, H, YkV=G)
        m1 = jax.make_jaxpr(
            lambda q, x, w: auto.mode1_xkv_bucket(b, q, x, w))(Q, XkV, Wb)
        assert {name for name, _ in ran} == {route}, ran
        assert {stage for _, stage in ran} == set(_STAGES)
        assert ("pallas_call" in str(m1)) == (route == "fused")
