"""Correctness layer for the streaming service (launch/stream.py).

Covers the ISSUE-6 serving paths:
  * ``update_subjects`` against an independent dense numpy reference of the
    Q-then-w coordinate step (the same stage-3c math ``als_step`` runs,
    evaluated at FIXED H/V — ``als_step`` itself reports W solved against a
    Procrustes basis from the start of its step, so the reference, not the
    fitted W, is the ground truth here);
  * N appends + a cold drift refit reproducing a batch fit over the union
    dataset (f64; H/V bitwise, fit within 1e-8 — the service re-solves every
    subject's (Q_k, w_k) once after adopting refit factors, a
    coordinate-descent half-step that can only raise the fit);
  * CC vs SCOO append parity;
  * drift-threshold semantics (no refit below, exactly one above);
  * fail-fast payload validation and the tPARAFAC2 smooth anchor.

All tests run in f64 (tests/conftest.py enables jax x64 globally).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import Parafac2Options, bucketize, fit, update_subjects
from repro.core.nnls import hals_nnls
from repro.sparse import (
    IrregularCOO, plan_layout, random_irregular, random_parafac2)
from repro.launch.stream import (
    StreamService, synthetic_stream, validate_payload)

RANK = 3
TOL = dict(rtol=0, atol=1e-10)


def _data(seed=0, n_subjects=14, n_cols=36, max_rows=24, density=0.5,
          noise=0.05):
    data, _ = random_parafac2(
        n_subjects=n_subjects, n_cols=n_cols, max_rows=max_rows, rank=RANK,
        density=density, seed=seed, noise=noise)
    return data


def _opts(**kw):
    kw.setdefault("rank", RANK)
    kw.setdefault("dtype", jnp.float64)
    return Parafac2Options(**kw)


def _dense(s):
    X = np.zeros((s.n_rows, s.n_cols))
    X[s.rows, s.cols] = s.vals
    return X


def _bucketize_like_service(data, opts, fmt):
    """The exact batch-path bucketization StreamService uses for (re)fits."""
    rc, cc, nz = data.row_counts(), data.col_counts(), data.nnz_counts()
    plan, fmts = plan_layout(rc, cc, nz, format=fmt, max_buckets=4)
    return bucketize(data, dtype=opts.dtype, plan=plan, formats=fmts)


# ---------------------------------------------------------------------------
# update_subjects vs independent dense reference
# ---------------------------------------------------------------------------

def test_update_subjects_matches_dense_reference():
    """One inner iteration == the als_step stage-3 coordinate step at fixed
    H/V: SVD-polar Procrustes, then one HALS row solve, then the exact
    residual expansion — all reproduced independently in dense numpy."""
    data = _data(seed=1)
    opts = _opts(procrustes="svd")
    bt = _bucketize_like_service(data, opts, "cc")
    # enough iterations that every subject's B_k = X_k V S_k H^T is
    # well-conditioned — the polar factor (hence the reference) is only
    # unique for full-rank B_k
    state, _ = fit(bt, opts, max_iters=25, seed=0)
    H = np.asarray(state.H)
    V = np.asarray(state.V)
    W0 = np.asarray(state.W)

    W_new, resid = update_subjects(bt, state.H, state.V, opts,
                                   w_init=state.W, inner_iters=1)
    W_new, resid = np.asarray(W_new), np.asarray(resid)

    VtV = V.T @ V
    Phi = H.T @ H
    gram3 = VtV * Phi
    for k, s in enumerate(data.subjects):
        X = _dense(s)
        B = X @ V @ np.diag(W0[k]) @ H.T
        U, _, Vt = np.linalg.svd(B, full_matrices=False)
        Q = U @ Vt
        YkV = Q.T @ X @ V
        m = np.einsum("rl,rl->l", H, YkV)
        w_ref = np.asarray(hals_nnls(
            jnp.asarray(m[None]), jnp.asarray(gram3),
            jnp.asarray(W0[k][None]), sweeps=opts.nnls_sweeps))[0]
        r_ref = (np.sum(X * X)
                 - 2.0 * np.einsum("rl,rl,l->", H, YkV, w_ref)
                 + np.einsum("rl,rl,r,l->", Phi, VtV, w_ref, w_ref))
        np.testing.assert_allclose(W_new[k], w_ref, rtol=0, atol=1e-11)
        np.testing.assert_allclose(resid[k], r_ref, rtol=1e-11, atol=1e-11)


def test_update_subjects_cc_scoo_parity():
    """The incremental solve is format-agnostic: CC and SCOO buckets give
    the same rows/residuals to f64 roundoff."""
    data = _data(seed=2)
    opts = _opts()
    out = {}
    for fmt in ("cc", "scoo"):
        bt = _bucketize_like_service(data, opts, fmt)
        state, _ = fit(bt, opts, max_iters=6, seed=0)
        out[fmt] = state
    # same math path in fit → same factors; now compare the streaming solve
    # on a shared factor bundle across formats
    H, V, W = out["cc"].H, out["cc"].V, out["cc"].W
    res = {}
    for fmt in ("cc", "scoo"):
        bt = _bucketize_like_service(data, opts, fmt)
        res[fmt] = update_subjects(bt, H, V, opts, w_init=W, inner_iters=2)
    np.testing.assert_allclose(np.asarray(res["cc"][0]),
                               np.asarray(res["scoo"][0]), **TOL)
    np.testing.assert_allclose(np.asarray(res["cc"][1]),
                               np.asarray(res["scoo"][1]),
                               rtol=1e-9, atol=1e-9)


# ---------------------------------------------------------------------------
# stream parity with batch fits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["cc", "scoo"])
def test_stream_then_cold_refit_matches_batch_fit(fmt):
    """N appends followed by a cold refit reproduce a batch fit over the
    union dataset: same plan, same seed, same engine → bitwise H/V, and the
    service fit differs only by its post-refit re-solve (which cannot lower
    it)."""
    data = _data(seed=3)
    opts = _opts()
    warm, payloads = synthetic_stream(data, warm_frac=0.5, touch_frac=0.5,
                                      seed=3)
    svc, _ = StreamService.warm_start(
        warm, opts, iters=6, seed=0, batch_slots=4, drift_threshold=np.inf,
        format=fmt, refit="cold", refit_iters=30, refit_tol=1e-9)
    for p in payloads:
        svc.submit(p)
    svc.flush()

    # union of warm data + appends is EXACTLY the original dataset (new
    # subjects arrive in stream order, so compare as a multiset of slices)
    union = svc.union_data()
    assert union.n_subjects == data.n_subjects
    assert union.nnz == data.nnz
    assert (sorted((s.n_rows, _dense(s).tobytes()) for s in union.subjects)
            == sorted((s.n_rows, _dense(s).tobytes()) for s in data.subjects))

    info = svc.refit(mode="cold")
    bt = _bucketize_like_service(union, opts, fmt)
    ref_state, ref_hist = fit(bt, opts, max_iters=30, tol=1e-9, seed=0)
    np.testing.assert_array_equal(np.asarray(svc.H), np.asarray(ref_state.H))
    np.testing.assert_array_equal(np.asarray(svc.V), np.asarray(ref_state.V))
    assert info["fit"] == ref_hist[-1]
    # after adopting the refit factors the service re-solves every subject's
    # (Q_k, w_k) once to rebuild its residual ledger — a coordinate-descent
    # half-step, so stream_fit can only sit slightly ABOVE the batch fit
    # (~1e-4 at 30 unconverged iterations; exactly equal at convergence)
    assert svc.stream_fit >= ref_hist[-1] - 1e-12
    assert abs(svc.stream_fit - ref_hist[-1]) < 1e-3


def test_stream_cc_scoo_service_parity():
    """Serving the same append stream through CC and SCOO dispatch paths
    yields the same model."""
    data = _data(seed=4)
    opts = _opts()
    warm, payloads = synthetic_stream(data, warm_frac=0.5, touch_frac=0.4,
                                      seed=4)
    svcs = {}
    for fmt in ("cc", "scoo"):
        svc, _ = StreamService.warm_start(
            warm, opts, iters=6, seed=0, batch_slots=4,
            drift_threshold=np.inf, format=fmt)
        for p in payloads:
            svc.submit(p)
        svc.flush()
        svcs[fmt] = svc
    np.testing.assert_allclose(svcs["cc"].W, svcs["scoo"].W,
                               rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(svcs["cc"]._sub_resid, svcs["scoo"]._sub_resid,
                               rtol=1e-8, atol=1e-8)
    assert abs(svcs["cc"].stream_fit - svcs["scoo"].stream_fit) < 1e-9


# ---------------------------------------------------------------------------
# drift / refit policy
# ---------------------------------------------------------------------------

def _drifting_stream(seed=5):
    """Warm population from a low-rank model; appends from an unrelated
    random tensor, so the frozen factors fit them poorly and drift grows."""
    warm = _data(seed=seed, n_subjects=10)
    junk = random_irregular(n_subjects=6, n_cols=warm.n_cols, max_rows=20,
                            avg_nnz_per_subject=60, seed=seed + 1)
    payloads = [{"rows": s.rows.tolist(), "cols": s.cols.tolist(),
                 "vals": (8.0 * s.vals).tolist(), "n_rows": s.n_rows}
                for s in junk.subjects]
    return warm, payloads


def test_drift_threshold_no_refit_below_one_above():
    warm, payloads = _drifting_stream()
    opts = _opts()

    # measuring run: unbounded threshold → zero refits, drift per batch
    svc, _ = StreamService.warm_start(warm, opts, iters=8, seed=0,
                                      batch_slots=2, drift_threshold=np.inf)
    drifts = []
    for p in payloads:
        svc.submit(p)
        svc.flush()           # one batch (or less) per flush
        drifts.append(svc.drift)
    assert svc.stats()["refits"] == 0
    assert max(drifts) > 0.0

    # threshold above every observed drift → still no refit
    svc_hi, _ = StreamService.warm_start(
        warm, opts, iters=8, seed=0, batch_slots=2,
        drift_threshold=max(drifts) * 1.01)
    for p in payloads:
        svc_hi.submit(p)
    svc_hi.flush()
    assert svc_hi.stats()["refits"] == 0

    # threshold below the first batch's drift → exactly one refit,
    # triggered by that batch, and the refit resets drift below threshold
    thresh = drifts[0] * 0.9
    svc_one, _ = StreamService.warm_start(
        warm, opts, iters=8, seed=0, batch_slots=2, drift_threshold=thresh,
        refit_iters=10)
    svc_one.submit(payloads[0])
    svc_one.flush()
    st = svc_one.stats()
    assert st["refits"] == 1
    assert st["refit_at"] == [1]
    assert st["drift"] <= thresh  # refit reset the baseline
    assert svc_one.baseline_fit >= svc_one.stream_fit - 1e-12


# ---------------------------------------------------------------------------
# smooth anchor + payload validation
# ---------------------------------------------------------------------------

def test_smooth_anchor_pulls_touched_rows_toward_previous():
    data = _data(seed=6)
    opts = _opts()
    warm, payloads = synthetic_stream(data, warm_frac=0.7, touch_frac=1.0,
                                      holdout_frac=0.5, seed=6)
    touched = [p for p in payloads if "subject" in p]
    assert touched, "stream must contain accrual payloads for this test"
    moves = {}
    for lam in (0.0, 1e4):
        svc, _ = StreamService.warm_start(
            warm, opts, iters=6, seed=0, batch_slots=4,
            drift_threshold=np.inf, smooth_lam=lam, inner_iters=1)
        deltas = []
        for p in touched:
            w_before = svc.W[p["subject"]].copy()
            r = svc.append(p)
            deltas.append(float(np.linalg.norm(r.w_row - w_before)))
        moves[lam] = np.mean(deltas)
    # a huge anchor must pin the streamed rows to their previous values
    assert moves[1e4] < 0.05 * max(moves[0.0], 1e-12) or moves[1e4] < 1e-8


def test_payload_validation_fails_fast():
    n_cols, n_known = 16, 3
    ok = {"rows": [0, 1], "cols": [2, 3], "vals": [1.0, 2.0]}
    sid, block = validate_payload(dict(ok), n_cols, n_known)
    assert sid is None and block.nnz == 2 and block.n_rows == 2

    bad = [
        ("must be a mapping", [1, 2, 3]),
        ("missing required key", {"rows": [0], "cols": [0]}),
        ("lengths differ", {**ok, "vals": [1.0]}),
        ("no observations", {"rows": [], "cols": [], "vals": []}),
        ("negative row", {**ok, "rows": [-1, 0]}),
        ("column ids", {**ok, "cols": [0, n_cols]}),
        ("finite", {**ok, "vals": [1.0, float("nan")]}),
        ("n_rows", {**ok, "n_rows": 1}),
        ("subject id", {**ok, "subject": n_known}),
        ("subject id must be an int", {**ok, "subject": "zero"}),
        ("not numeric", {**ok, "vals": ["a", "b"]}),
    ]
    for msg, payload in bad:
        with pytest.raises(ValueError, match=msg):
            validate_payload(payload, n_cols, n_known)


def test_service_rejects_bad_config():
    data = _data(seed=7, n_subjects=4)
    opts = _opts()
    with pytest.raises(ValueError, match="w_layout"):
        StreamService(data.subjects, data.n_cols,
                      _opts(w_layout="bucketed"),
                      H=np.eye(RANK), V=np.zeros((data.n_cols, RANK)),
                      W=np.ones((4, RANK)))
    with pytest.raises(ValueError, match="refit"):
        StreamService(data.subjects, data.n_cols, opts, H=np.eye(RANK),
                      V=np.zeros((data.n_cols, RANK)), W=np.ones((4, RANK)),
                      refit="lukewarm")
    with pytest.raises(ValueError, match="format"):
        StreamService(data.subjects, data.n_cols, opts, H=np.eye(RANK),
                      V=np.zeros((data.n_cols, RANK)), W=np.ones((4, RANK)),
                      format="csr")


def test_padded_dispatch_reuses_one_geometry():
    """Appends with similar shapes share one compiled (geometry, format)
    entry — the jit-cache-stability property the service is built around."""
    data = _data(seed=8, n_subjects=12, max_rows=16)
    opts = _opts()
    warm, payloads = synthetic_stream(data, warm_frac=0.5, touch_frac=0.0,
                                      seed=8)
    svc, _ = StreamService.warm_start(warm, opts, iters=4, seed=0,
                                      batch_slots=2, drift_threshold=np.inf,
                                      format="cc", row_align=32, col_align=64)
    for p in payloads:
        svc.submit(p)
    svc.flush()
    st = svc.stats()
    assert st["appends"] == len(payloads)
    # generous alignment → every batch fits the first pinned rectangle
    assert st["compiled_geometries"] == 1
