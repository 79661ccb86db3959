"""End-to-end PARAFAC2-ALS behaviour: monotone fit, recovery, option parity."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.sparse import random_parafac2, random_irregular
from repro.core import bucketize, Parafac2Options, Parafac2State, als_step, fit, init_state
from repro.core.parafac2 import reconstruct_uk


def _exact_data(seed=1, K=20, J=30, R=4):
    data, truth = random_parafac2(
        n_subjects=K, n_cols=J, max_rows=25, rank=R, density=1.0, seed=seed
    )
    return bucketize(data, max_buckets=2, dtype=jnp.float64), truth


def test_fit_monotone_nondecreasing():
    bt, _ = _exact_data()
    opts = Parafac2Options(rank=4, dtype=jnp.float64)
    _, hist = fit(bt, opts, max_iters=40, tol=0.0)
    diffs = np.diff(hist)
    assert (diffs > -1e-8).all(), f"fit decreased: min diff {diffs.min()}"


def test_exact_recovery_high_fit():
    bt, _ = _exact_data()
    opts = Parafac2Options(rank=4, dtype=jnp.float64)
    _, hist = fit(bt, opts, max_iters=250, tol=1e-12)
    assert hist[-1] > 0.95, hist[-1]


def test_sparse_data_fit_reasonable():
    data, _ = random_parafac2(
        n_subjects=25, n_cols=40, max_rows=20, rank=3, density=0.5, seed=3
    )
    bt = bucketize(data, max_buckets=3, dtype=jnp.float64)
    opts = Parafac2Options(rank=3, dtype=jnp.float64)
    _, hist = fit(bt, opts, max_iters=30, tol=0.0)
    assert hist[-1] > 0.3
    assert (np.diff(hist) > -1e-8).all()


@pytest.mark.parametrize("method", ["svd", "gram_eigh", "newton_schulz"])
def test_procrustes_methods_equivalent_fit(method):
    bt, _ = _exact_data(seed=5)
    opts = Parafac2Options(rank=4, procrustes=method, dtype=jnp.float64)
    _, hist = fit(bt, opts, max_iters=30, tol=0.0)
    assert hist[-1] > 0.7, (method, hist[-1])


def test_mode1_reuse_bitwise_equivalent():
    """The beyond-paper mode-1 cache must not change a single iteration."""
    bt, _ = _exact_data(seed=9)
    base = Parafac2Options(rank=4, mode1_reuse=False, dtype=jnp.float64)
    reuse = Parafac2Options(rank=4, mode1_reuse=True, dtype=jnp.float64)
    s0 = init_state(bt, base, seed=0)
    s_a = als_step(bt, s0, base)
    s_b = als_step(bt, s0, reuse)
    np.testing.assert_allclose(s_a.H, s_b.H, atol=1e-9)
    np.testing.assert_allclose(s_a.V, s_b.V, atol=1e-9)
    np.testing.assert_allclose(s_a.W, s_b.W, atol=1e-9)
    np.testing.assert_allclose(s_a.fit, s_b.fit, atol=1e-9)


def test_nonneg_factors_are_nonneg():
    bt, _ = _exact_data(seed=11)
    opts = Parafac2Options(rank=4, dtype=jnp.float64)
    state, _ = fit(bt, opts, max_iters=15, tol=0.0)
    assert (np.asarray(state.V) >= 0).all()
    assert (np.asarray(state.W) >= 0).all()


def test_uk_orthogonality_structure():
    """U_k^T U_k must be (approximately) invariant over k: the PARAFAC2
    constraint the Q_k H factorization enforces by construction."""
    bt, _ = _exact_data(seed=13)
    opts = Parafac2Options(rank=4, dtype=jnp.float64)
    state, _ = fit(bt, opts, max_iters=50, tol=0.0)
    uks = reconstruct_uk(bt, state, opts)
    grams = [u.T @ u for u in uks.values() if u.shape[0] >= 4]
    ref = grams[0]
    for g in grams[1:]:
        np.testing.assert_allclose(g, ref, atol=1e-6)


def test_bucketed_w_layout_equivalent():
    """w_layout='bucketed' (production shard-aligned W) must produce the same
    iterates as the global [K,R] layout."""
    from repro.core.parafac2 import w_global

    bt, _ = _exact_data(seed=21)
    g = Parafac2Options(rank=4, dtype=jnp.float64, w_layout="global")
    b = Parafac2Options(rank=4, dtype=jnp.float64, w_layout="bucketed")
    sg = init_state(bt, g, seed=0)
    sb = init_state(bt, b, seed=0)
    for _ in range(3):
        sg = als_step(bt, sg, g)
        sb = als_step(bt, sb, b)
    np.testing.assert_allclose(sg.H, sb.H, atol=1e-9)
    np.testing.assert_allclose(sg.V, sb.V, atol=1e-9)
    np.testing.assert_allclose(sg.W, np.asarray(w_global(bt, sb.W)), atol=1e-9)
    np.testing.assert_allclose(float(sg.fit), float(sb.fit), atol=1e-9)


def test_reconstruction_error_matches_fit():
    """fit reported by als_step equals explicit residual computation."""
    data, _ = random_parafac2(
        n_subjects=10, n_cols=20, max_rows=15, rank=3, density=1.0, seed=17
    )
    bt = bucketize(data, max_buckets=2, dtype=jnp.float64)
    opts = Parafac2Options(rank=3, dtype=jnp.float64)
    state, _ = fit(bt, opts, max_iters=25, tol=0.0)
    uks = reconstruct_uk(bt, state, opts)
    V, W = np.asarray(state.V), np.asarray(state.W)
    sq = 0.0
    for k, sub in enumerate(data.subjects):
        Xk = sub.to_dense()
        Uk = uks[k]
        recon = Uk @ np.diag(W[k]) @ V.T
        sq += np.linalg.norm(Xk - recon) ** 2
    explicit_fit = 1.0 - np.sqrt(sq) / np.sqrt(data.frobenius_sq())
    # reconstruct_uk recomputes Q_k against the FINAL factors — one extra
    # Procrustes half-step — so the explicit fit may only be >= the reported
    # one, and both agree tightly near convergence.
    assert explicit_fit >= float(state.fit) - 1e-8
    np.testing.assert_allclose(float(state.fit), explicit_fit, atol=1e-3)


def test_als_step_asks_for_f32_matmuls():
    """A TPU runs an f32 matmul as one bf16 pass unless asked for more; at
    that precision the fit broke down on a v5e. Every matmul of the step
    must carry HIGHEST precision (the CPU runs it the same either way)."""
    data, _ = random_parafac2(n_subjects=12, n_cols=16, max_rows=10, rank=3,
                              density=0.5, seed=0)
    bt = bucketize(data, max_buckets=2, dtype=jnp.float32)
    opts = Parafac2Options(rank=3)
    st = init_state(bt, opts, 0)
    text = jax.jit(lambda d, s: als_step(d, s, opts)).lower(bt, st).as_text()
    dots = [l for l in text.splitlines() if "stablehlo.dot_general" in l]
    assert dots and all("HIGHEST" in l for l in dots)
