"""Batched Procrustes/polar solvers: orthonormality + cross-method agreement."""
import numpy as np
import pytest
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.core.procrustes import polar_gram_eigh, polar_newton_schulz, polar_svd, solve_q


def _rand_b(seed, kb=6, i=20, r=5):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((kb, i, r)))


@pytest.mark.parametrize("method", ["svd", "gram_eigh", "newton_schulz"])
def test_orthonormal_columns(method):
    B = _rand_b(0)
    Q = solve_q(B, method)
    G = jnp.einsum("kir,kil->krl", Q, Q)
    eye = jnp.eye(5)[None]
    tol = 1e-6 if method != "newton_schulz" else 1e-3
    np.testing.assert_allclose(G, jnp.broadcast_to(eye, G.shape), atol=tol)


def test_gram_eigh_matches_svd():
    B = _rand_b(1)
    np.testing.assert_allclose(polar_gram_eigh(B), polar_svd(B), atol=1e-8)


def test_newton_schulz_matches_svd():
    B = _rand_b(2)
    np.testing.assert_allclose(polar_newton_schulz(B, iters=30), polar_svd(B), atol=1e-4)


def test_padded_rows_stay_zero():
    B = np.array(_rand_b(3), copy=True)
    B[:, 15:, :] = 0.0  # padding rows
    Q = np.asarray(polar_gram_eigh(jnp.asarray(B)))
    assert np.abs(Q[:, 15:, :]).max() == 0.0


def test_polar_maximizes_trace():
    """Procrustes optimality: Q = polar(B) maximizes tr(Q^T B) over orthonormal Q."""
    B = _rand_b(4, kb=3, i=10, r=4)
    Q = polar_svd(B)
    opt = jnp.einsum("kir,kir->k", Q, B)
    rng = np.random.default_rng(0)
    for _ in range(10):
        A = rng.standard_normal((3, 10, 4))
        Qr, _ = np.linalg.qr(A)
        other = np.einsum("kir,kir->k", Qr, np.asarray(B))
        assert (np.asarray(opt) >= other - 1e-8).all()


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), kb=st.integers(1, 5),
       i=st.integers(2, 16), r=st.integers(1, 6))
def test_property_gram_eigh_orthonormal(seed, kb, i, r):
    if i < r:
        i = r  # polar needs I >= R for full column rank in general
    B = _rand_b(seed, kb, i, r)
    Q = polar_gram_eigh(B)
    G = np.einsum("kir,kil->krl", np.asarray(Q), np.asarray(Q))
    np.testing.assert_allclose(G, np.broadcast_to(np.eye(r), G.shape), atol=1e-6)


def test_gram_eigh_rank_deficient_f32_matches_svd():
    """A subject with fewer rows than R has R - I null directions in its
    Gram. In f32 their eigenvalues are roundoff noise; giving them an inverse
    root put noise of order sqrt(roundoff) into Q, so the pseudo-polar
    factor must cut them off at the dtype's resolution."""
    rng = np.random.default_rng(0)
    B = rng.standard_normal((16, 3, 8))                    # I=3 < R=8
    ref = np.asarray(polar_svd(jnp.asarray(B)))            # f64
    Q = np.asarray(polar_gram_eigh(jnp.asarray(B, jnp.float32)))
    np.testing.assert_allclose(Q, ref, atol=1e-5)
