"""Batched Procrustes/polar solvers: orthonormality + cross-method agreement."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.core.procrustes import (_polar_cols, _polar_rows, polar_gram_eigh,
                                   polar_newton_schulz, polar_svd, solve_q)
from repro.core.spans import dispatch_tally


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


# -- the Gram side: I x I row Gram when I < R, R x R column Gram otherwise --

def _padded_batch(seed, kb, i, r):
    """A bucket of subjects with 1..i real rows and zero-padded rows below:
    each real block has singular values in [1, 10], so its polar factor is
    well conditioned; subject 0 is all padding, subject 1 fills every row."""
    rng = np.random.default_rng(seed)
    B = np.zeros((kb, i, r))
    rows = rng.integers(1, i + 1, kb)
    rows[0], rows[1] = 0, i
    for k in range(1, kb):
        n = rows[k]
        m = min(n, r)
        U, _ = np.linalg.qr(rng.standard_normal((n, m)))
        V, _ = np.linalg.qr(rng.standard_normal((r, m)))
        B[k, :n] = (U * np.exp(rng.uniform(0.0, np.log(10.0), m))) @ V.T
    return B.astype(np.float32), rows


def _svd_pseudo_polar(B, rel_tol):
    """Float64 pseudo-polar factor keeping singular values with
    s^2 > rel_tol * s_max^2, the cutoff ``polar_gram_eigh`` puts on its
    Gram eigenvalues."""
    U, s, Vt = np.linalg.svd(np.asarray(B, np.float64), full_matrices=False)
    keep = s ** 2 > rel_tol * s[:, :1] ** 2
    return np.einsum("kil,kl,klr->kir", U, keep.astype(np.float64), Vt)


_SHAPES = [(3, 8), (8, 40), (24, 40), (40, 40), (168, 40)]


@pytest.mark.parametrize("i,r", _SHAPES)
def test_gram_eigh_f32_matches_f64_svd_pseudo_polar(i, r):
    B, rows = _padded_batch(i * 100 + r, 32, i, r)
    ref = _svd_pseudo_polar(B, r * float(np.finfo(np.float32).eps))
    Q = np.asarray(polar_gram_eigh(jnp.asarray(B)))
    assert Q.shape == B.shape and Q.dtype == np.float32
    np.testing.assert_allclose(Q, ref, atol=2e-5)
    padded = np.arange(i)[None, :] >= rows[:, None]
    assert np.abs(Q[padded]).max() == 0.0      # padded rows, and subject 0
    assert np.abs(Q[0]).max() == 0.0


@pytest.mark.parametrize("i,r", [s for s in _SHAPES if s[0] < s[1]])
def test_gram_sides_agree(i, r):
    """The row side and the column side are one polar factor: the column
    path run on a batch whose I < R gives what the row path gives."""
    B, _ = _padded_batch(7 * i + r, 32, i, r)
    rel_tol = r * float(np.finfo(np.float32).eps)
    rows = np.asarray(_polar_rows(jnp.asarray(B), rel_tol))
    cols = np.asarray(_polar_cols(jnp.asarray(B), rel_tol))
    np.testing.assert_allclose(rows, cols, atol=2e-5)
    np.testing.assert_allclose(np.asarray(polar_gram_eigh(jnp.asarray(B))),
                               rows, atol=0)


def _eigh_operand_shapes(jaxpr):
    """Shapes of the operands of every ``eigh`` in a jaxpr and its subjaxprs."""
    shapes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "eigh":
            shapes.append(tuple(eqn.invars[0].aval.shape))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    shapes += _eigh_operand_shapes(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    shapes += _eigh_operand_shapes(sub)
    return shapes


@pytest.mark.parametrize("i,r,side,gram", [
    (8, 40, "polar.gram_rows", (5, 8, 8)),
    (24, 40, "polar.gram_rows", (5, 24, 24)),
    (40, 40, "polar.gram_cols", (5, 40, 40)),
    (168, 40, "polar.gram_cols", (5, 40, 40))])
def test_gram_side_tally_and_eigh_operand(i, r, side, gram):
    """The side is chosen from B's static shape: one tally tick per call,
    and the eigh runs on the I x I Gram exactly when I < R."""
    B = jax.ShapeDtypeStruct((5, i, r), jnp.float32)
    with dispatch_tally() as tally:
        jaxpr = jax.make_jaxpr(polar_gram_eigh)(B)
    assert dict(tally) == {side: 1}
    assert _eigh_operand_shapes(jaxpr.jaxpr) == [gram]
