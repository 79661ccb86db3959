"""The mode-2 segment sum: ``kernels/segsum.py``'s segmented prefix sums and
``core/spartan.py::mode2_scatter`` on top of them, against float64 oracles.
Each sum must be accumulated from its own segment's terms only."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import spartan
from repro.kernels import segsum

F32_EPS = float(np.finfo(np.float32).eps)


def _route(monkeypatch, path):
    """Steer ``sorted_segment_sum``: ``segment_sum`` as off the TPU, or the
    TPU's Pallas kernel, which runs here in interpret mode."""
    if path == "pallas":
        monkeypatch.setattr(segsum, "_on_tpu", lambda: True)


@pytest.fixture(params=["segment_sum", "pallas"])
def path(request, monkeypatch):
    _route(monkeypatch, request.param)
    return request.param


def _ids(case, rng, n):
    if case == "random":
        return np.sort(rng.integers(0, 50, n)), 50
    if case == "empty":                  # only even segments hold rows
        return np.sort(2 * rng.integers(0, 100, n)), 201
    # compact pads: zero rows routed to segment 0, ahead of the real rows
    pads = n // 3
    return np.sort(np.r_[np.zeros(pads, int), rng.integers(1, 40, n - pads)]), 40


def _draw(case, dtype, n=1000, r=6):
    rng = np.random.default_rng(3)
    ids, J = _ids(case, rng, n)
    x = rng.standard_normal((n, r)) * rng.choice([1e-3, 1.0, 1e3], (n, 1))
    x[ids == 0] = 0.0 if case == "pads" else x[ids == 0]
    return x.astype(dtype), ids, J


# the kernel sums f32 rows only: the chip has no f64
@pytest.mark.parametrize("path,dtype", [("segment_sum", np.float32),
                                        ("segment_sum", np.float64),
                                        ("pallas", np.float32)])
@pytest.mark.parametrize("case", ["random", "empty", "pads"])
def test_segment_sums_match_f64_oracle(monkeypatch, path, dtype, case):
    _route(monkeypatch, path)
    x, ids, J = _draw(case, dtype)
    got = np.asarray(segsum.sorted_segment_sum(
        jnp.asarray(x), jnp.asarray(ids, jnp.int32), J))
    assert got.dtype == dtype and got.shape == (J, x.shape[1])
    want = np.zeros((J, x.shape[1]))
    mass = np.zeros((J, x.shape[1]))
    np.add.at(want, ids, x.astype(np.float64))
    np.add.at(mass, ids, np.abs(x.astype(np.float64)))
    eps = float(np.finfo(dtype).eps)
    # every segment within a few eps of its own terms' mass
    assert np.all(np.abs(got - want) <= 16 * eps * mass)
    assert np.all(got[mass.sum(1) == 0] == 0.0)


def _oracle_prefix(x, ids):
    """f64 segmented prefix sums of x and of |x|, row by row."""
    x = x.astype(np.float64)
    out, mass = np.empty_like(x), np.empty_like(x)
    for i in range(len(x)):
        cont = i > 0 and ids[i] == ids[i - 1]
        out[i] = x[i] + (out[i - 1] if cont else 0)
        mass[i] = np.abs(x[i]) + (mass[i - 1] if cont else 0)
    return out, mass


@pytest.mark.parametrize("case", ["random", "empty", "pads"])
def test_pallas_segmented_prefix_matches_f64_oracle(case):
    """Every row of the kernel's prefix, not only the segment ends, with
    blocks of 256 rows so that the carry crosses tiles and blocks."""
    x, ids, _ = _draw(case, np.float32)
    y = np.asarray(segsum.segmented_cumsum_pallas(
        jnp.asarray(x), segsum.segment_starts(jnp.asarray(ids)), block=256,
        interpret=True))
    assert y.dtype == np.float32 and y.shape == x.shape
    ref, mass = _oracle_prefix(x, ids)
    assert np.all(np.abs(y - ref) <= 16 * F32_EPS * mass)
    if case == "pads":
        assert np.all(y[ids == 0] == 0.0)


def _cumsum_and_difference_f32(x, ids, J):
    """What mode2_scatter computed before: one running f32 total over all
    sorted rows, differenced at the segment ends."""
    cs = np.concatenate([np.zeros((1, x.shape[1]), np.float32),
                         np.cumsum(x, axis=0, dtype=np.float32)])
    ends = np.searchsorted(ids, np.arange(1, J + 1))
    return np.diff(cs[ends], axis=0, prepend=np.zeros((1, x.shape[1]),
                                                      np.float32))


def test_a_tiny_column_after_a_heavy_one_keeps_its_digits(path):
    """A long run of large terms, then a column of a few tiny ones: the
    running total's difference misses the tiny column by far more than
    1e-3; the per-segment sum is within 1e-6 of the f64 oracle. The large
    terms are whole numbers whose sum stays below 2**24, so that their own
    column sums exactly in f32 in any order."""
    rng = np.random.default_rng(5)
    heavy, r = 2_000, 4
    x = np.concatenate([rng.integers(1000, 8000, (heavy, r)),
                        1e-3 * (1 + rng.random((3, r)))]).astype(np.float32)
    ids = np.r_[np.zeros(heavy, int), np.ones(3, int)]
    want = np.stack([x[ids == j].astype(np.float64).sum(0) for j in (0, 1)])
    old = _cumsum_and_difference_f32(x, ids, 2)
    assert np.max(np.abs(old[1] - want[1]) / np.abs(want[1])) > 1e-3
    got = np.asarray(spartan.mode2_scatter(
        jnp.asarray(x)[None], jnp.asarray(ids, jnp.int32)[None], 2))
    assert got.dtype == np.float32
    assert np.all(np.abs(got - want) / np.abs(want) <= 1e-6)


def test_mode2_scatter_matches_f64_oracle_on_zipf_columns(path):
    """A MovieLens-shaped draw: distinct Zipf-popular columns per subject
    (one rating per movie), ragged counts, zero pads in column 0, and
    columns no subject has."""
    rng = np.random.default_rng(11)
    kb, c, r, J = 48, 256, 40, 3000
    p = 1.0 / np.arange(1, J + 1)
    p /= p.sum()
    cols = np.zeros((kb, c), np.int32)
    mask = np.zeros((kb, c), np.float32)
    for k in range(kb):
        n = rng.integers(20, c + 1)
        cols[k, :n] = rng.choice(J, n, replace=False, p=p)
        mask[k, :n] = 1.0
    A = (rng.standard_normal((kb, c, r)) * mask[..., None]).astype(np.float32)
    want = np.zeros((J, r))
    np.add.at(want, cols.reshape(-1), A.reshape(-1, r).astype(np.float64))
    got = np.asarray(spartan.mode2_scatter(jnp.asarray(A), jnp.asarray(cols),
                                           J))
    assert got.dtype == np.float32 and got.shape == (J, r)
    mass = np.zeros((J, r))
    np.add.at(mass, cols.reshape(-1), np.abs(A.reshape(-1, r)))
    assert np.all(np.abs(got - want) <= 16 * F32_EPS * mass)
    untouched = mass.sum(1) == 0
    assert untouched.any() and np.all(got[untouched] == 0.0)
