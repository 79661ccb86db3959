"""The plain reference agrees with the program's ``als_step`` on the CPU, on
the SCOO and the CC layout, and imports nothing of the program."""
import ast
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import gen, reference

HERE = Path(__file__).resolve().parents[1]
RANK = 8


def _cohort(n_subjects=300, seed=7):
    with open(HERE / "configs" / "choa-r40.json") as f:
        cfg = json.load(f)
    return gen.generate(dict(cfg, n_subjects=n_subjects), seed)


def _program_steps(cohort, fmt, steps, seed):
    """The program's ``als_step`` in float64, and the start V it drew."""
    import jax
    import jax.numpy as jnp

    from chipbench.cell import to_program
    from repro.core import Parafac2Options, bucketize, init_state
    from repro.core.parafac2 import als_step

    with jax.enable_x64(True):
        bt = bucketize(to_program(cohort), format=fmt, dtype=jnp.float64)
        opts = Parafac2Options(rank=RANK, backend="jnp", dtype=jnp.float64,
                               constraints={"v": "nonneg", "w": "nonneg"})
        state = init_state(bt, opts, seed)
        v0 = np.asarray(state.V, np.float64)
        step = jax.jit(lambda d, s: als_step(d, s, opts))
        out = []
        for _ in range(steps):
            state = step(bt, state)
            out.append({k: np.asarray(getattr(state, k), np.float64)
                        for k in ("H", "V", "W", "fit")})
    return out, v0


@pytest.mark.parametrize("fmt", ["scoo", "cc"])
def test_reference_agrees_with_als_step(fmt):
    """In float64 the program and the reference agree to rounding: the same
    algorithm, step for step, on either layout."""
    cohort = _cohort()
    prog, v0 = _program_steps(cohort, fmt, 3, seed=11)
    refs = reference.run(cohort, v0, 3, stated=np.float64)
    for p, r in zip(prog, refs):
        assert abs(float(p["fit"]) - r.fit) < 1e-10
        for leaf in ("H", "V", "W"):
            q = getattr(r, leaf)
            gap = np.linalg.norm(p[leaf] - q) / np.linalg.norm(q)
            assert gap < 1e-8, (leaf, gap)


def test_reference_fit_rises():
    cohort = _cohort(200, seed=3)
    v0 = np.random.default_rng(0).random((cohort.n_cols, RANK))
    fits = [f.fit for f in reference.run(cohort, v0, 6)]
    assert all(b >= a - 1e-12 for a, b in zip(fits, fits[1:]))
    assert 0.0 < fits[-1] < 1.0


def test_polar_is_orthonormal_on_the_rows_a_subject_has():
    rng = np.random.default_rng(1)
    ar = reference.Arith("f64")
    for m in (3, 40, 60):            # fewer, as many and more rows than R
        B = rng.standard_normal((5, m, 40))
        Q = reference.polar(B, ar)
        QtQ = np.einsum("kir,kil->krl", Q, Q)
        p = min(m, 40)
        ev = np.linalg.eigvalsh(QtQ)
        assert np.allclose(ev[:, -p:], 1.0, atol=1e-10)
        assert np.allclose(ev[:, :40 - p], 0.0, atol=1e-10)
        # Q maximises tr(Q^T B): the polar factor, not any orthonormal basis
        assert np.all(np.einsum("kir,kir->k", Q, B) > 0)


def test_reference_imports_nothing_of_the_program():
    tree = ast.parse((HERE / "reference.py").read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert "repro" not in names and "chipbench" not in names
