"""The plain reference agrees with the program's ``als_step`` on the CPU on
the MovieLens ratings cohort too, at a small share of its users: wide J,
Zipf-popular columns and hundreds of ratings per user, on either layout."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import gen, reference
from test_reference import _program_steps

HERE = Path(__file__).resolve().parents[1]
N_SUBJECTS = 60


def _cohort(seed=7):
    with open(HERE / "configs" / "movielens-r40.json") as f:
        cfg = json.load(f)
    return gen.generate(dict(cfg, n_subjects=N_SUBJECTS), seed)


@pytest.mark.parametrize("fmt", ["scoo", "cc"])
def test_reference_agrees_with_als_step_movielens(fmt):
    """In float64 the program and the reference agree to rounding."""
    cohort = _cohort()
    prog, v0 = _program_steps(cohort, fmt, 3, seed=11)
    refs = reference.run(cohort, v0, 3, stated=np.float64)
    for p, r in zip(prog, refs):
        assert abs(float(p["fit"]) - r.fit) < 1e-10
        for leaf in ("H", "V", "W"):
            q = getattr(r, leaf)
            gap = np.linalg.norm(p[leaf] - q) / np.linalg.norm(q)
            assert gap < 1e-8, (leaf, gap)
