"""A run with the timed path broken underneath comes out not correct.

These drive ``chipbench.cell.run`` past the harness's look for a chip, on
the CPU, at a small share of each cell's cohort, with each fault a cell can
have planted in the compiled chunk the window drives. (The exchange between
chips is not among them: every cell runs on one chip.)
"""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell as cell_mod

CELLS = [w["name"] for w in cell_mod.load_benchmark()["workloads"]]
SMALL = {"choa-r40": 1500, "movielens-r40": 150}


def _small(name):
    c = cell_mod.load_cell(name)
    return dataclasses.replace(
        c, cfg=dict(c.cfg, n_subjects=SMALL[c.entry["config"]]))


def frozen(prog):
    """A step that returns its state unchanged."""
    return lambda s: (s, jnp.reshape(s.fit, (1,)))


def half(prog):
    """Every other subject left out of the step; the fit over the rest."""
    from repro.core.engine import make_als_chunk

    buckets = []
    for b in prog.bt.buckets:
        keep = (jnp.arange(b.kb) % 2 == 0).astype(b.subject_mask.dtype)
        m = keep * b.subject_mask
        shape = (b.kb,) + (1,) * (b.vals.ndim - 1)
        buckets.append(dataclasses.replace(
            b, vals=b.vals * m.reshape(shape).astype(b.vals.dtype),
            subject_mask=m))
    bt = dataclasses.replace(prog.bt, buckets=buckets)
    return make_als_chunk(bt, prog.opts, prog.opts.check_every)


def altered(prog):
    """An answer altered where it is produced: H[0, 0] of each step, by 1%."""
    def chunk(s):
        s2, fits = prog.chunk(s)
        return s2._replace(H=s2.H.at[0, 0].multiply(1.01)), fits
    return chunk


def _run(name, fault, seed=2**32 + 9):
    return cell_mod.run(_small(name), seed, 0.2, False, time.perf_counter(),
                        fault=fault, log=lambda *_: None)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [frozen, half, altered],
                         ids=["frozen", "half", "altered"])
def test_fault_is_not_correct(name, fault):
    r = _run(name, fault)
    assert r["correct"] is False
    failing = [k for k, c in r["checks"].items()
               if not (np.isfinite(c["value"]) and c["value"] <= c["limit"])]
    assert failing


@pytest.mark.parametrize("name", CELLS)
def test_result_line_shape(name):
    r = _run(name, None)
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device",
                      "checks"}
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"iter_s", "hbm_peak_gib", "setup_s"}
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["device"]["count"] == 1
