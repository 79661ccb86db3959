"""Engine-parity suite: host / scan / mesh must walk the same fit trajectory.

The scan engine takes the data as an argument exactly like the host loop's jit,
so its trajectory is bitwise host's; the mesh engine compiles under shard_map
(different fusion), so it gets a small epsilon. Both the jnp route and the
fused Pallas route are covered (the fused kernels in interpret mode on CPU —
tiny cases only).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax.numpy as jnp

from repro.data import choa_like
from repro.sparse import random_parafac2
from repro.core import ENGINES, Parafac2Options, bucketize, fit, init_state
from repro.core import backend as backend_mod
from repro.core import engine as als_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def choa_bt():
    """Small CHOA-geometry dataset (K≈23), f64 for tight parity asserts."""
    data = choa_like(scale=5e-5, seed=0)
    return bucketize(data, max_buckets=2, dtype=jnp.float64)


def _traj(bt, engine, *, backend="jnp", check_every=4, iters=12, tol=0.0,
          rank=3, dtype=jnp.float64):
    opts = Parafac2Options(rank=rank, dtype=dtype, engine=engine,
                           backend=backend, check_every=check_every)
    state, hist = fit(bt, opts, max_iters=iters, tol=tol, seed=0)
    return state, np.asarray(hist)


def test_scan_matches_host_trajectory(choa_bt):
    sh, hh = _traj(choa_bt, "host")
    ss, hs = _traj(choa_bt, "scan", check_every=5)   # chunks 5,5,2
    assert len(hh) == len(hs)
    np.testing.assert_allclose(hs, hh, rtol=0, atol=1e-12)
    np.testing.assert_allclose(np.asarray(ss.V), np.asarray(sh.V), atol=1e-10)
    np.testing.assert_allclose(np.asarray(ss.W), np.asarray(sh.W), atol=1e-10)


def test_while_variant_matches_host_trajectory(choa_bt):
    """check_every=0: the single-dispatch lax.while_loop engine."""
    _, hh = _traj(choa_bt, "host")
    _, hw = _traj(choa_bt, "scan", check_every=0)
    np.testing.assert_allclose(hw, hh, rtol=0, atol=1e-12)


def test_mesh_matches_host_trajectory(choa_bt):
    """shard_map compiles the step differently, so epsilon not bitwise."""
    _, hh = _traj(choa_bt, "host")
    _, hm = _traj(choa_bt, "mesh", check_every=4)
    np.testing.assert_allclose(hm, hh, rtol=0, atol=1e-8)


def test_mesh_bucketed_w_matches_host(choa_bt):
    opts_kw = dict(rank=3, dtype=jnp.float64, w_layout="bucketed")
    sh, hh = fit(choa_bt, Parafac2Options(engine="host", **opts_kw),
                 max_iters=8, tol=0.0, seed=0)
    sm, hm = fit(choa_bt, Parafac2Options(engine="mesh", check_every=4, **opts_kw),
                 max_iters=8, tol=0.0, seed=0)
    np.testing.assert_allclose(np.asarray(hm), np.asarray(hh), atol=1e-8)
    assert isinstance(sm.W, tuple)


@pytest.mark.parametrize("engine", ["scan", "mesh"])
def test_engine_parity_pallas_backend(engine, monkeypatch):
    """Engine parity on the fused Pallas route: the router's platform test
    answers as on a TPU, so every bucket takes the fused kernels (interpret
    mode on CPU — keep it tiny). f32: the kernels accumulate in f32."""
    monkeypatch.setattr(backend_mod, "_on_tpu", lambda: True)
    data, _ = random_parafac2(n_subjects=12, n_cols=24, max_rows=12, rank=3,
                              density=1.0, seed=3)
    bt = bucketize(data, max_buckets=1, dtype=jnp.float32)
    assert {backend_mod.get_backend("auto").route(b, 8)
            for b in bt.buckets} == {"fused"}
    kw = dict(backend="auto", iters=4, rank=8, dtype=jnp.float32)
    _, hh = _traj(bt, "host", **kw)
    _, he = _traj(bt, engine, check_every=2, **kw)
    np.testing.assert_allclose(he, hh, rtol=0, atol=1e-5)


def test_fit_history_nondecreasing_on_choa(choa_bt):
    for engine in ("host", "scan", "mesh"):
        _, hist = _traj(choa_bt, engine, iters=15)
        diffs = np.diff(hist)
        assert (diffs > -1e-9).all(), (engine, diffs.min())


def test_while_variant_stops_like_host(choa_bt):
    """On-device tol stopping must reproduce the host rule exactly: same
    iteration count, same final fit."""
    tol = 3e-4
    _, hh = _traj(choa_bt, "host", iters=50, tol=tol)
    _, hw = _traj(choa_bt, "scan", check_every=0, iters=50, tol=tol)
    assert len(hh) < 50, "tol never hit — test geometry too hard"
    assert len(hw) == len(hh)
    np.testing.assert_allclose(hw, hh, rtol=0, atol=1e-12)


def test_scan_chunked_tol_overshoots_at_most_one_chunk(choa_bt):
    """Chunked convergence stops within check_every-1 iterations of host and
    history stays consistent with the returned state."""
    tol = 3e-4
    state_h, hh = _traj(choa_bt, "host", iters=50, tol=tol)
    state_s, hs = _traj(choa_bt, "scan", check_every=4, iters=50, tol=tol)
    assert len(hh) <= len(hs) < len(hh) + 4
    np.testing.assert_allclose(hs[: len(hh)], hh, rtol=0, atol=1e-12)
    assert hs[-1] == pytest.approx(float(state_s.fit), abs=1e-12)


def test_unknown_engine_raises(choa_bt):
    opts = Parafac2Options(rank=3, engine="warp")
    with pytest.raises(ValueError, match="engine"):
        fit(choa_bt, opts, max_iters=2)
    assert "warp" not in ENGINES


def test_mesh_divisibility_check(choa_bt):
    """_check_divisible rejects bucket subject counts the shard count does
    not divide (the error tells the user to re-bucketize)."""
    opts = Parafac2Options(rank=3, dtype=jnp.float64)
    state = init_state(choa_bt, opts, seed=0)
    kb = choa_bt.buckets[0].kb
    with pytest.raises(ValueError, match="subject_align"):
        als_engine._check_divisible(choa_bt, state, kb + 1)
    als_engine._check_divisible(choa_bt, state, 1)  # 1 shard always fine


@pytest.mark.slow
def test_mesh_engine_multidevice_subprocess():
    """The real thing: 4 host placeholder devices, data sharded 4 ways under
    shard_map, explicit psums — trajectory must match the host engine."""
    src = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np
        import jax, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        from repro.data import choa_like
        from repro.core import Parafac2Options, bucketize, fit

        assert len(jax.devices()) == 4
        data = choa_like(scale=5e-5, seed=0)
        bt = bucketize(data, max_buckets=2, dtype=jnp.float64,
                       subject_align=4)
        kw = dict(rank=3, dtype=jnp.float64)
        _, hh = fit(bt, Parafac2Options(engine="host", **kw),
                    max_iters=8, tol=0.0, seed=0)
        _, hm = fit(bt, Parafac2Options(engine="mesh", check_every=4, **kw),
                    max_iters=8, tol=0.0, seed=0)
        np.testing.assert_allclose(np.asarray(hm), np.asarray(hh), atol=1e-8)
        print("MESH4_OK", hh[-1])
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH4_OK" in proc.stdout
