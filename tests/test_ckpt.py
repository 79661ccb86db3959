"""Checkpoint/resume correctness for the serving & training paths.

ISSUE-6 satellite: `checkpoint/ckpt.py` grew users (the streaming service
warm state, scan-engine resumes) whose correctness depends on properties the
basic round-trip tests in test_runtime.py never pinned down:

  * a full ``Parafac2State`` — including the PR-4 ``aux`` ADMM dual pytree
    (nested dict of tuples of arrays) — survives save/restore leaf-exact;
  * elastic reshard: a checkpoint written sharded over N devices restores
    onto an M-device submesh (the "write on 512, resume on 64" path, scaled
    to forced host devices in a subprocess — slow-marked);
  * restore-then-continue under the scan engine is BITWISE identical to the
    uninterrupted run (the data is a fixed argument of the scan chunk, so the only
    state is the carried ``Parafac2State`` — if the checkpoint preserves it exactly, the
    trajectory must re-converge exactly).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt
from repro.core import Parafac2Options, bucketize, fit, init_state
from repro.sparse import random_parafac2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RANK = 3


def _bt(seed=0, dtype=jnp.float64):
    data, _ = random_parafac2(n_subjects=10, n_cols=30, max_rows=20,
                              rank=RANK, density=0.6, seed=seed, noise=0.05)
    return bucketize(data, max_buckets=2, dtype=dtype)


def _admm_opts(**kw):
    """Options whose W constraint routes through ADMM, so ``state.aux``
    carries a real (Z, U) dual pytree (the PR-4 structure)."""
    kw.setdefault("rank", RANK)
    kw.setdefault("dtype", jnp.float64)
    kw.setdefault("constraints", {"v": "nonneg", "w": "nonneg+l1:0.01"})
    return Parafac2Options(**kw)


def _assert_state_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert jnp.asarray(x).dtype == jnp.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_parafac2_state_roundtrip_with_admm_aux(tmp_path):
    bt = _bt()
    opts = _admm_opts()
    state, _ = fit(bt, opts, max_iters=5, tol=0.0, seed=0)
    # the aux pytree must actually contain ADMM duals, otherwise this test
    # is vacuous
    aux_leaves = jax.tree_util.tree_leaves(state.aux)
    assert len(aux_leaves) >= 2, "expected (Z, U) duals in state.aux"

    ckpt.save(str(tmp_path), 5, state, extra={"fit": float(state.fit)})
    template = init_state(bt, opts, seed=0)  # same structure, fresh values
    restored, step, extra = ckpt.restore(str(tmp_path), template)
    assert step == 5
    assert extra["fit"] == float(state.fit)
    _assert_state_equal(restored, state)


def test_restore_then_continue_bitwise_scan(tmp_path):
    """Interrupt/resume under the scan engine reproduces the uninterrupted
    trajectory BITWISE: same chunk boundaries, state round-tripped exactly
    through disk, the same data passed to the compiled chunk."""
    bt = _bt(seed=1)
    opts = _admm_opts(engine="scan", check_every=4)

    # uninterrupted: 16 iterations in 4-iteration scan chunks
    full, _ = fit(bt, opts, max_iters=16, tol=0.0, seed=0)

    # interrupted at the 8-iteration chunk boundary + resumed from disk
    half, _ = fit(bt, opts, max_iters=8, tol=0.0, seed=0)
    ckpt.save(str(tmp_path), 8, half)
    template = init_state(bt, opts, seed=0)
    restored, _, _ = ckpt.restore(str(tmp_path), template)
    _assert_state_equal(restored, half)
    resumed, _ = fit(bt, opts, max_iters=8, tol=0.0, seed=0, state=restored)

    _assert_state_equal(resumed, full)


def test_restore_casts_to_template_dtype(tmp_path):
    t = {"a": jnp.arange(6, dtype=jnp.float64).reshape(2, 3)}
    ckpt.save(str(tmp_path), 1, t)
    restored, _, _ = ckpt.restore(
        str(tmp_path), {"a": jnp.zeros((2, 3), jnp.float32)})
    assert restored["a"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.arange(6, dtype=np.float32).reshape(2, 3))


def test_restore_missing_leaf_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": jnp.zeros(3)})
    with pytest.raises(KeyError, match="b"):
        ckpt.restore(str(tmp_path), {"a": jnp.zeros(3), "b": jnp.zeros(3)})


@pytest.mark.slow
def test_elastic_reshard_write_8_restore_4_subprocess():
    """The 'write on 512 chips, resume on 64' path, scaled down: save a
    state sharded over an 8-device mesh, restore it onto a 4-device submesh
    via the ``shardings=`` argument — values identical, new placement."""
    src = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import tempfile
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        jax.config.update("jax_enable_x64", True)
        from repro import checkpoint as ckpt

        assert len(jax.devices()) == 8
        mesh8 = Mesh(np.asarray(jax.devices()), ("s",))
        sh8 = NamedSharding(mesh8, P("s"))
        tree = {"W": jax.device_put(
                    jnp.arange(16 * 3, dtype=jnp.float64).reshape(16, 3),
                    sh8),
                "H": jnp.eye(3, dtype=jnp.float64)}
        assert len(tree["W"].sharding.device_set) == 8

        d = tempfile.mkdtemp()
        ckpt.save(d, 512, tree)

        mesh4 = Mesh(np.asarray(jax.devices()[:4]), ("s",))
        sh4 = NamedSharding(mesh4, P("s"))
        template = {"W": jnp.zeros((16, 3), jnp.float64),
                    "H": jnp.zeros((3, 3), jnp.float64)}
        shards = {"W": sh4, "H": NamedSharding(mesh4, P())}
        restored, step, _ = ckpt.restore(d, template, shardings=shards)
        assert step == 512
        assert len(restored["W"].sharding.device_set) == 4
        np.testing.assert_array_equal(np.asarray(restored["W"]),
                                      np.asarray(tree["W"]))
        np.testing.assert_array_equal(np.asarray(restored["H"]), np.eye(3))
        print("RESHARD_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RESHARD_OK" in proc.stdout


def test_stream_service_state_roundtrip(tmp_path):
    """The streaming service's warm-state checkpoint (launch/stream.py)
    restores leaf-exact, including the residual ledger and the sticky batch
    geometry recorded in ``extra``."""
    from repro.launch.stream import StreamService, synthetic_stream

    data, _ = random_parafac2(n_subjects=10, n_cols=30, max_rows=20,
                              rank=RANK, density=0.6, seed=2, noise=0.05)
    opts = Parafac2Options(rank=RANK, dtype=jnp.float64)
    warm, payloads = synthetic_stream(data, warm_frac=0.6, seed=2)
    svc, _ = StreamService.warm_start(warm, opts, iters=5, seed=0,
                                      batch_slots=2, drift_threshold=np.inf)
    for p in payloads:
        svc.submit(p)
    svc.flush()
    svc.save(str(tmp_path))

    svc2 = StreamService.from_checkpoint(str(tmp_path), svc.union_data(),
                                         opts, batch_slots=2,
                                         drift_threshold=np.inf)
    np.testing.assert_array_equal(svc2.W, svc.W)
    np.testing.assert_array_equal(np.asarray(svc2.H), np.asarray(svc.H))
    np.testing.assert_array_equal(np.asarray(svc2.V), np.asarray(svc.V))
    np.testing.assert_array_equal(svc2._sub_resid, svc._sub_resid)
    np.testing.assert_array_equal(svc2._sub_norm, svc._sub_norm)
    assert svc2.baseline_fit == svc.baseline_fit
    assert svc2.n_appends == svc.n_appends
    assert (svc2._i_pad, svc2._c_pad, svc2._n_pad) == (
        svc._i_pad, svc._c_pad, svc._n_pad)
    # subject-count mismatch between checkpoint and dataset fails fast
    with pytest.raises(ValueError, match="subjects"):
        StreamService.from_checkpoint(
            str(tmp_path),
            type(data)(subjects=list(data.subjects[:-1]),
                       n_cols=data.n_cols),
            opts)
