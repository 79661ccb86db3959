"""Fault-tolerant supervisor correctness (ISSUE-10).

The contract under test: a fit with injected faults — a transient blip
(in-place retry), a retry-exhausting fault (checkpoint-restore + rewind),
and an injected NaN (health-sentinel rollback) — converges to the SAME
final factors as an unfaulted run: bitwise under the scan engine (the data
is a fixed argument of the chunk, so the carried state is the only state), ≤1e-8 under
mesh. Plus the satellites: StepWatchdog behaviour under the supervisor
(straggler flags never consume the retry budget, compile chunks never
flagged, window bounding on 1000+ chunk histories), the nnz-balanced shard
planner, retry backoff/jitter determinism, resume, and the driver's
``--fail-at``/``--nan-at``/``--ckpt-dir`` surface with the
retry/restore/rollback counts stamped into the ``--json`` summary.

Slow-marked (nightly): the 4-process sharded-SCOO mesh fit vs
single-process (f64, ≤1e-8), kill-and-resume across two processes, and the
100M+-nnz SCOO geometry lowered on a 256-chip pod mesh with per-device
bytes under the 16 GiB budget.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import Parafac2Options, bucketize, fit
from repro.dist.fault import (FaultInjector, StepWatchdog, TransientFault,
                              run_with_retries)
from repro.dist.supervisor import SupervisorConfig, supervised_fit
from repro.sparse import plan_buckets, random_parafac2
from repro.sparse.bucketing import fixed_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RANK = 3
ITERS = 14   # 3 full chunks of 4 + a remainder chunk of 2


def _bt(seed=0, dtype=jnp.float64, subject_align=1):
    data, _ = random_parafac2(n_subjects=10, n_cols=30, max_rows=20,
                              rank=RANK, density=0.6, seed=seed, noise=0.05)
    return bucketize(data, max_buckets=2, dtype=dtype,
                     subject_align=subject_align)


def _opts(**kw):
    kw.setdefault("rank", RANK)
    kw.setdefault("dtype", jnp.float64)
    kw.setdefault("engine", "scan")
    kw.setdefault("check_every", 4)
    return Parafac2Options(**kw)


def _assert_state_equal(a, b, atol=0.0):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if atol:
            np.testing.assert_allclose(x, y, atol=atol, rtol=0)
        else:
            np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module")
def reference():
    """The unfaulted scan run every recovery path must reproduce bitwise."""
    bt = _bt()
    state, hist = fit(bt, _opts(), max_iters=ITERS, tol=0.0, seed=0)
    return bt, state, hist


# ---------------------------------------------------------------------------
# supervised_fit: the recovery ladder, bitwise vs the unfaulted run
# ---------------------------------------------------------------------------

def test_faultless_supervised_bitwise(reference):
    bt, state, hist = reference
    s, h, rep = supervised_fit(bt, _opts(), max_iters=ITERS, tol=0.0, seed=0)
    assert h == hist
    _assert_state_equal(s, state)
    assert (rep.retries, rep.restores, rep.rollbacks) == (0, 0, 0)
    assert rep.chunks == 4


def test_transient_blip_retries_in_place_bitwise(reference):
    bt, state, hist = reference
    cfg = SupervisorConfig(injector=FaultInjector({1: 1}))
    s, h, rep = supervised_fit(bt, _opts(), max_iters=ITERS, tol=0.0, seed=0,
                               config=cfg)
    assert h == hist
    _assert_state_equal(s, state)
    assert rep.retries == 1 and rep.restores == 0 and rep.rollbacks == 0


def test_exhausted_retries_restore_from_ckpt_bitwise(reference, tmp_path):
    bt, state, hist = reference
    # times = max_retries + 1 exhausts exactly one run_with_retries pass,
    # then the fault clears: one checkpoint-restore, replay is clean
    cfg = SupervisorConfig(injector=FaultInjector({2: 3}), max_retries=2,
                           ckpt_dir=str(tmp_path))
    s, h, rep = supervised_fit(bt, _opts(), max_iters=ITERS, tol=0.0, seed=0,
                               config=cfg)
    assert h == hist
    _assert_state_equal(s, state)
    assert rep.restores == 1 and rep.retries == 2
    assert rep.checkpoints_written >= 4


def test_exhausted_retries_without_ckpt_dir_uses_memory_boundary(reference):
    bt, state, hist = reference
    cfg = SupervisorConfig(injector=FaultInjector({1: 3}), max_retries=2)
    s, h, rep = supervised_fit(bt, _opts(), max_iters=ITERS, tol=0.0, seed=0,
                               config=cfg)
    assert h == hist
    _assert_state_equal(s, state)
    assert rep.restores == 1


def test_nan_poison_rolls_back_bitwise(reference):
    bt, state, hist = reference
    cfg = SupervisorConfig(injector=FaultInjector(nan_steps=[1]))
    s, h, rep = supervised_fit(bt, _opts(), max_iters=ITERS, tol=0.0, seed=0,
                               config=cfg)
    assert h == hist and np.isfinite(h).all()
    _assert_state_equal(s, state)
    assert rep.rollbacks == 1 and rep.ridge_final == 0.0


def test_persistent_nan_escalates_ridge_and_recovers(reference):
    bt, state, hist = reference
    # the poison survives the first clean replay (times=2), so the sentinel
    # escalates to the tightened-regularization retry; the ridged trajectory
    # is finite and lands within soft tolerance of the unfaulted fit
    cfg = SupervisorConfig(injector=FaultInjector(nan_steps={1: 2}),
                           health_retries=1)
    s, h, rep = supervised_fit(bt, _opts(), max_iters=ITERS, tol=0.0, seed=0,
                               config=cfg)
    assert rep.rollbacks == 2 and rep.escalations == 1
    assert rep.ridge_final > 0.0
    assert np.isfinite(h).all()
    assert abs(h[-1] - hist[-1]) < 1e-6


def test_unrecoverable_divergence_raises():
    bt = _bt()
    cfg = SupervisorConfig(injector=FaultInjector(nan_steps={0: 99}),
                           health_retries=0, max_escalations=2)
    with pytest.raises(RuntimeError, match="escalation"):
        supervised_fit(bt, _opts(), max_iters=ITERS, tol=0.0, seed=0,
                       config=cfg)


def test_supervisor_rejects_host_and_while_engines():
    bt = _bt()
    with pytest.raises(ValueError, match="scan"):
        supervised_fit(bt, _opts(engine="host"), max_iters=4)
    with pytest.raises(ValueError, match="chunk"):
        supervised_fit(bt, _opts(check_every=0), max_iters=4)


def test_mesh_engine_faulted_matches_unfaulted(reference):
    """The acceptance bound under mesh: faulted vs unfaulted ≤1e-8 (here on
    the default single-device mesh; the 4-process variant is slow-marked)."""
    bt = _bt(subject_align=len(jax.devices()))
    opts = _opts(engine="mesh")
    s0, h0 = fit(bt, opts, max_iters=ITERS, tol=0.0, seed=0)
    cfg = SupervisorConfig(
        injector=FaultInjector({1: 1, 2: 4}, nan_steps=[3]), max_retries=3)
    s1, h1, rep = supervised_fit(bt, opts, max_iters=ITERS, tol=0.0, seed=0,
                                 config=cfg)
    assert rep.retries >= 1 and rep.restores == 1 and rep.rollbacks == 1
    np.testing.assert_allclose(h0, h1, atol=1e-8, rtol=0)
    _assert_state_equal(s0, s1, atol=1e-8)


# ---------------------------------------------------------------------------
# resume (write-on-N, resume-on-M semantics live in test_ckpt/test_sharding)
# ---------------------------------------------------------------------------

def test_resume_continues_bitwise(reference, tmp_path):
    bt, state, hist = reference
    opts = _opts()
    cfg = SupervisorConfig(ckpt_dir=str(tmp_path))
    supervised_fit(bt, opts, max_iters=8, tol=0.0, seed=0, config=cfg)
    cfg2 = SupervisorConfig(ckpt_dir=str(tmp_path), resume=True)
    s, h, rep = supervised_fit(bt, opts, max_iters=ITERS, tol=0.0, seed=0,
                               config=cfg2)
    assert rep.resumed_from_step == 8
    assert h == hist
    _assert_state_equal(s, state)


def test_resume_without_ckpt_dir_raises():
    bt = _bt()
    with pytest.raises(ValueError, match="ckpt_dir"):
        supervised_fit(bt, _opts(), max_iters=4,
                       config=SupervisorConfig(resume=True))


# ---------------------------------------------------------------------------
# retry backoff/jitter (satellite: dist/fault.py)
# ---------------------------------------------------------------------------

def test_run_with_retries_backoff_jitter_deterministic():
    calls = {"n": 0}
    sleeps = []

    def flaky(tag, *, bump=1):
        calls["n"] += bump
        if calls["n"] < 4:
            raise TransientFault(tag)
        return tag

    out = run_with_retries(flaky, "ok", bump=1, max_retries=3,
                           backoff=0.5, backoff_factor=2.0, jitter=0.1,
                           seed=7, sleep=sleeps.append)
    assert out == "ok" and len(sleeps) == 3      # kwargs passed through
    # exponential base schedule, jitter multiplies by [1, 1.1)
    for i, s in enumerate(sleeps):
        base = 0.5 * 2.0 ** i
        assert base <= s < base * 1.1
    # same seed -> identical schedule (deterministic, private RNG stream)
    calls["n"] = 0
    sleeps2 = []
    run_with_retries(flaky, "ok", max_retries=3, backoff=0.5, jitter=0.1,
                     seed=7, sleep=sleeps2.append)
    assert sleeps == sleeps2


def test_run_with_retries_bare_call_still_works():
    def boom():
        raise TransientFault("always")

    with pytest.raises(TransientFault):
        run_with_retries(boom, max_retries=1)
    assert run_with_retries(lambda x: x + 1, 41) == 42


def test_fault_injector_per_step_times_and_poison():
    inj = FaultInjector({1: 2, 3: 1}, nan_steps={2: 2})
    for _ in range(2):
        with pytest.raises(TransientFault):
            inj.check(1)
    inj.check(1)                       # cleared after 2 firings
    assert inj.poison(2) and inj.poison(2) and not inj.poison(2)
    assert not inj.poison(1)           # only listed steps poison


# ---------------------------------------------------------------------------
# StepWatchdog under the supervisor (satellite)
# ---------------------------------------------------------------------------

def _clock_from(durations):
    """A fake SupervisorConfig.clock: dispatch i reads (t0=0, t1=dt_i)."""
    ticks = iter(t for dt in durations for t in (0.0, dt))
    return lambda: next(ticks)


def test_straggler_flagged_but_no_retry_budget_spent(reference):
    bt, state, hist = reference
    # 4 chunks; chunk 0 is the compile dispatch (never observed), chunk 3
    # compiles the remainder length (never observed) -> observed: 1, 2. Add
    # iterations so a slow chunk lands after min_history warm-up.
    opts = _opts(check_every=2)
    s0, h0 = fit(bt, opts, max_iters=12, tol=0.0, seed=0)
    durations = [9.9, 1.0, 1.0, 1.0, 1.0, 50.0]   # chunk 5 straggles
    cfg = SupervisorConfig(clock=_clock_from(durations))
    s, h, rep = supervised_fit(bt, opts, max_iters=12, tol=0.0, seed=0,
                               config=cfg)
    assert rep.stragglers == [5]
    assert rep.retries == 0 and rep.restores == 0 and rep.rollbacks == 0
    assert h == h0                       # a slow chunk is still committed
    _assert_state_equal(s, s0)


def test_cold_start_compile_chunk_never_flagged(reference):
    bt, _, _ = reference
    # chunk 0 (compile) is excluded by construction; the first OBSERVED
    # chunks are under min_history and never flag either, however slow
    durations = [999.0, 500.0, 1.0, 1.0]
    cfg = SupervisorConfig(clock=_clock_from(durations))
    _, _, rep = supervised_fit(bt, _opts(), max_iters=ITERS, tol=0.0, seed=0,
                               config=cfg)
    assert rep.stragglers == []


def test_watchdog_window_bounds_1000_plus_chunk_histories():
    wd = StepWatchdog(factor=3.0, min_history=3, window=50)
    for step in range(1200):
        assert not wd.observe(step, 1.0)
    assert len(wd._times) <= wd.window       # bounded, not 1200
    assert wd.observe(1200, 10.0)            # flagged vs the median
    assert wd.flagged == [1200]
    assert len(wd._times) <= wd.window       # flagged dt excluded from history


# ---------------------------------------------------------------------------
# nnz-balanced shard planner (tentpole layer 1, sparse/bucketing.py)
# ---------------------------------------------------------------------------

def test_balance_for_shards_equalizes_nnz():
    rng = np.random.default_rng(0)
    n, shards = 64, 4
    nnz = rng.integers(1, 1000, size=n)
    plan = fixed_plan(n, i_pad=8, c_pad=128)
    before = plan.shard_imbalance(nnz, shards)
    bal = plan.balance_for_shards(nnz, shards)
    after = bal.shard_imbalance(nnz, shards)
    # same members, same shapes — only the order moved
    assert sorted(np.concatenate(bal.members).tolist()) == list(range(n))
    assert bal.shapes == plan.shapes and bal.nnz_pads == plan.nnz_pads
    assert after <= before
    assert after < 1.05                      # LPT gets near-perfect here
    # per-shard loads match the imbalance accounting
    loads = bal.shard_nnz(nnz, shards)[0]
    assert sum(loads) == int(nnz.sum())


def test_balance_respects_tail_padding_capacities():
    # 10 members over 4 shards -> padded Kb 12, capacities [3, 3, 3, 1]:
    # the LAST shard holds the padding, so it must get the fewest subjects
    nnz = np.arange(1, 11) * 10
    plan = fixed_plan(10, i_pad=8, c_pad=128)
    bal = plan.balance_for_shards(nnz, 4)
    mem = bal.members[0]
    cs = -(-len(mem) // 4)
    sizes = [len(mem[s * cs:(s + 1) * cs]) for s in range(4)]
    assert sizes == [3, 3, 3, 1]
    assert bal.shard_imbalance(nnz, 4) <= plan.shard_imbalance(nnz, 4)


def test_balance_single_shard_is_identity_and_validates():
    plan = fixed_plan(6, i_pad=8, c_pad=128)
    assert plan.balance_for_shards([1] * 6, 1) is plan
    with pytest.raises(ValueError, match="n_shards"):
        plan.balance_for_shards([1] * 6, 0)


def test_balanced_plan_fit_matches_unbalanced(reference):
    """Reordering members changes slot assignment, not the model: the fits
    agree to reassociation-level fp noise (f64)."""
    bt, _, hist = reference
    data, _ = random_parafac2(n_subjects=10, n_cols=30, max_rows=20,
                              rank=RANK, density=0.6, seed=0, noise=0.05)
    plan = plan_buckets(data.row_counts(), data.col_counts(), max_buckets=2,
                        nnz_counts=data.nnz_counts())
    bal = plan.balance_for_shards(data.nnz_counts(), 2)
    bt2 = bucketize(data, dtype=jnp.float64, plan=bal, subject_align=2)
    _, h2 = fit(bt2, _opts(), max_iters=ITERS, tol=0.0, seed=0)
    np.testing.assert_allclose(h2, hist, atol=1e-10, rtol=0)


# ---------------------------------------------------------------------------
# driver surface: --fail-at / --nan-at / --ckpt-dir / --resume + --json
# ---------------------------------------------------------------------------

def test_decompose_faulted_run_bitwise_with_counts_in_json(tmp_path):
    """The acceptance command: choa rank-5/20-iter with a transient blip, a
    retry-exhausting fault (restore), and an injected NaN (rollback) —
    fit_history bitwise vs the unfaulted run, counts in the --json blob."""
    from repro.launch.decompose import main

    base = ["--dataset", "choa", "--scale", "0.001", "--rank", "5",
            "--iters", "20", "--engine", "scan", "--check-every", "5",
            "--tol", "0"]
    clean = main(base + ["--json", str(tmp_path / "clean.json")])
    faulted = main(base + [
        "--fail-at", "1,2:4", "--nan-at", "3", "--max-retries", "3",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--json", str(tmp_path / "faulted.json")])
    assert faulted["fit_history"] == clean["fit_history"]
    blob = json.loads((tmp_path / "faulted.json").read_text())
    sup = blob["supervisor"]
    assert sup["retries"] >= 1           # the blip at chunk 1
    assert sup["restores"] == 1          # chunk 2 exhausted its retries
    assert sup["rollbacks"] == 1         # the NaN at chunk 3
    assert sup["checkpoints_written"] >= 1
    assert blob["supervisor"]["ridge_final"] == 0.0
    assert json.loads((tmp_path / "clean.json").read_text())["supervisor"] is None


def test_decompose_ckpt_resume_bitwise(tmp_path):
    from repro.launch.decompose import main

    base = ["--dataset", "choa", "--scale", "0.001", "--rank", "5",
            "--engine", "scan", "--check-every", "5", "--tol", "0",
            "--ckpt-dir", str(tmp_path / "ckpt")]
    full = main(base + ["--iters", "20", "--json", str(tmp_path / "a.json")])
    # fresh dir: run 10, then resume to 20 — history must match the one-shot
    base2 = [a if a != str(tmp_path / "ckpt") else str(tmp_path / "ckpt2")
             for a in base]
    main(base2 + ["--iters", "10"])
    resumed = main(base2 + ["--iters", "20", "--resume",
                            "--json", str(tmp_path / "b.json")])
    assert resumed["supervisor"]["resumed_from_step"] == 10
    assert resumed["fit_history"] == full["fit_history"]


def test_decompose_fault_flags_reject_host_engine():
    from repro.launch.decompose import main

    with pytest.raises(SystemExit):
        main(["--dataset", "choa", "--scale", "0.001", "--engine", "host",
              "--fail-at", "1"])


def test_parse_fail_spec():
    from repro.launch.decompose import parse_fail_spec

    assert parse_fail_spec("") == {}
    assert parse_fail_spec("1,3:5") == {1: 1, 3: 5}
    with pytest.raises(ValueError, match="fault spec"):
        parse_fail_spec("x:y")


# ---------------------------------------------------------------------------
# slow suite: multi-process sharded SCOO, kill-and-resume, pod dryrun cell
# ---------------------------------------------------------------------------

def _run_sub(src, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(src)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


@pytest.mark.slow
def test_sharded_scoo_mesh_4proc_matches_single():
    """Tentpole layer 1: the SCOO buckets under shard_map on 4 forced host
    devices, members nnz-BALANCED across the shards; the mesh fit matches
    the single-device host fit ≤1e-8 in f64."""
    proc = _run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np, jax.numpy as jnp, dataclasses
        from repro.core import Parafac2Options, bucketize, fit
        from repro.sparse import plan_buckets, random_irregular

        data = random_irregular(n_subjects=48, n_cols=256, max_rows=40,
                                avg_nnz_per_subject=60, seed=0)
        nnz = data.nnz_counts()
        plan = plan_buckets(data.row_counts(), data.col_counts(),
                            max_buckets=2, nnz_counts=nnz, sort_by="nnz")
        bal = plan.balance_for_shards(nnz, 4)
        assert (bal.shard_imbalance(nnz, 4)
                <= plan.shard_imbalance(nnz, 4) + 1e-12)
        bt = bucketize(data, dtype=jnp.float64, plan=bal, subject_align=4,
                       formats=["scoo"] * bal.n_buckets)
        for b in bt.buckets:
            assert type(b).__name__ == "SparseBucket" and b.kb % 4 == 0

        opts = Parafac2Options(rank=3, dtype=jnp.float64, backend="auto",
                               engine="mesh", check_every=4)
        sm, hm = fit(bt, opts, max_iters=10, tol=0.0, seed=0)
        sh, hh = fit(bt, dataclasses.replace(opts, engine="host"),
                     max_iters=10, tol=0.0, seed=0)
        np.testing.assert_allclose(hm, hh, atol=1e-8, rtol=0)
        for a, b in zip(jax.tree_util.tree_leaves(sm),
                        jax.tree_util.tree_leaves(sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-8, rtol=0)
        print("SCOO4_OK", hm[-1])
    """)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SCOO4_OK" in proc.stdout


@pytest.mark.slow
def test_kill_and_resume_across_processes(tmp_path):
    """The preemption story end-to-end: process A fits 8 iterations under
    the supervisor and dies; process B resumes from A's checkpoints and
    must land bitwise on the uninterrupted 16-iteration trajectory."""
    common = """
        import numpy as np, jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.core import Parafac2Options, bucketize, fit
        from repro.dist.supervisor import SupervisorConfig, supervised_fit
        from repro.sparse import random_parafac2

        data, _ = random_parafac2(n_subjects=10, n_cols=30, max_rows=20,
                                  rank=3, density=0.6, seed=0, noise=0.05)
        bt = bucketize(data, max_buckets=2, dtype=jnp.float64)
        opts = Parafac2Options(rank=3, dtype=jnp.float64, engine="scan",
                               check_every=4)
    """
    a = _run_sub(common + f"""
        cfg = SupervisorConfig(ckpt_dir={str(tmp_path)!r})
        supervised_fit(bt, opts, max_iters=8, tol=0.0, seed=0, config=cfg)
        print("PHASE1_OK")
    """, timeout=300)
    assert a.returncode == 0, a.stderr[-2000:]
    assert "PHASE1_OK" in a.stdout
    b = _run_sub(common + f"""
        cfg = SupervisorConfig(ckpt_dir={str(tmp_path)!r}, resume=True)
        s, h, rep = supervised_fit(bt, opts, max_iters=16, tol=0.0, seed=0,
                                   config=cfg)
        assert rep.resumed_from_step == 8, rep
        s0, h0 = fit(bt, opts, max_iters=16, tol=0.0, seed=0)
        assert h == h0
        for x, y in zip(jax.tree_util.tree_leaves(s),
                        jax.tree_util.tree_leaves(s0)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("RESUME_OK")
    """, timeout=300)
    assert b.returncode == 0, b.stderr[-2000:]
    assert "RESUME_OK" in b.stdout


@pytest.mark.slow
def test_dryrun_pod_cell_100m_nnz_under_budget():
    """Tentpole layer 1's scale proof: the synth-500M SCOO geometry (592M
    padded triplets — well past 100M nnz) lowered through the mesh engine on
    the 256-chip pod mesh, per-device bytes under the 16 GiB HBM budget."""
    proc = _run_sub("""
        from repro.launch import dryrun as dr

        mesh = dr.make_production_mesh(multi_pod=False)
        rec = dr.run_parafac2_cell("parafac2-synth500m-r40", mesh, "pod16x16",
                                   engine="mesh", format="scoo",
                                   check_every=2)
        assert rec["n_chips"] == 256, rec["n_chips"]
        assert rec["padded_nnz"] >= 100_000_000, rec["padded_nnz"]
        assert rec["fits_hbm_16g"], rec["bytes_per_device"]
        print("POD_SCOO_OK", rec["padded_nnz"], rec["bytes_per_device"])
    """)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "POD_SCOO_OK" in proc.stdout
