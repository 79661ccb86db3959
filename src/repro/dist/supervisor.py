"""Fault-tolerant supervisor for the chunked device-resident ALS fit.

``repro.core.engine.fit_device`` dispatches one compiled chunk of
``opts.check_every`` iterations per host sync. For a multi-hour pod-scale
fit that loop is fragile: a preempted host or flaky interconnect kills the
dispatch, a straggling device stretches it, and a numerical blow-up (bad
conditioning, aggressive precision) silently fills the trajectory with NaNs.
:func:`supervised_fit` runs the SAME chunk loop — same chunk lengths, same
tol semantics, bitwise identical history and factors on a faultless run
under the scan engine — with a recovery ladder wrapped around every chunk
boundary:

1. **retry** — the chunk dispatch runs under
   :func:`repro.dist.fault.run_with_retries` (exponential backoff +
   deterministic jitter); a :class:`repro.dist.fault.TransientFault` is
   retried in place up to ``max_retries`` times.
2. **restore** — exhausted retries escalate to elastic checkpoint-restore:
   the newest ``checkpoint/ckpt.py`` checkpoint (written every
   ``ckpt_every`` chunks; globally-unsharded arrays, so a write-on-N
   restores on M devices) is loaded, the fit history rewound to its step,
   and the chunks replayed. Replay is bitwise: the data is a fixed argument
   of the scan chunk, so the carried ``Parafac2State`` is the only state.
3. **rollback** — a numerical-health sentinel checks every chunk's fit
   values on the host sync: non-finite fits, or a fit regression below the
   best seen (ALS fit is monotone), roll the state back to the last good
   chunk boundary and replay. After ``health_retries`` consecutive failed
   replays the retry tightens regularization
   (``Parafac2Options.ridge = ridge_escalation``, growing 10x per further
   escalation) — the classic remedy for an ill-conditioned Gram — and a run
   that still cannot produce finite fits raises.

A :class:`repro.dist.fault.StepWatchdog` observes every successful chunk's
wall time; straggler flags are reported (``SupervisorReport.stragglers``)
but never consume the retry budget — slow is not broken. Fault injection at
chunk boundaries goes through :class:`repro.dist.fault.FaultInjector`
(``--fail-at`` / ``--nan-at`` on ``launch/decompose.py``).

Resume: with ``ckpt_dir`` set, checkpoints carry the fit history in their
``extra`` blob (step = iterations completed); ``resume=True`` picks up the
newest one and continues — restore-then-continue is bitwise the
uninterrupted run (the ``tests/test_ckpt.py`` contract).

See docs/ARCHITECTURE.md (stage 11) for the full decision tree.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.dist.fault import (FaultInjector, StepWatchdog, TransientFault,
                              run_with_retries)

__all__ = ["SupervisorConfig", "SupervisorReport", "supervised_fit"]


@dataclasses.dataclass
class SupervisorConfig:
    """Knobs for :func:`supervised_fit` (all host-side)."""

    # --- retry ladder -----------------------------------------------------
    max_retries: int = 3            # in-place retries per chunk dispatch
    backoff: float = 0.0            # base backoff seconds (0 = no sleep)
    backoff_factor: float = 2.0     # exponential growth per attempt
    jitter: float = 0.0             # deterministic jitter fraction (seeded)
    retry_seed: int = 0             # seed for the jitter stream
    # --- checkpointing ----------------------------------------------------
    ckpt_dir: Optional[str] = None  # None = in-memory snapshots only
    ckpt_every: int = 1             # write a checkpoint every N chunks
    keep: int = 3                   # checkpoints retained on disk
    resume: bool = False            # continue from ckpt_dir's newest step
    # --- sentinels --------------------------------------------------------
    watchdog_factor: float = 3.0    # straggler threshold vs running median
    regress_tol: float = 1e-3       # fit drop below best-seen => unhealthy
    health_retries: int = 1         # clean replays before ridge escalation
    ridge_escalation: float = 1e-6  # first escalated ridge (10x per repeat)
    max_escalations: int = 3        # give up (raise) past this many
    # --- fault injection / test seams ------------------------------------
    injector: Optional[FaultInjector] = None
    sleep: Callable = time.sleep            # injectable for backoff tests
    clock: Callable = time.perf_counter     # injectable for watchdog tests
    # compiled-chunk cache shared ACROSS supervised_fit calls (a {length:
    # callable} dict the caller owns). Lengths already present are treated as
    # warm. This is how repeated fits of one geometry — warm restarts, the
    # benchmark's overhead measurement — skip recompiling the chunk.
    chunk_cache: Optional[Dict[int, Callable]] = None


@dataclasses.dataclass
class SupervisorReport:
    """What happened on the way to convergence — stamped into the
    ``launch/summary.py`` payload by ``launch/decompose.py``."""

    retries: int = 0                # in-place transient-fault retries
    restores: int = 0               # exhausted-retry checkpoint restores
    rollbacks: int = 0              # health-sentinel rollbacks
    stragglers: List[int] = dataclasses.field(default_factory=list)
    checkpoints_written: int = 0
    resumed_from_step: Optional[int] = None
    ridge_final: float = 0.0        # >0 iff regularization was escalated
    escalations: int = 0
    chunks: int = 0                 # successful (committed) chunk dispatches

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _poison(state):
    """NaN the H factor: every downstream update and the fit inherit the
    NaN, which is exactly what the health sentinel must catch."""
    import jax.numpy as jnp
    return state._replace(H=state.H * jnp.asarray(float("nan"), state.H.dtype))


def _healthy(fits: np.ndarray, best: float, regress_tol: float) -> bool:
    if not np.all(np.isfinite(fits)):
        return False
    # ALS fit is monotone: a drop below the best fit seen (beyond tol) means
    # the trajectory diverged even if every value is finite
    return not (np.isfinite(best) and float(fits.min()) < best - regress_tol)


def supervised_fit(
    data,
    opts,
    *,
    max_iters: int = 100,
    tol: float = 1e-6,
    seed: int = 0,
    verbose: bool = False,
    state=None,
    config: Optional[SupervisorConfig] = None,
) -> Tuple[Any, List[float], SupervisorReport]:
    """Fault-tolerant drop-in for ``fit`` on the chunked scan/mesh engines.

    Same ``(state, history)`` contract as :func:`repro.core.parafac2.fit`
    plus a :class:`SupervisorReport`; a faultless supervised run is BITWISE
    the bare ``fit`` under ``engine="scan"`` (identical chunk lengths and tol
    semantics, donation off so a failed dispatch's input carry survives
    retry) and ≤1e-8 under ``engine="mesh"``.
    """
    # lazy: repro.core imports repro.dist.sharding at module scope, so the
    # engine import must not run at repro.dist import time
    from repro.core import engine as _engine
    from repro.core import parafac2 as p2
    from repro.core.spans import span
    from repro import checkpoint as ckpt

    cfg = config or SupervisorConfig()
    if opts.engine not in ("scan", "mesh"):
        raise ValueError(
            f"supervised_fit wraps the chunked device engines "
            f"(engine='scan'|'mesh'), got engine={opts.engine!r}")
    if opts.check_every <= 0:
        raise ValueError(
            "supervised_fit needs chunked execution (check_every > 0); the "
            "while_loop variant has no chunk boundaries to supervise")
    if opts.compress not in ("", "none"):
        raise ValueError(
            f"supervised_fit runs the core ALS only (compress={opts.compress!r})")
    if cfg.ckpt_every < 1:
        raise ValueError(f"ckpt_every must be >= 1, got {cfg.ckpt_every}")

    if state is None:
        state = p2.init_state(data, opts, seed)
    history: List[float] = []
    report = SupervisorReport()

    if cfg.resume:
        if cfg.ckpt_dir is None:
            raise ValueError("resume=True needs ckpt_dir")
        step = ckpt.latest_step(cfg.ckpt_dir)
        if step is not None:
            state, step, extra = ckpt.restore(cfg.ckpt_dir, state, step=step)
            history = [float(f) for f in extra.get("history", [])][:step]
            report.resumed_from_step = step
            if verbose:
                print(f"[supervisor] resumed from step {step} "
                      f"(fit={history[-1] if history else float('nan'):.6f})")

    run_opts = opts
    chunks: Dict[int, Callable] = (
        cfg.chunk_cache if cfg.chunk_cache is not None else {})
    warm_lengths: set = set(chunks)  # chunk lengths whose compile already ran
    watchdog = StepWatchdog(factor=cfg.watchdog_factor)
    injector = cfg.injector

    # last good chunk boundary (in-memory; arrays are immutable, refs suffice)
    good_state, good_history = state, list(history)
    # newest on-disk step, so the restore path knows whether disk can help
    disk_step = (ckpt.latest_step(cfg.ckpt_dir)
                 if cfg.ckpt_dir is not None else None)

    def save(st, hist):
        nonlocal disk_step
        if cfg.ckpt_dir is None:
            return
        ckpt.save(cfg.ckpt_dir, len(hist), st,
                  extra={"history": hist}, keep=cfg.keep)
        disk_step = len(hist)
        report.checkpoints_written += 1

    def best_fit(hist):
        return max(hist) if hist else float("-inf")

    def on_retry(attempt, exc):
        report.retries += 1
        if verbose:
            print(f"[supervisor] retry {attempt + 1}/{cfg.max_retries} "
                  f"after {exc}")

    chunk_idx = len(history) // opts.check_every   # resumes keep chunk ids
    consecutive_bad = 0
    prev = history[-1] if history else -np.inf
    done = False
    while len(history) < max_iters and not done:
        n = min(opts.check_every, max_iters - len(history))
        if n not in chunks:
            # donate=False: a retried dispatch must be able to re-read its
            # input carry (and the benchmark's ≤5% overhead gate holds the
            # cost of forgoing donation accountable)
            chunks[n] = _engine.make_als_chunk(data, run_opts, n, donate=False)

        dispatch_state = state
        if injector is not None and injector.poison(chunk_idx):
            if verbose:
                print(f"[supervisor] injected NaN poison at chunk {chunk_idx}")
            dispatch_state = _poison(dispatch_state)

        timing = {}

        def attempt_chunk(s):
            if injector is not None:
                injector.check(chunk_idx)
            t0 = cfg.clock()
            with span("fit.dispatch"):
                s2, fits = chunks[n](s)
            with span("fit.sync"):
                fits = np.asarray(fits)    # the chunk's one device sync
            timing["dt"] = cfg.clock() - t0
            return s2, fits

        try:
            new_state, fits = run_with_retries(
                attempt_chunk, dispatch_state,
                max_retries=cfg.max_retries, on_retry=on_retry,
                backoff=cfg.backoff, backoff_factor=cfg.backoff_factor,
                jitter=cfg.jitter, seed=cfg.retry_seed, sleep=cfg.sleep)
        except TransientFault as e:
            # retry budget exhausted: elastic checkpoint-restore + rewind.
            # Disk is authoritative when present (the preemption story —
            # write-on-N-resume-on-M); the in-memory boundary covers
            # ckpt_dir=None and the pre-first-checkpoint window.
            report.restores += 1
            if cfg.ckpt_dir is not None and disk_step is not None:
                state, step, extra = ckpt.restore(
                    cfg.ckpt_dir, state, step=disk_step)
                history = [float(f) for f in extra.get("history", [])][:step]
            else:
                state, history = good_state, list(good_history)
            good_state, good_history = state, list(history)
            prev = history[-1] if history else -np.inf
            chunk_idx = len(history) // opts.check_every
            consecutive_bad = 0
            if verbose:
                print(f"[supervisor] retries exhausted ({e}); restored to "
                      f"step {len(history)}, replaying")
            continue

        if not _healthy(fits, best_fit(history), cfg.regress_tol):
            # numerical-health sentinel: roll back to the last good chunk
            # boundary; repeated failures of the SAME replay escalate to a
            # tightened-regularization retry (ridge on every Gram)
            report.rollbacks += 1
            consecutive_bad += 1
            state, history = good_state, list(good_history)
            prev = history[-1] if history else -np.inf
            chunk_idx = len(history) // opts.check_every
            if consecutive_bad > cfg.health_retries:
                report.escalations += 1
                if report.escalations > cfg.max_escalations:
                    raise RuntimeError(
                        f"supervised_fit: fit stayed non-finite/regressing "
                        f"after {report.escalations - 1} regularization "
                        f"escalations (last ridge={run_opts.ridge:g})")
                new_ridge = cfg.ridge_escalation * (
                    10.0 ** (report.escalations - 1))
                run_opts = dataclasses.replace(opts, ridge=new_ridge)
                report.ridge_final = new_ridge
                chunks = {}          # recompile against the ridged step
                warm_lengths = set() # ...whose compile dispatches are slow
                if verbose:
                    print(f"[supervisor] escalating: ridge={new_ridge:g}")
            if verbose:
                print(f"[supervisor] unhealthy chunk {chunk_idx} "
                      f"(finite={bool(np.all(np.isfinite(fits)))}); rolled "
                      f"back to step {len(history)}")
            continue

        # ---- healthy chunk: commit ---------------------------------------
        consecutive_bad = 0
        state = new_state
        if n in warm_lengths:
            # a compile dispatch (any chunk length's first call) is slow by
            # construction, not a straggler — never observed, so it neither
            # flags nor drags the watchdog's median up
            if watchdog.observe(chunk_idx, timing.get("dt", 0.0)):
                report.stragglers.append(chunk_idx)
                if verbose:
                    print(f"[supervisor] straggler flag on chunk {chunk_idx} "
                          f"({timing['dt']:.3f}s)")
        else:
            warm_lengths.add(n)
        for f in fits:
            history.append(float(f))
            if len(history) > 1 and abs(f - prev) < tol:
                done = True                # fit_device's exact semantics:
            prev = f                       # keep the full chunk
        good_state, good_history = state, list(history)
        report.chunks += 1
        chunk_idx += 1
        if report.chunks % cfg.ckpt_every == 0:
            save(state, history)
        if verbose:
            print(f"[supervisor:{opts.engine}] iter {len(history) - 1:3d}  "
                  f"fit={history[-1]:.6f}")

    if cfg.ckpt_dir is not None and disk_step != len(history):
        save(state, history)               # final boundary, resume-exact
    return state, history, report
