"""PARAFAC2 decomposition driver — the paper's workload as a first-class job.

  PYTHONPATH=src python -m repro.launch.decompose --dataset choa --scale 0.002 \
      --rank 5 --iters 20 --engine scan --format auto --json out.json \
      --constraint v=nonneg+l1:0.1,w=smooth:0.1

``--engine`` picks the ALS execution engine (host | scan | mesh — see
repro.core.engine); ``--format`` the device data format (cc | scoo | auto —
repro.core.irregular; "auto" routes each bucket CC-vs-SCOO by measured
density, the O(nnz) sparse path for EHR-like sparsity); ``--constraint`` the
per-mode factor constraints (COPA-style AO-ADMM layer — see
repro.core.constraints; a bare spec such as ``--constraint nonneg_admm``
applies to both V and W); ``--compress`` the preprocessing stage
(repro.core.compress; ``rsvd[:r[:p[:q]]]`` runs the whole ALS on randomized
small cores and expands exactly at the end — the DPar2-style decoupling of
iteration count from data size); ``--json`` writes the machine-readable run
summary CI and the benchmarks consume (the unified ``schema_version`` +
``resolved_options`` layout of repro.launch.summary), including the resolved
constraint/compress blocks, the per-bucket format/density decisions and the
``spans`` block of host-span totals (repro.core.spans).

Fault tolerance (repro.dist.supervisor; scan/mesh engines): ``--ckpt-dir``
checkpoints every ``--ckpt-every`` chunks and ``--resume`` continues from
the newest one (restore-then-continue is bitwise under scan).
``--fail-at "1,3:5"`` injects transient faults at chunk boundaries (an
optional ``:times`` > ``--max-retries`` exhausts the in-place retries and
forces the checkpoint-restore path); ``--nan-at`` poisons a chunk's state
with NaNs so the numerical-health sentinel rolls back. A faulted run
re-converges to the SAME factors as an unfaulted one, and the
retry/restore/rollback counts land in the ``--json`` summary's
``supervisor`` block. ``--supervise`` engages the supervisor without any
faults (e.g. for checkpoint cadence alone). Under ``--engine mesh`` the
bucket plan is additionally nnz-BALANCED across the subject shards
(BucketPlan.balance_for_shards — equal nonzeros per shard, not equal
subjects), with the per-shard nnz and the residual imbalance reported.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ENGINES, FORMATS, Parafac2Options, bucketize, fit
from repro.core.backend import get_backend
from repro.core.compress import available as available_preprocess
from repro.core.constraints import (
    available as available_constraints, constraint_summary, parse_constraint_arg)
from repro.core.interpret import subject_top_phenotypes, top_phenotype_features
from repro.core.spans import reset_spans, span, span_totals
from repro.data import choa_like, movielens_like
from repro.dist.fault import FaultInjector
from repro.dist.supervisor import SupervisorConfig, supervised_fit
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.summary import resolved_options, run_summary
from repro.sparse import plan_layout, random_irregular, route_formats


def parse_fail_spec(spec: str) -> dict:
    """``"1,3:5"`` -> ``{1: 1, 3: 5}``: comma-separated chunk indices, each
    with an optional ``:times`` count (how many attempts fault before the
    injected failure clears — times > --max-retries forces a restore)."""
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            if ":" in part:
                step, times = part.split(":", 1)
                out[int(step)] = int(times)
            else:
                out[int(part)] = 1
        except ValueError:
            raise ValueError(
                f"bad fault spec {part!r} (want CHUNK or CHUNK:TIMES, "
                f"e.g. '1,3:5')") from None
    return out


def load_dataset(name: str, scale: float, seed: int):
    if name == "choa":
        return choa_like(scale=scale, seed=seed)
    if name == "movielens":
        return movielens_like(scale=scale, seed=seed)
    if name == "synthetic":
        return random_irregular(
            n_subjects=max(16, int(10_000 * scale)), n_cols=5_000,
            max_rows=100, avg_nnz_per_subject=500, seed=seed)
    raise ValueError(name)


@span("layout")
def build_buckets(data, *, format: str, engine: str, max_buckets: int):
    """The decompose CLI's device layout for ``data``: the bucket plan
    (nnz-balanced over the subject shards under the mesh engine), the
    per-bucket format routing, and ``bucketize``. Returns ``(Bucketed,
    per-bucket stats, shard-balance report or None)``. Runs under the host
    span ``layout``, with ``layout.plan`` and ``layout.route`` here and
    ``layout.pack`` and ``layout.to_device`` in ``bucketize``."""
    # shard_map needs every bucket's subject count to divide the shard count
    subject_align = len(jax.devices()) if engine == "mesh" else 1
    with span("layout.plan"):
        rc, ccnt, nnzc = (data.row_counts(), data.col_counts(),
                          data.nnz_counts())
        plan, _ = plan_layout(rc, ccnt, nnzc, format=format,
                              max_buckets=max_buckets)
        shard_balance = None
        if engine == "mesh" and subject_align > 1:
            # nnz-balance the subject shards: equal nonzeros per contiguous
            # shard chunk, not equal subject counts — the planner sorts
            # members by size, which would put every heavy subject on
            # the last shard (the straggler the watchdog would then flag
            # forever)
            naive = plan.shard_imbalance(nnzc, subject_align)
            plan = plan.balance_for_shards(nnzc, subject_align)
            shard_balance = {
                "n_shards": subject_align,
                "shard_nnz": plan.shard_nnz(nnzc, subject_align),
                "imbalance_max_over_mean": plan.shard_imbalance(
                    nnzc, subject_align),
                "imbalance_unbalanced": naive,
            }
            print(f"[shard-balance] {subject_align} shards: imbalance "
                  f"{naive:.3f} -> "
                  f"{shard_balance['imbalance_max_over_mean']:.3f} "
                  f"(max/mean nnz)")
    with span("layout.route"):
        fmts = route_formats(plan, nnzc, format=format)
    bt = bucketize(data, dtype=jnp.float32, subject_align=subject_align,
                   plan=plan, formats=fmts)
    bucket_stats = plan.stats(rc, ccnt, nnzc, formats=fmts)
    for rec, b in zip(bucket_stats, bt.buckets):
        rec["device_bytes"] = int(sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(b)))
        # the mode-2 scatter gathers the real compact rows of its index,
        # out of the bucket's Kb * C_pad compact slots
        rec["scatter_rows"] = int(np.asarray(b.scatter.ends)[:, -1].sum())
        rec["scatter_slots"] = b.kb * b.c_pad
    return bt, bucket_stats, shard_balance


def main(argv=None) -> dict:
    enable_compile_cache()
    reset_spans()       # the summary's ``spans`` block covers this run
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="choa", choices=["choa", "movielens", "synthetic"])
    ap.add_argument("--scale", type=float, default=0.002)
    ap.add_argument("--rank", type=int, default=5)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--constraint", default="", metavar="SPECS",
                    help="per-mode factor constraints, e.g. "
                         "'v=nonneg+l1:0.1,w=smooth:0.1' (modes h/v/w; a bare "
                         "spec applies to v and w; registered: "
                         f"{', '.join(available_constraints())} — see "
                         "repro.core.constraints). Default: the paper's "
                         "nonneg V/W.")
    ap.add_argument("--compress", default="none", metavar="SPEC",
                    help="preprocessing stage (repro.core.compress): "
                         f"registered: {', '.join(available_preprocess())}. "
                         "'rsvd[:r[:p[:q]]]' compresses every tall bucket to "
                         "randomized cores (rank r, default 2*rank; "
                         "oversampling p; q power iterations), runs the core "
                         "ALS, and expands exactly at the end")
    ap.add_argument("--backend", default="auto", choices=["jnp", "auto"],
                    help="MTTKRP backend for the ALS hot loop: 'auto' runs "
                         "each bucket on its route (SCOO segment sums, the "
                         "fused Pallas kernels on a TPU, or jnp), 'jnp' the "
                         "plain jnp reference (see repro.core.backend)")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "f16"],
                    help="compute precision for the streamed operands: "
                         "bf16/f16 stage slab values half-width while every "
                         "dot accumulates f32 (repro.kernels.common)")
    ap.add_argument("--format", default="cc", choices=list(FORMATS),
                    help="device data format (repro.core.irregular): cc "
                         "(dense over kept columns), scoo (O(nnz) flat COO), "
                         "auto (route each bucket by measured density)")
    ap.add_argument("--engine", default="host", choices=list(ENGINES),
                    help="ALS execution engine: host (per-iteration dispatch), "
                         "scan (device-resident compiled chunks), mesh "
                         "(scan + shard_map over subjects — see repro.core.engine)")
    ap.add_argument("--check-every", type=int, default=10,
                    help="iterations per device dispatch for scan/mesh "
                         "(0 = single-dispatch lax.while_loop convergence)")
    ap.add_argument("--tol", type=float, default=1e-7,
                    help="fit-change convergence tolerance")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the machine-readable run summary to PATH")
    ap.add_argument("--buckets", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    # --- fault-tolerant supervisor (repro.dist.supervisor) -----------------
    ap.add_argument("--supervise", action="store_true",
                    help="run the fit under the fault-tolerant supervisor "
                         "even without faults/checkpointing (scan/mesh only; "
                         "faultless supervised runs are bitwise the bare fit "
                         "under scan)")
    ap.add_argument("--ckpt-dir", default="", metavar="DIR",
                    help="checkpoint directory: write elastic checkpoints "
                         "every --ckpt-every chunks (repro.checkpoint)")
    ap.add_argument("--ckpt-every", type=int, default=1, metavar="N",
                    help="chunks between checkpoint writes (with --ckpt-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest checkpoint in --ckpt-dir "
                         "(restore-then-continue is bitwise under scan)")
    ap.add_argument("--fail-at", default="", metavar="SPEC",
                    help="inject transient faults at these chunk boundaries: "
                         "'1,3:5' = a blip at chunk 1, a 5-times fault at "
                         "chunk 3 (times > --max-retries forces the "
                         "checkpoint-restore path)")
    ap.add_argument("--nan-at", default="", metavar="SPEC",
                    help="poison the state with NaNs at these chunk "
                         "boundaries (same SPEC syntax as --fail-at); the "
                         "health sentinel rolls back to the last good "
                         "checkpoint")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="in-place retries per chunk before escalating to "
                         "checkpoint-restore")
    ap.add_argument("--backoff", type=float, default=0.0,
                    help="base retry backoff seconds (exponential, "
                         "deterministic seeded jitter — repro.dist.fault)")
    args = ap.parse_args(argv)

    fail_spec = parse_fail_spec(args.fail_at)
    nan_spec = parse_fail_spec(args.nan_at)
    supervise = (args.supervise or bool(args.ckpt_dir) or args.resume
                 or bool(fail_spec) or bool(nan_spec))
    if supervise and args.engine not in ("scan", "mesh"):
        raise SystemExit(
            "--supervise/--ckpt-dir/--resume/--fail-at/--nan-at need the "
            "chunked device engines: pass --engine scan or --engine mesh")
    if args.resume and not args.ckpt_dir:
        raise SystemExit("--resume needs --ckpt-dir")

    if args.constraint:
        # raises ValueError listing the registered constraints on a bad spec
        specs = parse_constraint_arg(args.constraint)
    else:
        specs = {"v": "nonneg", "w": "nonneg"}     # the paper's default
    print(f"[constraints] {constraint_summary(specs)}")

    t0 = time.perf_counter()
    data = load_dataset(args.dataset, args.scale, args.seed)
    print(f"[data] K={data.n_subjects} J={data.n_cols} nnz={data.nnz} "
          f"({time.perf_counter()-t0:.1f}s)")

    bt, bucket_stats, shard_balance = build_buckets(
        data, format=args.format, engine=args.engine, max_buckets=args.buckets)
    device_bytes = sum(rec["device_bytes"] for rec in bucket_stats)

    # raises ValueError listing the registered preprocessors on a bad spec
    opts = Parafac2Options(rank=args.rank, constraints=specs, backend=args.backend,
                           precision=args.precision,
                           engine=args.engine, check_every=args.check_every,
                           compress=args.compress)
    be = get_backend(opts.backend, opts.precision)
    for rec, b in zip(bucket_stats, bt.buckets):
        rec["route"] = be.route(b, args.rank)
    print(f"[bucketize] {len(bt.buckets)} buckets ({args.format}): "
          + ", ".join(f"{r['format']}@{r['density']*100:.1f}%->{r['route']}"
                      for r in bucket_stats)
          + f"; device bytes {device_bytes/2**20:.1f} MiB")
    t0 = time.perf_counter()
    supervisor_report = None
    if supervise:
        injector = (FaultInjector(fail_spec, nan_steps=nan_spec)
                    if (fail_spec or nan_spec) else None)
        cfg = SupervisorConfig(
            max_retries=args.max_retries, backoff=args.backoff,
            jitter=0.1 if args.backoff else 0.0,
            ckpt_dir=args.ckpt_dir or None, ckpt_every=args.ckpt_every,
            resume=args.resume, injector=injector)
        state, hist, report = supervised_fit(
            bt, opts, max_iters=args.iters, tol=args.tol, seed=args.seed,
            verbose=True, config=cfg)
        supervisor_report = report.as_dict()
        print(f"[supervisor] retries={report.retries} "
              f"restores={report.restores} rollbacks={report.rollbacks} "
              f"stragglers={len(report.stragglers)} "
              f"checkpoints={report.checkpoints_written}")
    else:
        state, hist = fit(bt, opts, max_iters=args.iters, tol=args.tol,
                          seed=args.seed, verbose=True)
    dt = time.perf_counter() - t0
    print(f"[fit] {len(hist)} iters in {dt:.1f}s "
          f"({dt/max(len(hist),1):.2f}s/iter), fit={hist[-1]:.4f}")

    phen = top_phenotype_features(np.asarray(state.V), top=5)
    for r, feats in enumerate(phen):
        print(f"phenotype {r}: " + ", ".join(f"{n}({w:.2f})" for n, w in feats[:5]))
    print("subject 0 top phenotypes:", subject_top_phenotypes(np.asarray(state.W), 0))
    V_np = np.asarray(state.V)
    summary = run_summary(
        "decompose",
        # the canonicalized option block every driver shares (includes the
        # resolved constraint + compress specs)
        resolved_options(opts, format=args.format, tol=args.tol,
                         seed=args.seed),
        dataset=args.dataset, scale=args.scale, rank=args.rank,
        engine=args.engine, backend=args.backend, precision=args.precision,
        tol=args.tol,
        check_every=args.check_every, seed=args.seed,
        # device-format decisions: requested format + the per-bucket routing
        # (chosen format, density, nnz, padded shape, device bytes)
        format=args.format,
        buckets=bucket_stats,
        device_bytes=device_bytes,
        # resolved (canonicalized) per-mode constraint specs + the V sparsity
        # they induced — the l1 knob's observable effect
        constraints=constraint_summary(specs),
        compress=args.compress,
        v_zero_fraction=float((V_np == 0.0).mean()),
        n_subjects=data.n_subjects, n_cols=data.n_cols, nnz=data.nnz,
        fit=float(hist[-1]), fit_history=[float(f) for f in hist],
        iters=len(hist), seconds_total=dt,
        seconds_per_iter=dt / max(len(hist), 1),
        platform=jax.default_backend(),
        # fault-tolerance observability: retry/restore/rollback/straggler
        # counts (None when the supervisor was not engaged) + the mesh
        # engine's per-shard nnz balance
        supervisor=supervisor_report,
        shard_balance=shard_balance,
        # host spans of this run (repro.core.spans): layout.* and
        # fit.dispatch / fit.sync, each {count, s, self_s}
        spans=span_totals(),
    )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1)
        print(f"[json] wrote {args.json}")
    return summary


if __name__ == "__main__":
    main()
