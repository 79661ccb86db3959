"""Kernel-level micro-benchmark: per-mode SPARTan MTTKRP vs materialized-KRP
baseline on identical inputs (the paper's core computational claim).

``--backends jnp,auto`` times every requested MTTKRP backend side by side
in one invocation (rows ``mttkrp/<mode>/<backend>``), each against the shared
dense baseline; ``--formats cc,scoo`` adds the device-format axis (rows for
non-CC formats get a ``/<fmt>`` suffix; under ``auto`` SCOO stages run the
O(nnz) segment-sum route of :mod:`repro.kernels.scoo` through the
bucket-level backend API). The format axis also times the two formation stages the
whole-iteration cost is dominated by on sparse data — ``xkv`` (X_k V) and
``project`` (Y_k = Q_k^T X_k) — which the mode-level rows never see.
``--json PATH`` additionally writes the timings as a JSON artifact (the CI
perf trajectory, BENCH_mttkrp.json), including a ``dispatches_per_iter``
block per backend — the bucket-stage dispatch count one full ALS iteration
costs (the jnp route: 5/bucket; the fused route, which ``auto`` takes only on
a TPU: 4/bucket, the exact-parity fusion floor — see repro.kernels.fused).
"""
from __future__ import annotations

import argparse
import json

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import Parafac2Options, als_step, bucketize, init_state
from repro.core.backend import dispatch_tally, get_backend
from repro.core.spans import launches
from repro.core.baseline import baseline_mode1, baseline_mode2, baseline_mode3, dense_y
from repro.sparse import random_irregular
from benchmarks.common import calibrate, emit, time_call


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--subjects", type=int, default=2000)
    ap.add_argument("--cols", type=int, default=2000)
    ap.add_argument("--rank", type=int, default=40)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--backends", default="jnp,auto",
                    help="comma list of MTTKRP backends to time side by side "
                         "(jnp,auto)")
    ap.add_argument("--formats", default="cc",
                    help="comma list of device formats (cc,scoo); non-CC "
                         "rows get a /<fmt> suffix")
    ap.add_argument("--json", default="",
                    help="write per-mode/backend timings to this JSON file")
    args = ap.parse_args(argv)
    backends = [s.strip() for s in args.backends.split(",") if s.strip()]

    # geometry mirrors the paper's sparse regime: few active columns (c_k)
    # out of many variables J — that is where the reformulation wins.
    rng = np.random.default_rng(0)
    data = random_irregular(n_subjects=args.subjects, n_cols=args.cols,
                            max_rows=30, avg_nnz_per_subject=60, seed=5)
    K, J, R = data.n_subjects, data.n_cols, args.rank
    H = jnp.asarray(rng.standard_normal((R, R)), jnp.float32)
    V = jnp.asarray(rng.standard_normal((J, R)), jnp.float32)
    W = jnp.asarray(rng.standard_normal((K, R)), jnp.float32)

    # the CC bucketing defines the shared geometry; the SCOO bucketing reuses
    # the identical plan so every format sees the same buckets and the same
    # random Q (and therefore bitwise-identical Yc up to accumulation order)
    from repro.sparse import plan_buckets
    plan = plan_buckets(data.row_counts(), data.col_counts(),
                        nnz_counts=data.nnz_counts(), max_buckets=4)
    bts = {}
    for fmt in [s.strip() for s in args.formats.split(",") if s.strip()]:
        bts[fmt] = bucketize(data, dtype=jnp.float32, plan=plan,
                             formats=[fmt] * plan.n_buckets)
    bt0 = next(iter(bts.values()))
    Qs = [jnp.asarray(rng.standard_normal((b.kb, b.i_pad, R)), jnp.float32)
          for b in bt0.buckets]
    Ycs = [b.project(Q) for b, Q in zip(bts.get("cc", bt0).buckets, Qs)]

    # factors are traced ARGUMENTS (otherwise XLA constant-folds the whole
    # computation and the timing is meaningless); bucket data is closed over
    # identically for every method.
    Y = jax.jit(lambda: dense_y(bt0.buckets, Ycs, J, K))()
    base_fns = {
        "mode1": (jax.jit(lambda V, W: baseline_mode1(Y, V, W)), (V, W)),
        "mode2": (jax.jit(lambda H, W: baseline_mode2(Y, H, W)), (H, W)),
        "mode3": (jax.jit(lambda H, V: baseline_mode3(Y, H, V)), (H, V)),
    }
    base = {}
    for name, (fn, fargs) in base_fns.items():
        base[name] = time_call(fn, *fargs, iters=args.iters)

    results = {"config": {"subjects": K, "cols": J, "rank": R,
                          "nnz": data.nnz,
                          "platform": jax.default_backend(),
                          "calib_seconds": calibrate()}}
    for fmt, bt in bts.items():
        sfx = "" if fmt == "cc" else f"/{fmt}"
        for bname in backends:
            be = get_backend(bname)
            buckets = bt.buckets
            # per-bucket projected representations (untimed, like Ycs): the
            # jnp route materializes Yc, the scoo route carries Q
            projs = [be.project_bucket(b, Q) for b, Q in zip(buckets, Qs)]

            def run_mode1(V, W):
                return sum(
                    be.mode1_bucket(b, p, jnp.take(W, b.subject_ids, 0), V)
                    for b, p in zip(buckets, projs))

            def run_mode2(H, W):
                M2 = jnp.zeros((J, R), H.dtype)
                for b, p in zip(buckets, projs):
                    A = be.mode2_bucket(b, p, H, jnp.take(W, b.subject_ids, 0))
                    M2 = M2 + be.mode2_scatter(A, b.scatter).astype(M2.dtype)
                return M2

            def run_mode3(H, V):
                M3 = jnp.zeros((K, R), H.dtype)
                for b, p in zip(buckets, projs):
                    rows = be.mode3_bucket(b, p, H, V)
                    M3 = M3.at[b.subject_ids].add(rows.astype(M3.dtype))
                return M3

            sp_fns = {
                "mode1": (jax.jit(run_mode1), (V, W)),
                "mode2": (jax.jit(run_mode2), (H, W)),
                "mode3": (jax.jit(run_mode3), (H, V)),
            }
            for name, (fn, fargs) in sp_fns.items():
                t_sp, a = time_call(fn, *fargs, iters=args.iters)
                t_bl, b = base[name]
                err = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))
                emit(f"mttkrp/{name}/{bname}{sfx}", t_sp,
                     f"speedup={t_bl/t_sp:.2f}x relerr={err:.2e}")
                results[f"{name}/{bname}{sfx}"] = {
                    "us_per_call": t_sp * 1e6, "speedup_vs_baseline": t_bl / t_sp,
                    "relerr": err}

            # formation stages (X_k V, Q^T X_k): the O(nnz)-vs-O(I*C) gap
            # lives here, not in the compact mode contractions
            def run_xkv(V):
                return [be.xkv_bucket(b, V) for b in buckets]

            def run_project(H):
                # H is a stand-in traced arg to defeat constant folding
                return [be.project_bucket(b, Q * H[0, 0]) for b, Q in
                        zip(buckets, Qs)]

            t_x, _ = time_call(jax.jit(run_xkv), V, iters=args.iters)
            emit(f"mttkrp/xkv/{bname}{sfx}", t_x, "")
            results[f"xkv/{bname}{sfx}"] = {"us_per_call": t_x * 1e6}
            # the scoo route's project_bucket (auto on SCOO buckets) is Q
            # pass-through BY DESIGN (Yc is never materialized; the cost
            # moves into the triplet contractions timed above) — a timing
            # row for it would be a meaningless ~0
            if not (fmt == "scoo" and bname == "auto"):
                t_p, _ = time_call(jax.jit(run_project), H, iters=args.iters)
                emit(f"mttkrp/project/{bname}{sfx}", t_p, "")
                results[f"project/{bname}{sfx}"] = {"us_per_call": t_p * 1e6}
    for name, (t_bl, _) in base.items():
        emit(f"mttkrp/{name}/baseline", t_bl, "")
        results[f"{name}/baseline"] = {"us_per_call": t_bl * 1e6}

    # bucket-stage dispatch count per full ALS iteration (ticks fire at
    # trace time, so eval_shape counts one als_step without running it):
    # jnp route = 5/bucket, fused route = 4/bucket (the exact-parity floor)
    bt_cc = bts.get("cc", bt0)
    for bname in backends:
        opts = Parafac2Options(rank=R, dtype=jnp.float32, backend=bname)
        s0 = init_state(bt_cc, opts, seed=0)
        with dispatch_tally() as tally:
            jax.eval_shape(lambda s: als_step(bt_cc, s, opts), s0)
        per_iter = launches(tally)
        per_bucket = per_iter / max(len(bt_cc.buckets), 1)
        emit(f"mttkrp/dispatches_per_iter/{bname}", 0.0,
             f"total={per_iter} per_bucket={per_bucket:.1f}")
        results[f"dispatches_per_iter/{bname}"] = {
            "total": per_iter, "per_bucket": per_bucket,
            "by_stage": dict(tally)}

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    main()
