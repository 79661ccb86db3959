"""End-to-end ALS benchmark: whole decompositions, engine × backend ×
format grid.

DPar2 (PAPERS.md) argues whole-decomposition time is the metric that matters —
the MTTKRP micro benchmark (`mttkrp_micro.py`) cannot see the per-iteration
host dispatch + `float(state.fit)` sync the host loop pays, which at small
ranks IS the wall-clock floor. This benchmark times `iters` ALS iterations
through each execution engine (host | scan | mesh — repro.core.engine),
backend (jnp | auto), device data format (cc | scoo | auto —
repro.core.irregular; SCOO is the O(nnz) sparse path, and the low-density
``synthsparse`` dataset is the regime where CC's densified rectangles burn
~100x the FLOPs) and constraint route (none | nonneg | nonneg_admm |
smooth — repro.core.constraints; COPA's claim is that AO-ADMM constraints
ride the same MTTKRP core at negligible extra cost, and this axis measures
exactly that) on geometry-preserving shrinks of the paper's datasets
(`choa_like` / `movielens_like`), reporting steady-state seconds/iteration
(compile excluded; the compiled callables are built once, then timed), a
whole-run wall time, and ``peak_bytes`` — the compiled als_step's
argument+temp device allocation, the metric where SCOO's win is
density-proportional.

  PYTHONPATH=src python -m benchmarks.als_e2e --datasets synthsparse \
      --rank 5 --iters 20 --engines host,scan --formats cc,scoo \
      --constraints nonneg --json BENCH_als.json

Rows: ``als/<dataset>/<engine>/<backend>/<constraint>`` with a ``/scoo`` or
``/auto`` suffix for non-CC formats (CC rows keep the historical unsuffixed
names so the checked-in baseline stays comparable) and the canonical
compress spec as a suffix (``/rsvd:8:4:1``) for compressed runs
(``--compress none,rsvd:10:8:1`` — the DPar2-style
randomized compression stage of repro.core.compress: compression is timed
once as ``compress_seconds``, the grid times the CORE ALS, and
``speedup_vs_uncompressed_per_iter`` / ``fit_gap_vs_uncompressed`` record
the steady-state win and the accuracy cost vs the same uncompressed
configuration). ``--xl-probe`` runs the
"larger instance" demonstration: a geometry whose densified CC buffer alone
exceeds host+device memory, decomposed under SCOO and recorded with the CC
buffer size it avoided. The JSON artifact is the CI perf trajectory
(BENCH_als.json); `benchmarks/compare.py` gates it against the checked-in
baseline.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import Parafac2Options, bucketize, init_state
from repro.core import engine as als_engine
from repro.core.compress import parse_preprocess_spec
from repro.core.parafac2 import als_step
from repro.data import choa_like, movielens_like
from repro.sparse import random_irregular
from benchmarks.common import calibrate, emit, time_call

# the benchmark's constraint axis: name -> per-mode specs
CONSTRAINT_CASES = {
    "none": {"v": "none", "w": "none"},
    "nonneg": {"v": "nonneg", "w": "nonneg"},            # the paper's default
    "nonneg_admm": {"v": "nonneg_admm", "w": "nonneg_admm"},
    "l1": {"v": "nonneg+l1:0.1", "w": "nonneg"},
    "smooth": {"v": "nonneg", "w": "smooth:0.1"},
}


def _load(name: str, scale: float, seed: int):
    if name == "choa":
        return choa_like(scale=scale, seed=seed)
    if name == "movielens":
        return movielens_like(scale=scale, seed=seed)
    if name == "synthsparse":
        # EHR-like low intra-slice density (≤1% of the kept-column
        # rectangle): many observation rows, each touching a handful of the
        # kept columns — the regime the SCOO format exists for. K scales
        # like choa so --scale works uniformly.
        return random_irregular(
            n_subjects=max(64, int(256_000 * scale)), n_cols=4096,
            max_rows=256, avg_nnz_per_subject=256, seed=seed)
    raise ValueError(name)


def _peak_bytes(bt, opts) -> int:
    """Compiled als_step device allocation (arguments + temporaries) with the
    data passed as a runtime argument — counts the format's resident buffers
    plus the step's scratch, the number that decides whether a geometry fits."""
    state0 = init_state(bt, opts, seed=0)
    compiled = jax.jit(
        lambda d, s: als_step(d, s, opts)).lower(bt, state0).compile()
    mem = compiled.memory_analysis()
    return int((getattr(mem, "argument_size_in_bytes", 0) or 0)
               + (getattr(mem, "temp_size_in_bytes", 0) or 0))


def _make_runner(bt, opts, iters: int):
    """A zero-arg callable running `iters` ALS iterations the way the
    engine's fitting loop would, from a fixed init state, returning the final
    fit. Compiled callables are built ONCE here so timing excludes compile;
    donation is off so the init state survives repeated timed runs."""
    state0 = init_state(bt, opts, seed=0)

    if opts.engine == "host":
        step = jax.jit(lambda s: als_step(bt, s, opts))

        def run():
            s = state0
            f = float("nan")
            for _ in range(iters):
                s = step(s)
                f = float(s.fit)   # the host loop's per-iteration device sync
            return f

        return run

    # scan/mesh: ceil(iters / check_every) chunk dispatches, one sync each
    lengths = []
    left = iters
    while left > 0:
        n = min(opts.check_every or iters, left)
        lengths.append(n)
        left -= n
    chunks = {n: als_engine.make_als_chunk(bt, opts, n, donate=False)
              for n in set(lengths)}

    def run():
        s = state0
        f = float("nan")
        for n in lengths:
            s, fits = chunks[n](s)
            f = float(np.asarray(fits)[-1])
        return f

    return run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", default="choa,movielens")
    ap.add_argument("--scale", type=float, default=0.002)
    ap.add_argument("--rank", type=int, default=5)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--engines", default="host,scan",
                    help="comma list from host,scan,mesh")
    ap.add_argument("--backends", default="jnp",
                    help="comma list from jnp,auto")
    ap.add_argument("--formats", default="cc",
                    help="comma list from cc,scoo,auto (device data format; "
                         "cc rows keep the historical unsuffixed names)")
    ap.add_argument("--constraints", default="nonneg",
                    help=f"comma list from {','.join(CONSTRAINT_CASES)}")
    ap.add_argument("--compress", default="none",
                    help="comma list of repro.core.compress specs (e.g. "
                         "'none,rsvd:10:8:1'): non-identity specs compress "
                         "once (timed separately as compress_seconds), then "
                         "the grid times the CORE ALS; rows get a "
                         "'/<preprocess>' suffix and a gated "
                         "speedup_vs_uncompressed_per_iter ratio")
    ap.add_argument("--supervised-namespace", action="store_true",
                    help="additionally run the als_supervised grid: the bare "
                         "chunked scan loop vs a faultless supervised_fit "
                         "(repro.dist.supervisor) on the first dataset, "
                         "interleaved repeats — rows als_supervised/<ds>/bare "
                         "and /supervised with the paired "
                         "overhead_vs_bare_per_iter ratio")
    ap.add_argument("--overhead-gate", type=float, default=0.0,
                    help="with --supervised-namespace: fail (exit 1) if the "
                         "median paired supervised/bare s/iter ratio exceeds "
                         "this (e.g. 1.05 = supervisor overhead must stay "
                         "within 5%%); 0 disables the gate")
    ap.add_argument("--xl-probe", action="store_true",
                    help="run the 'larger instance' demo: a geometry whose "
                         "densified CC buffer exceeds memory, fit under SCOO "
                         "(records the avoided CC bytes; slow — not for CI)")
    ap.add_argument("--check-every", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed repetitions per case (median reported)")
    ap.add_argument("--json", default="",
                    help="write per-case timings to this JSON file")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    engines = [s.strip() for s in args.engines.split(",") if s.strip()]
    backends = [s.strip() for s in args.backends.split(",") if s.strip()]
    constraints = [s.strip() for s in args.constraints.split(",") if s.strip()]
    for c in constraints:
        if c not in CONSTRAINT_CASES:
            raise SystemExit(f"unknown constraint case {c!r}; choose from "
                             f"{', '.join(CONSTRAINT_CASES)}")
    compress_cases = [s.strip() for s in args.compress.split(",") if s.strip()]
    # parse eagerly (raises ValueError listing registered preprocessors) and
    # run identity first so the vs-uncompressed ratios always have their ref
    compress_cases.sort(key=lambda c: not parse_preprocess_spec(c).identity)
    results = {"config": {
        "scale": args.scale, "rank": args.rank, "iters": args.iters,
        "check_every": args.check_every, "platform": jax.default_backend(),
        "calib_seconds": calibrate(),
    }}

    formats = [s.strip() for s in args.formats.split(",") if s.strip()]
    # cc must be measured before the other formats so their vs-cc ratios
    # (the gated headline metrics) exist regardless of the flag order
    formats.sort(key=lambda f: f != "cc")
    for ds in [s.strip() for s in args.datasets.split(",") if s.strip()]:
        data = _load(ds, args.scale, args.seed)
        align = len(jax.devices()) if "mesh" in engines else 1
        for fmt in formats:
            bt = bucketize(data, max_buckets=4, dtype=jnp.float32,
                           subject_align=align, format=fmt)
            # CC rows keep the historical unsuffixed names; other formats
            # append "/<fmt>" so the baseline comparison stays stable
            suffix = "" if fmt == "cc" else f"/{fmt}"
            host_per_iter = {}
            cc_per_iter = {}
            peak_cache = {}
            comp_cache = {}
            uncompressed_ref = {}
            for engine in engines:
                for backend in backends:
                    for cname in constraints:
                        # two passes over the compress axis: build + warm
                        # every case's runner first, then interleave the
                        # timed repeats round-robin. The uncompressed and
                        # compressed runs land in the SAME noise window, so
                        # the gated speedup_vs_uncompressed ratio is robust
                        # to machine-load drift between measurement windows
                        # (sequential timing puts minutes between the pair).
                        prepped = []
                        for cspec in compress_cases:
                            pp = parse_preprocess_spec(cspec)
                            # the grid always times the (core) ALS itself:
                            # compression is a one-shot preprocessing stage,
                            # timed separately as compress_seconds
                            opts = Parafac2Options(
                                rank=args.rank,
                                constraints=CONSTRAINT_CASES[cname],
                                backend=backend, engine=engine,
                                check_every=args.check_every)
                            if pp.identity:
                                run_bt, compress_s, csuffix = bt, 0.0, ""
                            else:
                                if (backend, pp.spec) not in comp_cache:
                                    t0 = time.perf_counter()
                                    comp = pp.apply(bt, opts, seed=args.seed)
                                    jax.block_until_ready(
                                        jax.tree_util.tree_leaves(comp.data))
                                    comp_cache[(backend, pp.spec)] = (
                                        comp, time.perf_counter() - t0)
                                comp, compress_s = comp_cache[(backend, pp.spec)]
                                # the full canonical spec keeps two sketches
                                # of the same preprocessor (rsvd:8:4:1 vs
                                # rsvd:6:2:1) on distinct result keys
                                run_bt, csuffix = comp.data, f"/{pp.spec}"
                            pkey = (backend, cname, pp.spec)
                            if pkey not in peak_cache:
                                peak_cache[pkey] = _peak_bytes(run_bt, opts)
                            run = _make_runner(run_bt, opts, args.iters)
                            final_fit = float("nan")
                            for _ in range(2):  # compile + warm
                                final_fit = run()
                            prepped.append({
                                "pp": pp, "compress_s": compress_s,
                                "csuffix": csuffix, "peak": peak_cache[pkey],
                                "run": run, "final_fit": final_fit,
                                "times": []})
                        for _ in range(args.repeats):
                            for case in prepped:
                                t0 = time.perf_counter()
                                case["final_fit"] = case["run"]()
                                case["times"].append(
                                    time.perf_counter() - t0)
                        for case in prepped:
                            pp, csuffix = case["pp"], case["csuffix"]
                            compress_s, peak = case["compress_s"], case["peak"]
                            final_fit = case["final_fit"]
                            ts = sorted(case["times"])
                            seconds = ts[len(ts) // 2]
                            per_iter = seconds / args.iters
                            rel = ""
                            if engine == "host":
                                host_per_iter[(backend, cname, pp.spec)] = per_iter
                            elif (backend, cname, pp.spec) in host_per_iter:
                                speedup = (host_per_iter[(backend, cname, pp.spec)]
                                           / per_iter)
                                rel = f"speedup_vs_host={speedup:.2f}x"
                            emit(f"als/{ds}/{engine}/{backend}/{cname}"
                                 f"{suffix}{csuffix}",
                                 per_iter,
                                 f"fit={final_fit:.4f} peak={peak/2**20:.1f}MiB "
                                 f"{rel}".strip())
                            rec = {"seconds_per_iter": per_iter,
                                   "seconds_total": seconds,
                                   "iters": args.iters, "final_fit": final_fit,
                                   "peak_bytes": peak,
                                   "n_subjects": data.n_subjects,
                                   "nnz": data.nnz}
                            if rel:
                                rec["speedup_vs_host_per_iter"] = speedup
                            key = (engine, backend, cname)
                            if pp.identity:
                                uncompressed_ref[key] = (per_iter, final_fit)
                                if fmt == "cc":
                                    cc_per_iter[key] = per_iter
                                    results.setdefault("_cc_ref", {})[
                                        f"{ds}/{engine}/{backend}/{cname}"] = {
                                            "seconds_per_iter": per_iter,
                                            "peak_bytes": peak}
                                else:
                                    ref = results.get("_cc_ref", {}).get(
                                        f"{ds}/{engine}/{backend}/{cname}")
                                    if ref:
                                        rec["speedup_vs_cc_per_iter"] = (
                                            ref["seconds_per_iter"] / per_iter)
                                        rec["peak_bytes_vs_cc"] = (
                                            ref["peak_bytes"] / max(peak, 1))
                            else:
                                rec["compress_spec"] = pp.spec
                                rec["compress_seconds"] = compress_s
                                if key in uncompressed_ref:
                                    ref_s, ref_fit = uncompressed_ref[key]
                                    # the gated headline: steady-state core
                                    # s/iter vs the uncompressed same-config
                                    # run; fit_gap is informational
                                    rec["speedup_vs_uncompressed_per_iter"] = (
                                        ref_s / per_iter)
                                    rec["fit_gap_vs_uncompressed"] = (
                                        ref_fit - final_fit)
                            results[f"{ds}/{engine}/{backend}/{cname}"
                                    f"{suffix}{csuffix}"] = rec

    overhead = None
    if args.supervised_namespace:
        rows, overhead = _supervised_cases(args)
        results.update(rows)

    if args.xl_probe:
        results["xl"] = _xl_probe(args)

    # _cc_ref was scaffolding for the vs-cc ratios, not a gated namespace
    results.pop("_cc_ref", None)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)

    if args.overhead_gate and overhead is not None and overhead > args.overhead_gate:
        raise SystemExit(
            f"FAIL: supervisor overhead {overhead:.3f}x exceeds the "
            f"--overhead-gate {args.overhead_gate:.2f}x budget")
    return results


def _supervised_cases(args):
    """The ``als_supervised`` namespace: the chunked scan loop bare
    (``_make_runner``'s exact pattern) vs wrapped in a FAULTLESS
    ``repro.dist.supervisor.supervised_fit`` — identical data, init state and
    chunk lengths, the compiled chunk shared through the supervisor's
    ``chunk_cache`` seam so both sides time steady-state dispatches only.
    What remains is the supervisor's per-chunk host cost (health sentinel,
    watchdog, snapshot bookkeeping), the price of turning fault tolerance on;
    the paired median ratio is what ``--overhead-gate`` holds to budget.
    Returns ``(rows, median supervised/bare ratio)``."""
    from repro.dist.supervisor import SupervisorConfig, supervised_fit

    ds = [s.strip() for s in args.datasets.split(",") if s.strip()][0]
    data = _load(ds, args.scale, args.seed)
    bt = bucketize(data, max_buckets=4, dtype=jnp.float32)
    opts = Parafac2Options(rank=args.rank,
                           constraints=CONSTRAINT_CASES["nonneg"],
                           engine="scan", check_every=args.check_every)
    state0 = init_state(bt, opts, seed=0)   # _make_runner's init, shared
    bare = _make_runner(bt, opts, args.iters)
    cache = {}

    def supervised():
        cfg = SupervisorConfig(chunk_cache=cache)
        _, hist, _ = supervised_fit(bt, opts, max_iters=args.iters, tol=0.0,
                                    state=state0, config=cfg)
        return hist[-1]

    fits, ratios = {}, []
    times = {"bare": [], "supervised": []}
    for name, run in (("bare", bare), ("supervised", supervised)):
        for _ in range(2):   # compile + warm
            fits[name] = run()
    for _ in range(args.repeats):
        round_t = {}
        for name, run in (("bare", bare), ("supervised", supervised)):
            t0 = time.perf_counter()
            fits[name] = run()
            round_t[name] = time.perf_counter() - t0
            times[name].append(round_t[name])
        ratios.append(round_t["supervised"] / round_t["bare"])
    overhead = sorted(ratios)[len(ratios) // 2]

    out = {}
    for name in ("bare", "supervised"):
        ts = sorted(times[name])
        per_iter = ts[len(ts) // 2] / args.iters
        rec = {"seconds_per_iter": per_iter, "final_fit": fits[name],
               "iters": args.iters, "n_subjects": data.n_subjects,
               "nnz": data.nnz}
        rel = ""
        if name == "supervised":
            rec["overhead_vs_bare_per_iter"] = overhead
            rel = f"overhead_vs_bare={overhead:.3f}x"
        emit(f"als_supervised/{ds}/{name}", per_iter,
             f"fit={fits[name]:.4f} {rel}".strip())
        out[f"als_supervised/{ds}/{name}"] = rec
    # the supervisor must not change the answer, only survive faults: a
    # faultless wrapped run is bitwise the bare chunk loop
    assert fits["supervised"] == fits["bare"], (
        fits["supervised"], fits["bare"])
    return out, overhead


def _xl_probe(args) -> dict:
    """The "larger instance" demonstration: a ≤0.1%-density geometry whose
    densified CC rectangle alone would not fit in memory, decomposed under
    SCOO. Records the avoided CC bytes and the measured SCOO footprint."""
    from repro.sparse import plan_buckets

    print("[xl-probe] generating ~33M-nonzero low-density irregular tensor "
          "(this is deliberately past the densifiable regime)")
    data = random_irregular(n_subjects=16_384, n_cols=16_384, max_rows=1000,
                            avg_nnz_per_subject=2048, seed=args.seed)
    plan = plan_buckets(data.row_counts(), data.col_counts(),
                        nnz_counts=data.nnz_counts(), max_buckets=4)
    # what CC would have to allocate for the same plan (f32 vals alone)
    cc_bytes = sum(len(mem) * ip * cp * 4
                   for (ip, cp), mem in zip(plan.shapes, plan.members))
    bt = bucketize(data, dtype=jnp.float32, plan=plan,
                   formats=["scoo"] * plan.n_buckets)
    scoo_bytes = int(sum(
        leaf.size * leaf.dtype.itemsize
        for b in bt.buckets for leaf in jax.tree_util.tree_leaves(b)))
    opts = Parafac2Options(rank=args.rank, constraints={"v": "nonneg",
                                                        "w": "nonneg"},
                           backend="auto", engine="host")
    run = _make_runner(bt, opts, 2)
    seconds, final_fit = time_call(run, warmup=1, iters=1)
    per_iter = seconds / 2
    emit("als/xl/scoo", per_iter,
         f"fit={final_fit:.4f} scoo={scoo_bytes/2**30:.2f}GiB "
         f"cc_would_alloc={cc_bytes/2**30:.1f}GiB")
    return {
        "n_subjects": data.n_subjects, "n_cols": data.n_cols,
        "nnz": data.nnz, "seconds_per_iter_scoo": per_iter,
        "final_fit": final_fit,
        "scoo_device_bytes": scoo_bytes,
        "cc_would_alloc_bytes": int(cc_bytes),
        "cc_vs_scoo_bytes": cc_bytes / max(scoo_bytes, 1),
        "note": "cc_would_alloc_bytes is the f32 vals rectangle alone under "
                "the same bucket plan — it exceeds this host's memory, so "
                "the CC path cannot run this geometry at all",
    }


if __name__ == "__main__":
    main()
