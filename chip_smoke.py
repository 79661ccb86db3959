#!/usr/bin/env python3
"""Smoke run of the PARAFAC2 fit on the TPU, through the normal entry points.

    python chip_smoke.py             # phases A and B, one chip
    python chip_smoke.py --chips 4   # phase C only, four chips

One process drives every phase, and each fit goes through
``repro.launch.decompose.main``. A phase that fails ends the run with a
non-zero exit; nothing falls back to the CPU or to interpret mode.

Phase A, the users' main path: CHOA-like data at rank 40 with the paper's
nonneg V/W, ``--format auto --backend auto --engine scan``. The fit must be
finite and non-decreasing. A small SCOO fit must also agree with a CC fit on
the plain jnp backend.

Phase B, the kernel route: the same data as CC, where ``auto`` sends every
rank-40 bucket through the fused Pallas kernels. The compiled scan chunk
must contain the Mosaic kernels (``tpu_custom_call``), and the fit must
agree with ``--backend jnp`` on the same data.

Phase C (``--chips 4``): ``--engine mesh`` with its nnz-balanced subject
shards against ``--engine scan`` on one device, on phase B's data.

Each phase prints its compile seconds (trace, lowering and backend compile,
or the persistent-cache read), seconds per iteration (fit wall time less
compile time, over the iterations), the per-bucket format and route, the
device's peak bytes in use so far, and the fit history. The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Phase A's scale, fixed ahead of time from compiles for a v5e with no chip
# attached. The auto route runs CHOA-like data as SCOO, and one rank-40 scan
# chunk needs 0.58 GiB of arguments and 8.95 GiB of temporaries at scale 0.25
# (116,225 subjects). At 0.5 the compiler refuses it (22.69G of 15.75G of
# HBM), so full CHOA (464,900 subjects) cannot fit either. The temporaries
# are the [Kb, N_pad, R] gathers and prefix sums of kernels/scoo.py.
PHASE_A_SCALE = 0.25
PHASE_B_SCALE = 0.1
SMALL_SCALE = 0.002
RANK = 40
ITERS = 10
CHECK_EVERY = 5
NONDECREASING_TOL = 1e-5
SCOO_VS_CC_TOL = 1e-4
ROUTE_TOL = 1e-3

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(RuntimeError):
    pass


class CompileClock:
    """Sums JAX's compile-duration events (trace, lowering, backend compile
    or persistent-cache read) and counts persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def reset(self):
        self.seconds, self.cache_hits = 0.0, 0

    def on_duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def max_gap(a, b) -> float:
    check(len(a) == len(b), f"histories differ in length: {len(a)} vs {len(b)}")
    return max(abs(x - y) for x, y in zip(a, b))


def fit_run(clock, label: str, *, scale: float, fmt: str, backend: str,
            engine: str = "scan") -> dict:
    """One ``decompose.main`` fit; prints and returns its phase record."""
    import jax
    from repro.launch import decompose

    clock.reset()
    s = decompose.main([
        "--dataset", "choa", "--scale", str(scale), "--rank", str(RANK),
        "--format", fmt, "--backend", backend, "--engine", engine,
        "--check-every", str(CHECK_EVERY), "--iters", str(ITERS),
        "--seed", "0"])
    gc.collect()
    hist = s["fit_history"]
    rec = {
        "label": label, "scale": scale, "format": fmt, "backend": backend,
        "engine": engine, "n_subjects": s["n_subjects"], "nnz": s["nnz"],
        "compile_seconds": clock.seconds, "cache_hits": clock.cache_hits,
        "seconds_per_iter": max(s["seconds_total"] - clock.seconds, 0.0)
        / max(len(hist), 1),
        "buckets": [{k: b.get(k) for k in ("format", "route", "n_subjects",
                                            "i_pad", "c_pad", "nnz_pad")}
                    for b in s["buckets"]],
        "peak_bytes_in_use": jax.devices()[0].memory_stats().get(
            "peak_bytes_in_use"),
        "fit_history": hist,
    }
    print(f"[smoke] {json.dumps(rec)}", flush=True)
    check(len(hist) == ITERS, f"{label}: {len(hist)} iterations, want {ITERS}")
    check(all(math.isfinite(f) for f in hist), f"{label}: non-finite fit {hist}")
    return rec


def phase_a(clock) -> None:
    print(f"[phase A] CHOA-like at scale {PHASE_A_SCALE}: "
          f"{int(464_900 * PHASE_A_SCALE)} of 464,900 subjects; cut because "
          f"the SCOO chunk at scale 0.5 needs 22.69G of 15.75G of HBM "
          f"(see PHASE_A_SCALE)", flush=True)
    main = fit_run(clock, "A:auto", scale=PHASE_A_SCALE, fmt="auto",
                   backend="auto")
    h = main["fit_history"]
    drops = [b - a for a, b in zip(h, h[1:])]
    check(min(drops) >= -NONDECREASING_TOL,
          f"phase A: fit decreased by {-min(drops):.3g}")
    scoo = fit_run(clock, "A:small-scoo", scale=SMALL_SCALE, fmt="scoo",
                   backend="auto")
    cc = fit_run(clock, "A:small-cc-jnp", scale=SMALL_SCALE, fmt="cc",
                 backend="jnp")
    gap = abs(scoo["fit_history"][-1] - cc["fit_history"][-1])
    print(f"[phase A] SCOO vs CC/jnp |fit gap| after {ITERS} iterations = "
          f"{gap!r} (largest over the history "
          f"{max_gap(scoo['fit_history'], cc['fit_history'])!r})", flush=True)
    check(gap <= SCOO_VS_CC_TOL, f"phase A: SCOO vs CC gap {gap:.3g}")


def compiled_chunk_text(scale: float) -> str:
    """HLO of the scan chunk ``decompose`` compiles for phase B's data."""
    import jax
    from repro.core import Parafac2Options, init_state
    from repro.core.engine import als_chunk_fn
    from repro.launch import decompose

    data = decompose.load_dataset("choa", scale, 0)
    bt, _, _ = decompose.build_buckets(data, format="cc", engine="scan",
                                       max_buckets=4)
    opts = Parafac2Options(rank=RANK, constraints={"v": "nonneg", "w": "nonneg"},
                           backend="auto", engine="scan",
                           check_every=CHECK_EVERY)
    state = init_state(bt, opts, 0)
    chunk = jax.jit(als_chunk_fn(opts, CHECK_EVERY), donate_argnums=(1,))
    return chunk.lower(bt, state).compile().as_text()


def phase_b(clock) -> None:
    fused = fit_run(clock, "B:cc-auto", scale=PHASE_B_SCALE, fmt="cc",
                    backend="auto")
    routes = {b["route"] for b in fused["buckets"]}
    check(routes == {"fused"}, f"phase B: routes {routes}, want all fused")
    n_kernels = compiled_chunk_text(PHASE_B_SCALE).count("tpu_custom_call")
    print(f"[phase B] compiled chunk: tpu_custom_call x{n_kernels}", flush=True)
    check(n_kernels > 0, "phase B: no tpu_custom_call in the compiled chunk")
    ref = fit_run(clock, "B:cc-jnp", scale=PHASE_B_SCALE, fmt="cc",
                  backend="jnp")
    gap = max_gap(fused["fit_history"], ref["fit_history"])
    print(f"[phase B] fused vs jnp max |fit gap| = {gap!r}", flush=True)
    check(gap <= ROUTE_TOL, f"phase B: fused vs jnp gap {gap:.3g}")


def phase_c(clock) -> None:
    mesh = fit_run(clock, "C:mesh", scale=PHASE_B_SCALE, fmt="cc",
                   backend="auto", engine="mesh")
    scan = fit_run(clock, "C:scan", scale=PHASE_B_SCALE, fmt="cc",
                   backend="auto", engine="scan")
    gap = max_gap(mesh["fit_history"], scan["fit_history"])
    print(f"[phase C] mesh vs scan max |fit gap| = {gap!r}", flush=True)
    check(gap <= ROUTE_TOL, f"phase C: mesh vs scan gap {gap:.3g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: phases A and B; 4: phase C (mesh engine) only")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"[device] {dev.device_kind} x{len(devices)}", flush=True)
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    try:
        if args.chips == 4:
            phase_c(clock)
        else:
            phase_a(clock)
            phase_b(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
