"""Device time of the batched Procrustes eigendecomposition, by Gram side.

``polar_gram_eigh`` eigendecomposes the I x I row Gram of B [Kb, I, R] when
I < R and the R x R column Gram otherwise. This times, on a TPU, at the
bucket shapes of the ``choa-r40`` benchmark cells (Kb 11,623, R 40, I_pad
8, 16, 24 and 168):

* ``eigh/<side>/i<I>``: ``jnp.linalg.eigh`` alone on the f32 Grams of a
  bucket-like B: n x n with n = I on the row side, n = 40 on the column
  side;
* ``polar/<side>/i<I>``: the whole polar factor of that side, Gram to Q,
  under f32 matmul precision as ``als_step`` runs it;
* the chip's answer against a float64 SVD pseudo-polar on the host (first
  256 subjects of each bucket), and the largest entry of Q in padded rows.

B's real rows are dense nonnegative, |N(0, 1)| + 0.1, with each subject's
real row count drawn between the previous bucket's I_pad and its own;
rows below are zero. Device times are the summed device durations of the
program's ops in a profiler trace, per call. Needs a TPU; run from the root
of the checkout:

    PYTHONPATH=src python -m benchmarks.eigh_micro --json eigh_micro.json
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import trace
from repro.core.procrustes import _polar_cols, _polar_rows, polar_gram_eigh

I_PADS = (8, 16, 24, 168)


def bucket_b(key, kb: int, lo: int, i_pad: int, r: int) -> jax.Array:
    """[kb, i_pad, r] f32 with lo+1..i_pad real rows per subject."""
    kv, kr = jax.random.split(key)
    B = jnp.abs(jax.random.normal(kv, (kb, i_pad, r), jnp.float32)) + 0.1
    rows = jax.random.randint(kr, (kb,), lo + 1, i_pad + 1)
    return B * (jnp.arange(i_pad)[None, :] < rows[:, None])[..., None]


def device_ms(fn, arg, reps: int):
    """(device ms per call, eigh device ms per call, host ms per call)."""
    jfn = jax.jit(fn)
    compiled = jfn.lower(arg).compile()
    jax.block_until_ready(compiled(arg))
    host = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(arg))
        host.append(time.perf_counter() - t0)
    names = trace.hlo_op_names(compiled.as_text())
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(reps):
            jax.block_until_ready(compiled(arg))
        jax.profiler.stop_trace()
        tr = trace.load(tmp, names)
    all_ns = trace.op_time(tr, lambda op: True)
    eigh_ns = trace.op_time(tr, lambda op: "eigh" in op.op_name)
    return all_ns / reps / 1e6, eigh_ns / reps / 1e6, statistics.median(host) * 1e3


def pseudo_polar_f64(B: np.ndarray, rel_tol: float) -> np.ndarray:
    U, s, Vt = np.linalg.svd(B.astype(np.float64), full_matrices=False)
    keep = s ** 2 > rel_tol * s[:, :1] ** 2
    return np.einsum("kil,kl,klr->kir", U, keep.astype(np.float64), Vt)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--subjects", type=int, default=11623)
    ap.add_argument("--rank", type=int, default=40)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"eigh_micro: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    R, kb = args.rank, args.subjects
    rel_tol = R * float(jnp.finfo(jnp.float32).eps)
    rows_gram = lambda B: jnp.einsum("kir,kjr->kij", B, B)
    cols_gram = lambda B: jnp.einsum("kir,kil->krl", B, B)
    out = {"device": dev.device_kind, "subjects": kb, "rank": R, "cases": {},
           "check": {}}
    key = jax.random.PRNGKey(args.seed)
    lo = 0
    with jax.default_matmul_precision("float32"):
        for i_pad in I_PADS:
            key, sub = jax.random.split(key)
            B = bucket_b(sub, kb, lo, i_pad, R)
            sides = [("rows", rows_gram, _polar_rows)] if i_pad < R else []
            sides.append(("cols", cols_gram, _polar_cols))
            for side, gram, polar in sides:
                G = jax.jit(gram)(B)
                e_ms, _, e_host = device_ms(jnp.linalg.eigh, G, args.reps)
                p_ms, p_eigh, p_host = device_ms(
                    lambda b, polar=polar: polar(b, rel_tol), B, args.reps)
                out["cases"][f"eigh/{side}/i{i_pad}"] = {
                    "n": G.shape[-1], "device_ms": e_ms, "host_ms": e_host}
                out["cases"][f"polar/{side}/i{i_pad}"] = {
                    "device_ms": p_ms, "eigh_ms": p_eigh, "host_ms": p_host}
                print(f"i_pad {i_pad:3d} {side}: eigh [{kb},{G.shape[-1]},"
                      f"{G.shape[-1]}] {e_ms:9.3f} ms (host {e_host:9.3f}); "
                      f"polar {p_ms:9.3f} ms, its eigh {p_eigh:9.3f} ms",
                      flush=True)
            Bs = B[:256]
            Q = np.asarray(jax.jit(polar_gram_eigh)(Bs))
            Bh = np.asarray(Bs)
            padded = ~np.any(Bh != 0, axis=-1)
            out["check"][f"i{i_pad}"] = {
                "max_gap_vs_f64_svd": float(np.abs(Q - pseudo_polar_f64(Bh, rel_tol)).max()),
                "max_abs_padded_rows": float(np.abs(Q[padded]).max()) if padded.any() else 0.0,
            }
            print(f"i_pad {i_pad:3d} check: {out['check'][f'i{i_pad}']}", flush=True)
            lo = i_pad
            del B
    print(json.dumps(out))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
