"""Device time and accuracy of the mode-2 segment sum, by implementation.

``core/spartan.py::mode2_scatter`` sums each bucket's compact mode-2 rows
A [Kb, C, R] into M2 [J, R] by column id: a gather of the real rows in the
column order the layout fixed once (the bucket's ``ScatterIndex``), and a
segment sum over the gathered rows.
This times, on a TPU, at the bucket shapes of the benchmark cells (the
``choa-r40`` and ``movielens-r40`` cohorts as ``chipbench`` generates and
buckets them, ``--format auto``), each of these segment sums:

* ``cumsum``: one running f32 ``cumsum`` over all sorted rows, read at the
  segment ends and differenced (the sum before the per-segment one);
* ``pallas``: ``kernels/segsum.py``'s segmented prefix kernel, read at the
  segment ends (``pallas_t256``, ``pallas_b4096``: other tile and block
  sizes);
* ``segment_sum``: ``jax.ops.segment_sum`` over the sorted rows.

For each it reports the ms of the whole scatter (gather, sum; host
clock around ``block_until_ready``), the compiled program's temporary
bytes, and the
relative error of M2 against a float64 sum on the host: over all rows, and
the worst row among the columns with at most 5 terms. A's real entries are
N(0, 1); its padded rows are zero. Needs a TPU; run from the root of the
checkout:

    PYTHONPATH=src python -m benchmarks.segsum_micro --json segsum_micro.json
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import segsum

CELLS = ("choa-r40", "movielens-r40")


def _cumsum_diff(g, ends):
    cs = jnp.concatenate([jnp.zeros((1, g.shape[1]), g.dtype),
                          jnp.cumsum(g, 0)], 0)
    return jnp.diff(cs[ends], axis=0, prepend=jnp.zeros((1, g.shape[1]),
                                                        g.dtype))


def _at_ends(y, ends):
    begins = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    return jnp.where((ends > begins)[:, None], y[jnp.maximum(ends - 1, 0)],
                     0.0)


def _ids(g, ends):
    """Each sorted row's column; rows past the last column get J."""
    return jnp.searchsorted(ends, jnp.arange(g.shape[0], dtype=ends.dtype),
                            side="right")


def _sums(J):
    """{name: (sorted rows g, starts, ends) -> M2}."""
    return {
        "cumsum": lambda g, starts, ends: _cumsum_diff(g, ends),
        "pallas": lambda g, starts, ends: _at_ends(
            segsum.segmented_cumsum_pallas(g, starts), ends),
        "pallas_t256": lambda g, starts, ends: _at_ends(
            segsum.segmented_cumsum_pallas(g, starts, tile=256), ends),
        "pallas_b4096": lambda g, starts, ends: _at_ends(
            segsum.segmented_cumsum_pallas(g, starts, block=4096), ends),
        "segment_sum": lambda g, starts, ends: jax.ops.segment_sum(
            g, _ids(g, ends), num_segments=J, indices_are_sorted=True),
    }


def _sorted(A, index):
    """The bucket's real rows in its index's column order (one shard)."""
    g = A.reshape(-1, A.shape[-1])[index.perm[0]]
    return g, index.starts, index.ends[0]


def timed(fn, args, reps):
    compiled = jax.jit(fn).lower(*args).compile()
    out = jax.block_until_ready(compiled(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ts.append(time.perf_counter() - t0)
    ma = compiled.memory_analysis()
    return out, statistics.median(ts) * 1e3, int(ma.temp_size_in_bytes)


def buckets_of(cfg_name: str, seed: int):
    from chipbench import cell as cell_mod, gen
    from repro.launch import decompose

    with open(f"chipbench/configs/{cfg_name}.json") as f:
        cfg = json.load(f)
    cohort = gen.generate(cfg, seed)
    bt, stats, _ = decompose.build_buckets(
        cell_mod.to_program(cohort), format="auto", engine="scan",
        max_buckets=4)
    return cfg, bt, stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--json", default=None)
    ap.add_argument("--only", default=None,
                    help="comma-separated implementation names")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated <config>:<bucket index>")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"segsum_micro: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    out = {"device": dev.device_kind, "cases": {}}
    rng = np.random.default_rng(args.seed)
    for name in CELLS:
        if args.buckets and f"{name}:" not in args.buckets:
            continue
        cfg, bt, stats = buckets_of(name, args.seed)
        J, R = cfg["n_cols"], cfg["rank"]
        for bi, (b, st) in enumerate(zip(bt.buckets, stats)):
            if args.buckets and f"{name}:{bi}" not in args.buckets.split(","):
                continue
            mask = np.asarray(b.col_mask) * np.asarray(b.subject_mask)[:, None]
            A = (rng.standard_normal(mask.shape + (R,)).astype(np.float32)
                 * mask[..., None].astype(np.float32))
            cols_h = np.asarray(b.cols).reshape(-1)
            real = mask.reshape(-1) > 0
            ref = np.zeros((J, R))
            np.add.at(ref, cols_h[real], A.reshape(-1, R)[real].astype(np.float64))
            terms = np.bincount(cols_h[real], minlength=J)
            rare = (terms > 0) & (terms <= 5)
            A_d = jnp.asarray(A)
            for impl, fn in _sums(J).items():
                if args.only and impl not in args.only.split(","):
                    continue
                key = f"{name}/b{bi}/{impl}"
                try:
                    M2, full_ms, temp = timed(
                        lambda a, ix, fn=fn: fn(*_sorted(a, ix)),
                        (A_d, b.scatter), args.reps)
                except Exception as e:     # a refused variant is a reading
                    out["cases"][key] = {"error": repr(e)[:400]}
                    print(f"{key}: {e!r}"[:400], flush=True)
                    continue
                M2 = np.asarray(M2, np.float64)
                err = np.linalg.norm(M2 - ref) / np.linalg.norm(ref)
                row = (np.linalg.norm(M2 - ref, axis=1)
                       / np.maximum(np.linalg.norm(ref, axis=1), 1e-300))
                rec = {"rows": int(b.scatter.perm.size), "J": J, "kb": st.get("n_subjects"),
                       "c_pad": st["c_pad"], "full_ms": full_ms,
                       "temp_bytes": temp, "rel_err": float(err),
                       "rare_rel_err_max": float(row[rare].max()) if rare.any() else None,
                       "empty_max_abs": float(np.abs(M2[terms == 0]).max())
                       if (terms == 0).any() else 0.0}
                out["cases"][key] = rec
                print(f"{key}: rows {rec['rows']} full {full_ms:9.3f} ms, "
                      f"temp {temp / 2**30:.3f} GiB, "
                      f"rel err {err:.3e}, rare rows {rec['rare_rel_err_max']}",
                      flush=True)
        del bt
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
