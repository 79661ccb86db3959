#!/usr/bin/env python3
"""Readings that set a cell's limits: the control and the planted faults.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 [--program]

For each seed, at the cell's own size, prints one JSON line per reading of
the numbers the check compares (``chipbench.cell.compare``):

* ``control``: the reference put in the program's place, computed one
  precision below the configuration's (f32 with three-pass bf16 matmuls,
  for a program that states f32 matmuls at ``highest``);
* ``frozen``: a step that returns its state unchanged;
* ``half``: half of the subjects left out of the step, the fit taken over
  the rest;
* ``altered``: an answer altered where it is produced (H[0, 0] of every
  step's output, by 1%);
* ``program`` (with ``--program``, on the chip): the program itself, driven
  through its first iterations as a run drives it.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from chipbench import cell as cell_mod, gen, reference  # noqa: E402


def as_snaps(factors, iters=None):
    """Reference factors in the program's snapshot form."""
    return [{"H": np.asarray(f.H, np.float64), "V": np.asarray(f.V, np.float64),
             "W": np.asarray(f.W, np.float64), "fits": [float(f.fit)]}
            for f in factors]


def frozen(cohort, v0, steps):
    f = reference.init_factors(v0, cohort.n_subjects, reference.Arith("f64"))
    return as_snaps([f] * steps)


def half(cohort, v0, steps):
    """Every other subject left out: the fit runs on the rest, and the left
    out subjects keep their start rows of W."""
    keep = np.zeros(cohort.n_subjects, bool)
    keep[::2] = True
    m = keep[cohort.subj]
    idx = np.cumsum(keep) - 1
    sub = gen.Cohort(subj=idx[cohort.subj[m]].astype(np.int32),
                     row=cohort.row[m], col=cohort.col[m], val=cohort.val[m],
                     n_rows=cohort.n_rows[keep], n_cols=cohort.n_cols)
    out = []
    for f in reference.run(sub, v0, steps):
        W = np.ones((cohort.n_subjects, f.W.shape[1]))
        W[keep] = f.W
        out.append(reference.Factors(f.H, f.V, W, f.fit))
    return as_snaps(out)


def altered(refs):
    out = as_snaps(refs)
    for s in out:
        s["H"] = s["H"].copy()
        s["H"][0, 0] *= 1.01
    return out


def program(cell, cohort):
    spans = {}
    prog = cell_mod.build_program(cell, cohort, spans)
    snaps, state = [], prog.state
    while sum(len(s["fits"]) for s in snaps) < cell_mod.STEPS:
        state, fits = prog.chunk(state)
        snaps.append(cell_mod.snapshot(state, fits))
    return snaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    cell = cell_mod.load_cell(args.workload)
    if args.program:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    steps = cell_mod.STEPS
    for seed in (int(s) for s in args.seeds.split(",")):
        cohort = gen.generate(cell.cfg, seed)
        v0 = cell_mod.initial_v(cell.cfg)
        t = time.perf_counter()
        refs = reference.run(cohort, v0, steps)
        ref_s = time.perf_counter() - t
        t = time.perf_counter()
        ctl = reference.run(cohort, v0, steps, mode="high")
        ctl_s = time.perf_counter() - t
        readings = {"control": as_snaps(ctl), "frozen": frozen(cohort, v0, steps),
                    "half": half(cohort, v0, steps), "altered": altered(refs)}
        if args.program:
            readings["program"] = program(cell, cohort)
        for kind, snaps in readings.items():
            nums = cell_mod.compare(snaps, refs)
            print(json.dumps({"cell": cell.name, "seed": seed, "kind": kind,
                              "reference_s": ref_s, "control_s": ctl_s,
                              **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
