#!/usr/bin/env python3
"""The chip benchmark's one entry point.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell (see BENCHMARK.json and chipbench/cell.py). It needs the
chips the cell asks for, on a TPU: with none, or too few, it exits non-zero
and prints no result. The last line of standard output is the result; the
numbers the check compared, each beside its limit, are the last lines of
standard error and the last key of the result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()    # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    from chipbench import cell as cell_mod

    try:
        cell = cell_mod.load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        log(f"chipbench: cannot load cell {args.workload!r}: {e}")
        return 2

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # cache every program, however quickly it compiles, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"chipbench: JAX finds no device: {e}")
        return 1
    want = int(cell.entry["chips"])
    if devices[0].platform != "tpu" or len(devices) < want:
        log(f"chipbench: cell {cell.name} needs {want} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 1
    log(f"[device] {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache_dir}")

    result = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                          T0, log=log)
    for name, c in result["checks"].items():
        log(f"[limit] {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
