"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Scales are small by default so
the full suite runs in minutes on CPU; pass --full for larger instances.

  PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import argparse

from benchmarks import fig5_rank, fig6_subjects, fig7_variables, mttkrp_micro, table1_synthetic


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="larger problem sizes")
    ap.add_argument("--only", default="", help="comma list: table1,fig5,fig6,fig7,micro")
    ap.add_argument("--backends", default="jnp,auto",
                    help="comma list of MTTKRP backends for the micro rows "
                         "(jnp,auto side by side by default)")
    ap.add_argument("--bench-json", default="",
                    help="write the micro per-mode/backend timings to this "
                         "JSON file (CI artifact)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    if only is None or "micro" in only:
        micro_args = ["--subjects", "4000" if args.full else "1000",
                      "--iters", "3", "--backends", args.backends]
        if args.bench_json:
            micro_args += ["--json", args.bench_json]
        mttkrp_micro.main(micro_args)
    if only is None or "table1" in only:
        table1_synthetic.main(["--scale", "0.004" if args.full else "0.001"])
    if only is None or "fig5" in only:
        fig5_rank.main(["--choa-scale", "0.004" if args.full else "0.001",
                        "--ml-scale", "0.02" if args.full else "0.005"])
    if only is None or "fig6" in only:
        fig6_subjects.main([] if args.full else
                           ["--scales", "0.0005", "0.001", "0.002"])
    if only is None or "fig7" in only:
        fig7_variables.main(["--scale", "0.02" if args.full else "0.005"])


if __name__ == "__main__":
    main()
