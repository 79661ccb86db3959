"""Render the §Roofline table from the dry-run JSON (results/dryrun.json).

PARAFAC2 cells appear alongside the LM cells; a cell lowered against the
SCOO format (``dryrun.py --parafac2 --format scoo``) carries the O(nnz)
useful-flops model — its MODEL/HLO column is the sparse path's roofline,
counting only padded triplets instead of the densified CC rectangles — and
renders with a ``/scoo`` shape tag. Cells lowered through a non-default
backend/precision (``--backend auto``, ``--precision bf16``) render with
``/auto`` / ``@bf16`` tags and fill the AI columns: ``AI(hlo)`` is measured
flops per HLO byte accessed, ``AI(model)`` the precision-aware streamed-slab
model (bf16/f16 slabs move 2 bytes per cell, f32 moves 4 — see
launch/dryrun.py).
"""
from __future__ import annotations

import argparse
import json
import os

DEFAULT = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun.json")


def fmt_t(x):
    if x is None:
        return "-"
    if x >= 1.0:
        return f"{x:.2f}s"
    return f"{x*1e3:.2f}ms"


def render(path: str, mesh: str = "pod16x16", markdown: bool = True) -> str:
    with open(path) as f:
        results = json.load(f)
    rows = []
    for key, r in sorted(results.items()):
        if key.startswith("_") or r.get("mesh") != mesh:
            continue
        if "t_compute" not in r:
            continue
        rows.append(r)
    hdr = ("| arch | shape | t_compute | t_memory(live) | t_memory(hlo-ub) | "
           "t_collective | bottleneck | GiB/dev | fits 16G | MODEL/HLO flops | "
           "roofline frac | AI(hlo) | AI(model) |")
    sep = "|" + "---|" * 13
    lines = [hdr, sep]
    for r in rows:
        shape = r["shape"]
        if r.get("format") and r["format"] != "cc":
            shape = f"{shape}/{r['format']}"
        if r.get("backend") and r["backend"] != "jnp":
            shape = f"{shape}/{r['backend']}"
        if r.get("precision") and r["precision"] != "f32":
            shape = f"{shape}@{r['precision']}"

        def ai(key):
            return f"{r[key]:.1f}" if r.get(key) else "-"

        lines.append(
            f"| {r['arch']} | {shape} | {fmt_t(r.get('t_compute'))} | "
            f"{fmt_t(r.get('t_memory'))} | {fmt_t(r.get('t_memory_hlo'))} | "
            f"{fmt_t(r.get('t_collective'))} | {r.get('bottleneck','-')[2:]} | "
            f"{r.get('bytes_per_device',0)/2**30:.2f} | "
            f"{'Y' if r.get('fits_hbm_16g') else 'N'} | "
            f"{r.get('useful_fraction',0):.2f} | "
            f"{r.get('roofline_fraction_compute',0):.2f} | "
            f"{ai('arithmetic_intensity')} | "
            f"{ai('model_arithmetic_intensity')} |")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--path", default=os.path.normpath(DEFAULT))
    ap.add_argument("--mesh", default="pod16x16")
    args = ap.parse_args(argv)
    print(render(args.path, args.mesh))


if __name__ == "__main__":
    main()
