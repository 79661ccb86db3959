import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: the dry-run (and only the dry-run) builds the
# 512-chip production mesh out of host placeholder devices.

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.roofline import (
    TPU_V5E, calibrate_flops_convention, model_flops, roofline_terms)
from repro.configs import SHAPES, applicable_shapes, get_config, list_archs
from repro.core import (
    Bucket, Bucketed, Parafac2Options, Parafac2State, ScatterIndex,
    SparseBucket, als_step)
from repro.dist.sharding import LM_RULES, SP_RULES, axis_rules, param_shardings
from repro.launch.mesh import make_production_mesh
from repro.models import build

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "results", "dryrun.json")


# ---------------------------------------------------------------------------
# sharding builders
# ---------------------------------------------------------------------------

def _axis_size(mesh: Mesh, names) -> int:
    n = 1
    for nm in names:
        if nm in mesh.axis_names:
            n *= mesh.devices.shape[mesh.axis_names.index(nm)]
    return n


def _div(n: int, mesh: Mesh, names) -> bool:
    s = _axis_size(mesh, names)
    return s > 1 and n % s == 0


def _dp_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_shardings(specs: Dict[str, Any], mesh: Mesh):
    dp = _dp_axes(mesh)

    def spec_for(leaf):
        if not hasattr(leaf, "shape") or not leaf.shape:
            return NamedSharding(mesh, P())
        B = leaf.shape[0]
        if _div(B, mesh, dp):
            return NamedSharding(mesh, P(dp + (), *([None] * (len(leaf.shape) - 1))))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(spec_for, specs)


def cache_shardings(cache_shapes, mesh: Mesh):
    dp = _dp_axes(mesh)

    def visit(path, leaf):
        pathstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        shape = leaf.shape
        nd = len(shape)
        parts = [None] * nd
        def set_dim(d, axes):
            parts[d] = axes if len(axes) > 1 else axes[0]
        if "self" in pathstr or "cross" in pathstr:
            # kv cache [...,B,S,KV,hd]
            b_dim, s_dim, kv_dim = nd - 4, nd - 3, nd - 2
            if _div(shape[b_dim], mesh, dp):
                set_dim(b_dim, dp)
            if _div(shape[kv_dim], mesh, ("model",)):
                set_dim(kv_dim, ("model",))
            elif _div(shape[s_dim], mesh, ("model",)):
                set_dim(s_dim, ("model",))
        elif "ssm" in pathstr:
            # [..., B, H, P, N]
            b_dim, h_dim = nd - 4, nd - 3
            if _div(shape[b_dim], mesh, dp):
                set_dim(b_dim, dp)
            if _div(shape[h_dim], mesh, ("model",)):
                set_dim(h_dim, ("model",))
        elif "conv" in pathstr:
            b_dim, c_dim = nd - 3, nd - 1
            if _div(shape[b_dim], mesh, dp):
                set_dim(b_dim, dp)
            if _div(shape[c_dim], mesh, ("model",)):
                set_dim(c_dim, ("model",))
        elif pathstr.endswith("h") or "/h" in pathstr:
            b_dim, w_dim = nd - 2, nd - 1
            if _div(shape[b_dim], mesh, dp):
                set_dim(b_dim, dp)
            if _div(shape[w_dim], mesh, ("model",)):
                set_dim(w_dim, ("model",))
        while parts and parts[-1] is None:
            parts.pop()
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(visit, cache_shapes)


# ---------------------------------------------------------------------------
# one LM cell
# ---------------------------------------------------------------------------

def _lower_compile(cfg, shape_name: str, mesh: Mesh, *, unroll: bool, rules=LM_RULES,
                   microbatches: int = 1):
    """Lower + compile one step function for `cfg` on `mesh`."""
    from repro.dist.sharding import unroll_loops
    import contextlib

    shape = SHAPES[shape_name]
    bundle = build(cfg, microbatches=microbatches)
    ctxs = [axis_rules(rules, mesh), mesh]
    if unroll:
        ctxs.append(unroll_loops())
    with contextlib.ExitStack() as stack:
        for c in ctxs:
            stack.enter_context(c)
        rng_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params_shapes = jax.eval_shape(bundle.init_params, rng_spec)
        p_sh = param_shardings(params_shapes, mesh)
        specs = bundle.input_specs(shape_name)
        t0 = time.perf_counter()
        if shape.kind == "train":
            opt_shapes = jax.eval_shape(bundle.init_opt, params_shapes)
            o_sh = param_shardings(opt_shapes, mesh)
            b_sh = batch_shardings(specs["batch"], mesh)
            lowered = jax.jit(
                bundle.train_step,
                in_shardings=(p_sh, o_sh, b_sh, None),
                out_shardings=(p_sh, o_sh, None),
            ).lower(params_shapes, opt_shapes, specs["batch"], specs["step"])
        elif shape.kind == "prefill":
            b_sh = batch_shardings(specs["batch"], mesh)
            lowered = jax.jit(
                bundle.prefill_step, in_shardings=(p_sh, b_sh),
            ).lower(params_shapes, specs["batch"])
        else:  # decode
            c_sh = cache_shardings(specs["cache"], mesh)
            t_sh = batch_shardings({"t": specs["tokens"]}, mesh)["t"]
            lowered = jax.jit(
                bundle.decode_step,
                in_shardings=(p_sh, c_sh, t_sh, None),
                out_shardings=(None, c_sh),
            ).lower(params_shapes, specs["cache"], specs["tokens"], specs["pos"])
        lower_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
    return compiled, lower_s, compile_s


def _variant_cfg(cfg, units: int):
    """Scale every stacked depth to `units` pattern-groups (affine-cost probe)."""
    import dataclasses as dc
    from repro.models.transformer import default_pattern

    p = len(default_pattern(cfg))
    kw = {"n_layers": units * p, "remat": cfg.remat}
    if cfg.is_encdec:
        kw["encoder_layers"] = units
    return dc.replace(cfg, **kw)


def _raw_costs(compiled, hw) -> Dict[str, float]:
    t = roofline_terms(compiled, hw=hw)
    return {"hlo_flops": t["hlo_flops"], "hlo_bytes": t["hlo_bytes"],
            "collective_bytes": t["collective_bytes"]}


def run_cell(arch: str, shape_name: str, mesh: Mesh, mesh_name: str,
             hw=TPU_V5E, *, roofline: bool = True, sp: bool = False,
             remat_policy: str = "", microbatches: int = 1) -> Dict[str, Any]:
    """One (arch x shape x mesh) cell.

    Full scanned model: compiled for the shardability proof + memory_analysis.
    Roofline terms: XLA cost analysis counts while-loop bodies once, so the
    three terms come from TWO fully-unrolled probe models (1 and 2 pattern-
    groups deep) extrapolated affinely in depth — exact because step cost is
    affine in layer count (intercept = embed/head/loss/optimizer).
    """
    cfg = get_config(arch)
    if remat_policy:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, remat_policy=remat_policy)
    shape = SHAPES[shape_name]
    rules = SP_RULES if sp else LM_RULES
    n_chips = int(np.prod(mesh.devices.shape))
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "sp": sp,
        "kind": shape.kind, "n_chips": n_chips,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    rec["microbatches"] = microbatches
    compiled, rec["lower_s"], rec["compile_s"] = _lower_compile(
        cfg, shape_name, mesh, unroll=False, rules=rules, microbatches=microbatches)
    mem = compiled.memory_analysis()
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        rec[attr] = int(getattr(mem, attr, 0) or 0)
    rec["bytes_per_device"] = (
        rec["argument_size_in_bytes"] + rec["temp_size_in_bytes"])
    rec["fits_hbm_16g"] = rec["bytes_per_device"] <= 16 * 2**30

    if not roofline:
        return rec

    # --- unrolled 1-/2-group probes -> affine extrapolation in depth --------
    from repro.models.transformer import default_pattern

    p = len(default_pattern(cfg))
    units_full = cfg.n_layers / p
    c1, _, s1 = _lower_compile(_variant_cfg(cfg, 1), shape_name, mesh, unroll=True, rules=rules,
                               microbatches=microbatches)
    c2, _, s2 = _lower_compile(_variant_cfg(cfg, 2), shape_name, mesh, unroll=True, rules=rules,
                               microbatches=microbatches)
    rec["probe_compile_s"] = s1 + s2
    r1, r2 = _raw_costs(c1, hw), _raw_costs(c2, hw)
    extrap = {}
    for k in r1:
        per_unit = r2[k] - r1[k]
        extrap[k] = max(r1[k] + (units_full - 1.0) * per_unit, 0.0)
        # the microbatch accumulation scan is a while loop: its body is
        # counted once by cost analysis -> scale to the full step.
        extrap[k] *= max(microbatches, 1)
    rec.update(extrap)
    rec["t_compute"] = extrap["hlo_flops"] / hw.peak_flops
    # memory term, two bounds: HLO bytes-accessed is pre-fusion (upper bound);
    # live bytes (params+opt+cache+activations touched once) is the lower.
    rec["t_memory_hlo"] = extrap["hlo_bytes"] / hw.hbm_bw
    rec["t_memory"] = rec["bytes_per_device"] / hw.hbm_bw
    rec["t_collective"] = extrap["collective_bytes"] / hw.link_bw
    dominant = max(("t_compute", "t_memory", "t_collective"), key=lambda k: rec[k])
    rec["bottleneck"] = dominant
    tmax = rec[dominant]
    rec["roofline_fraction_compute"] = rec["t_compute"] / tmax if tmax > 0 else 0.0
    mf = model_flops(cfg, shape, per_device=True, n_chips=n_chips)
    rec["model_flops_per_device"] = mf
    rec["useful_fraction"] = mf / extrap["hlo_flops"] if extrap["hlo_flops"] else 0.0
    return rec


# ---------------------------------------------------------------------------
# PARAFAC2 cells (the paper's workload on the production mesh)
# ---------------------------------------------------------------------------

def parafac2_specs(K: int, J: int, R: int, geometry, dp: int,
                   opts: Optional[Parafac2Options] = None,
                   format: str = "cc"):
    """ShapeDtypeStruct Bucketed + state for a dataset geometry
    [(Kb, I_pad, C_pad, N_pad)...]; Kb rounded up to the DP shard count.
    ``format="scoo"`` lowers the O(nnz) flat-COO layout (N_pad triplets per
    subject) instead of the densified CC rectangle. ADMM-routed constraints
    in ``opts`` add their carried ``(Z, U)`` dual pairs to the state's aux
    pytree (bucketed-W aux follows the bucket shapes)."""
    from repro.core.parafac2 import constraints_for

    f32 = jnp.float32
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    K = ((K + dp - 1) // dp) * dp   # pad subject count to the DP shard count
    bf16 = jnp.bfloat16
    buckets = []
    for kb, ip, cp, npad in geometry:
        kb = ((kb + dp - 1) // dp) * dp
        # the scatter index per DP shard: only shapes are known here, so
        # every compact slot of the shard counts as a real row
        n = kb // dp * cp
        common = dict(
            cols=sds((kb, cp), i32),
            col_mask=sds((kb, cp), f32),
            subject_ids=sds((kb,), i32),
            subject_mask=sds((kb,), f32),
            row_counts=sds((kb,), i32),
            scatter=ScatterIndex(perm=sds((dp, n), i32),
                                 starts=sds((dp * n,), jnp.bool_),
                                 ends=sds((dp, J), i32)),
        )
        if format == "scoo":
            buckets.append(SparseBucket(
                vals=sds((kb, npad), bf16),   # bf16 triplet values, f32 accum
                rows=sds((kb, npad), i32),
                lcols=sds((kb, npad), i32),
                row_ends=sds((kb, ip), i32),
                cperm=sds((kb, npad), i32),
                col_ends=sds((kb, cp), i32),
                nnz_counts=sds((kb,), i32),
                n_rows_pad=ip,
                **common,
            ))
        else:
            buckets.append(Bucket(
                vals=sds((kb, ip, cp), bf16),   # bf16 slice values, f32 accum
                **common,
            ))
    data = Bucketed(buckets=buckets, n_subjects=K, n_cols=J, norm_sq=1.0)
    cons = constraints_for(opts) if opts is not None else None

    def aux_for(mode, shape):
        if cons is None or not cons[mode].admm:
            return ()
        return (sds(shape, f32), sds(shape, f32))    # (Z, U) dual pair

    aux = {"h": aux_for("h", (R, R)), "v": aux_for("v", (J, R)),
           "w": ([aux_for("w", (b.vals.shape[0], R)) for b in buckets]
                 if cons is not None and cons["w"].admm else ())}
    state = Parafac2State(
        H=sds((R, R), f32), V=sds((J, R), f32),
        W=tuple(sds((b.vals.shape[0], R), f32) for b in buckets),  # bucketed W
        fit=sds((), f32), aux=aux)
    return data, state


def parafac2_shardings(data: Bucketed, state, mesh: Mesh, *, wide: bool = True):
    """wide=True: subjects shard over EVERY mesh axis (pod x data x model) —
    the paper's workload has no tensor-parallel dimension, so leaving "model"
    idle wastes 16x memory/compute capacity (§Perf 'subject-wide sharding')."""
    axes = tuple(mesh.axis_names) if wide else _dp_axes(mesh)
    # every bucket leaf (CC or SCOO) is Kb-leading -> split over the subject
    # axes; tree_map keeps the Bucket/SparseBucket pytree structure intact
    kb = NamedSharding(mesh, P(axes))
    d_sh = jax.tree_util.tree_map(lambda _: kb, data)
    rep = NamedSharding(mesh, P())
    subj = NamedSharding(mesh, P(axes))
    # ADMM aux shardings follow the owning factor: bucketed-W duals split
    # over the subject axes, H/V duals replicate
    aux_sh = {k: jax.tree_util.tree_map(lambda _: subj if k == "w" else rep,
                                        sub)
              for k, sub in state.aux.items()} if isinstance(state.aux, dict) \
        else jax.tree_util.tree_map(lambda _: rep, state.aux)
    s_sh = Parafac2State(
        H=rep,
        V=rep,                             # replicated-V mode (J moderate)
        W=tuple(subj for _ in data.buckets),
        fit=rep, aux=aux_sh)
    return d_sh, s_sh


PARAFAC2_CELLS = {
    # name: (K, J, R, [(Kb_per_bucket, I_pad, C_pad, N_pad)...]) — CHOA /
    # synth-500M. N_pad is the SCOO per-subject triplet pad (≈4-8 nonzeros
    # per observation row — EHR-like ~1-3% intra-slice density); the CC
    # lowering ignores it.
    "parafac2-choa-r40": (464_900, 1_328, 40,
                          [(116_225, 32, 64, 128), (116_225, 64, 96, 256),
                           (116_225, 96, 128, 384), (116_225, 168, 256, 672)]),
    "parafac2-synth500m-r40": (1_000_000, 5_000, 40,
                               [(250_000, 48, 256, 384), (250_000, 64, 384, 512),
                                (250_000, 80, 512, 640), (250_000, 104, 640, 832)]),
}


def run_parafac2_cell(name: str, mesh: Mesh, mesh_name: str, hw=TPU_V5E,
                      backend: str = "jnp", engine: str = "host",
                      check_every: int = 8, constraint: str = "",
                      format: str = "cc", compress: str = "none",
                      precision: str = "f32"):
    """Lower + compile one PARAFAC2 cell. ``engine`` selects what one
    dispatch is: a single als_step ("host" — today's per-iteration loop), a
    check_every-iteration lax.scan chunk under GSPMD ("scan"), or the same
    chunk wrapped in shard_map over the subjects axes ("mesh") — see
    repro.core.engine. ``format`` picks the device layout the cell lowers
    against: "cc" (densified rectangles) or "scoo" (O(nnz) flat COO — the
    sparse path's production program shape + roofline). ``constraint`` is
    the driver spec syntax ("v=nonneg_admm,w=nonneg_admm"); ADMM specs put
    the carried dual pytree into the lowered state so the production program
    shape includes the AO-ADMM solver state. ``compress`` is a
    repro.core.compress spec ("rsvd[:r[:p[:q]]]"): the cell then lowers the
    CORE geometry the compressed ALS iterates over — every bucket's row pad
    clamped to the sketch dimension S = r + p, always CC (cores are dense) —
    i.e. the program shape whose per-iteration roofline the DPar2-style
    stage buys."""
    from repro.core import engine as als_engine
    from repro.core.compress import parse_preprocess_spec
    from repro.core.constraints import parse_constraint_arg

    K, J, R, geom = PARAFAC2_CELLS[name]
    pp = parse_preprocess_spec(compress)
    if not pp.identity:
        S = pp.sketch_dim(R)
        # core ALS geometry: [Kb, min(I_pad, S), C_pad]; cores are dense CC
        geom = [(kb, min(ip, S), cp, npad) for kb, ip, cp, npad in geom]
        format = "cc"
    n_chips = int(np.prod(mesh.devices.shape))
    rec = {"arch": name + ("+scoo" if format == "scoo" else "")
           + ("+rsvd" if not pp.identity else ""),
           "shape": "als_step", "mesh": mesh_name,
           "kind": "parafac2", "n_chips": n_chips, "params": 0,
           "active_params": 0, "backend": backend, "engine": engine,
           "format": format, "compress": pp.spec, "precision": precision}
    specs = (parse_constraint_arg(constraint) if constraint
             else {"v": "nonneg", "w": "nonneg"})
    rec["constraints"] = {m: s for m, s in specs.items()}
    opts = Parafac2Options(rank=R, constraints=specs, w_layout="bucketed",
                           backend=backend, precision=precision,
                           engine=engine, check_every=check_every)
    wide = rec.get("wide", True)
    dp = _axis_size(mesh, tuple(mesh.axis_names) if wide else ("pod", "data"))
    data, state = parafac2_specs(K, J, R, geom, dp, opts, format=format)
    d_sh, s_sh = parafac2_shardings(data, state, mesh, wide=wide)
    t0 = time.perf_counter()
    with axis_rules(LM_RULES, mesh), mesh:
        if engine == "host":
            step = jax.jit(
                lambda d, s: als_step(d, s, opts),
                in_shardings=(d_sh, s_sh), out_shardings=s_sh)
        elif engine == "scan":
            rec["check_every"] = check_every
            step = jax.jit(
                als_engine.als_chunk_fn(opts, check_every),
                in_shardings=(d_sh, s_sh),
                out_shardings=(s_sh, NamedSharding(mesh, P())))
        elif engine == "mesh":
            rec["check_every"] = check_every
            # shard_map defines the layouts itself; no jit in_shardings
            step = jax.jit(als_engine.mesh_wrap(
                als_engine.als_chunk_fn(opts, check_every), data, state,
                mesh=mesh))
        else:
            raise ValueError(engine)
        lowered = step.lower(data, state)
        rec["lower_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            rec[attr] = int(getattr(mem, attr, 0) or 0)
        rec["bytes_per_device"] = (
            rec["argument_size_in_bytes"] + rec["temp_size_in_bytes"])
        rec["fits_hbm_16g"] = rec["bytes_per_device"] <= 16 * 2**30
        terms = roofline_terms(compiled, hw=hw)
        rec.update(terms)
        rec["t_memory_hlo"] = terms["t_memory"]
        rec["t_memory"] = rec["bytes_per_device"] / hw.hbm_bw
        # flops per HLO byte accessed — the fused route's whole point is
        # raising this (Y_k never round-trips HBM between stages; bf16/f16
        # precision additionally halves every streamed slab byte)
        rec["arithmetic_intensity"] = (
            terms["hlo_flops"] / terms["hlo_bytes"]
            if terms.get("hlo_bytes") else 0.0)
        dominant = max(("t_compute", "t_memory", "t_collective"),
                       key=lambda k: rec[k])
        rec["bottleneck"] = dominant
        # useful work: the SPARTan flop count (Procrustes + 3 MTTKRPs +
        # grams). CC pays the densified rectangle; SCOO's O(nnz) roofline
        # counts only the padded triplets (the benchmarks/roofline_report.py
        # entry for the sparse path).
        if format == "scoo":
            cells = sum(kb * npad for kb, ip, cp, npad in geom)
            # the padded triplet count IS the lowered nonzero capacity — the
            # "100M+-nnz geometry on a pod mesh" claim in one number
            rec["padded_nnz"] = int(cells)
        else:
            cells = sum(kb * ip * cp for kb, ip, cp, npad in geom)
        useful = (6.0 * cells * R + 10.0 * K * R * R) / n_chips
        rec["model_flops_per_device"] = useful
        rec["useful_fraction"] = useful / terms["hlo_flops"] if terms["hlo_flops"] else 0.0
        # model-side streamed-slab traffic per iteration, precision-aware
        # (bf16/f16 slabs move 2 bytes/cell, f32 moves 4). The jnp route
        # (every CC bucket off a TPU, where this lowers) reads the vals slab
        # twice (X_k V, projection) and round-trips the compact Yc three
        # more times (write + mode-2 + ykv reads).
        if format != "scoo":
            val_b = 2                       # parafac2_specs lowers bf16 vals
            slab_b = 2 if precision in ("bf16", "f16") else 4
            yc_cells = sum(kb * R * cp for kb, ip, cp, npad in geom)
            streamed = 2.0 * cells * val_b + 3.0 * yc_cells * slab_b
            rec["model_streamed_bytes_per_device"] = streamed / n_chips
            rec["model_arithmetic_intensity"] = (
                useful / (streamed / n_chips) if streamed else 0.0)
    return rec


# ---------------------------------------------------------------------------
# sweep driver with JSON result cache
# ---------------------------------------------------------------------------

def load_results(path: str) -> Dict[str, Any]:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_results(path: str, results: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, default=float)
    os.replace(tmp, path)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=os.path.normpath(RESULTS_PATH))
    ap.add_argument("--parafac2", action="store_true", help="also run paper-workload cells")
    ap.add_argument("--backend", default="jnp", choices=["jnp", "auto"],
                    help="MTTKRP backend for the PARAFAC2 cells (on the host "
                         "placeholder mesh 'auto' runs SCOO buckets on their "
                         "segment sums and CC buckets on jnp)")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "f16"],
                    help="compute precision for the PARAFAC2 cells: bf16/f16 "
                         "stage the streamed slab operands half-width with "
                         "f32 accumulation — halves the roofline's streamed "
                         "bytes (repro.kernels.common)")
    ap.add_argument("--format", default="cc", choices=["cc", "scoo"],
                    help="device data format the PARAFAC2 cells lower "
                         "against: cc (densified rectangles) or scoo (the "
                         "O(nnz) flat-COO path; N_pad from PARAFAC2_CELLS)")
    ap.add_argument("--engine", default="host", choices=["host", "scan", "mesh"],
                    help="ALS execution engine for the PARAFAC2 cells: what "
                         "one lowered dispatch is (see repro.core.engine)")
    ap.add_argument("--check-every", type=int, default=8,
                    help="scan-chunk length for --engine scan/mesh")
    ap.add_argument("--compress", default="none",
                    help="preprocessing spec for the PARAFAC2 cells "
                         "(repro.core.compress, e.g. 'rsvd:80:8:1'): lowers "
                         "the compressed CORE geometry (row pads clamped to "
                         "the sketch dim, CC format) instead of the full "
                         "data — the program shape the core ALS iterates on")
    ap.add_argument("--constraint", default="",
                    help="constraint spec for the PARAFAC2 cells "
                         "(driver syntax, e.g. 'v=nonneg_admm,w=nonneg_admm'); "
                         "empty = legacy nonneg. The sweep ALWAYS additionally "
                         "lowers one AO-ADMM-constrained cell per mesh.")
    ap.add_argument("--sp", action="store_true", help="sequence-parallel residual stream (hillclimb)")
    ap.add_argument("--remat-policy", default="", help="override cfg.remat_policy (hillclimb)")
    ap.add_argument("--microbatches", type=int, default=1, help="gradient accumulation (train cells)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    archs = list_archs() if args.arch == "all" else [args.arch]
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("pods2x16x16", make_production_mesh(multi_pod=True)))

    results = load_results(args.out)
    from repro.launch.summary import run_summary
    meta = results.setdefault("_meta", {})
    meta["flops_convention"] = calibrate_flops_convention(meshes[0][1])
    # the unified driver schema block (repro.launch.summary); cells carry
    # their own resolved knobs, so the options block here stays empty
    meta.update(run_summary("dryrun", None))

    failures = []
    for mesh_name, mesh in meshes:
        for arch in archs:
            cfg = get_config(arch)
            shapes = applicable_shapes(cfg) if args.shape == "all" else [args.shape]
            for shape_name in shapes:
                key = (f"{arch}|{shape_name}|{mesh_name}" + ("+sp" if args.sp else "")
                       + (f"+{args.remat_policy}" if args.remat_policy else "")
                       + (f"+mb{args.microbatches}" if args.microbatches > 1 else ""))
                if key in results and not args.force:
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    rec = run_cell(arch, shape_name, mesh, mesh_name,
                                   roofline=(mesh_name == "pod16x16"), sp=args.sp,
                                   remat_policy=args.remat_policy,
                                   microbatches=args.microbatches)
                    results[key] = rec
                    save_results(args.out, results)
                    detail = (f"t_comp={rec['t_compute']*1e3:.2f}ms "
                              f"t_mem={rec['t_memory']*1e3:.2f}ms "
                              f"t_coll={rec['t_collective']*1e3:.2f}ms "
                              f"bottleneck={rec['bottleneck']} "
                              if "t_compute" in rec else "")
                    print(f"[dryrun] {key}: OK {detail}"
                          f"bytes/dev={rec['bytes_per_device']/2**30:.2f}GiB "
                          f"(compile {rec['compile_s']:.0f}s)", flush=True)
                except Exception as e:  # a failing cell is a bug to fix
                    failures.append((key, repr(e)))
                    print(f"[dryrun] {key}: FAIL {e}", flush=True)
                    if not args.quiet:
                        traceback.print_exc()
        if args.parafac2:
            # every cell with the requested constraint, plus at least one
            # AO-ADMM-constrained cell per mesh (the carried dual state must
            # lower + compile on the production meshes, not just on CPU)
            admm_spec = "v=nonneg_admm,w=nonneg_admm"
            cells = [(cell, args.constraint, "") for cell in PARAFAC2_CELLS]
            if args.constraint != admm_spec:
                cells.append((next(iter(PARAFAC2_CELLS)), admm_spec, "+admm"))
            for cell, cons, tag in cells:
                key = (f"{cell}|als_step|{mesh_name}"
                       + (f"+{args.format}" if args.format != "cc" else "")
                       + (f"+{args.backend}" if args.backend != "jnp" else "")
                       + (f"+{args.precision}" if args.precision != "f32"
                          else "")
                       + (f"+{args.engine}" if args.engine != "host" else "")
                       + (f"+[{cons}]" if cons else "")
                       + (f"+[{args.compress}]" if args.compress != "none"
                          else "")
                       + tag)
                if key in results and not args.force:
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    rec = run_parafac2_cell(cell, mesh, mesh_name,
                                            backend=args.backend,
                                            engine=args.engine,
                                            check_every=args.check_every,
                                            constraint=cons,
                                            format=args.format,
                                            compress=args.compress,
                                            precision=args.precision)
                    results[key] = rec
                    save_results(args.out, results)
                    print(f"[dryrun] {key}: OK bottleneck={rec['bottleneck']} "
                          f"(compile {rec['compile_s']:.0f}s)", flush=True)
                except Exception as e:
                    failures.append((key, repr(e)))
                    print(f"[dryrun] {key}: FAIL {e}", flush=True)
                    if not args.quiet:
                        traceback.print_exc()

    n_ok = len([k for k in results if not k.startswith("_")])
    print(f"[dryrun] done: {n_ok} cells recorded, {len(failures)} failures")
    for k, e in failures:
        print(f"  FAIL {k}: {e}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
