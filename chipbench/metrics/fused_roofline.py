"""Share of their roofline that the four fused Pallas kernels of
``kernels/fused.py`` reach, in %: the least time the chip could take for
their work (the larger of operations over peak FLOP/s and bytes over peak
HBM bytes/s, from the buckets' shapes by ``chipbench.kernel_cost``) over
their summed device time in the trace. Moves ``iter_s``."""

from chipbench import kernel_cost, peaks, trace

# cost key -> the name the kernel's custom call takes in the HLO (its
# wrapper's name in kernels/fused.py)
KERNELS = {"procrustes_b": "fused_procrustes_b",
           "mode1_xkv": "fused_mode1_xkv",
           "mode2": "fused_mode2_compact",
           "ykv": "fused_ykv"}


def is_fused(op):
    return any(op.instr == k or op.instr.startswith(k + ".")
               for k in KERNELS.values())


def bound(ctx):
    """(least seconds per iteration, which bound binds)."""
    pk = peaks.peaks_for(ctx.device_kind)
    flops = nbytes = 0
    for b in ctx.buckets:
        if b.get("route") != "fused":
            continue
        for f, n in kernel_cost.fused_costs(b["kb"], b["i_pad"], b["c_pad"],
                                            ctx.rank).values():
            flops += f
            nbytes += n
    t_flops = flops / pk["flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes else "hbm")


def read(ctx):
    if ctx.trace is None or not ctx.iterations_traced:
        return None
    ns = trace.op_time(ctx.trace, is_fused)
    if ns <= 0:
        return None
    least, _ = bound(ctx)
    return 100.0 * least * ctx.iterations_traced / (ns / 1e9)
