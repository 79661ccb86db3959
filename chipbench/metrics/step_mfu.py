"""Share of the chip's peak FLOP/s that the whole ALS iteration reaches, in
%: the least operations of an iteration on the cell's cohort
(``chipbench.kernel_cost.als_iteration_flops``) times the iterations traced,
over the traced window's seconds and the peak of ``chipbench.peaks``. It
bounds every kernel's share from above: a kernel taken off the path leaves
its own roofline silent, but not this. Moves ``iter_s``."""

from chipbench import kernel_cost, peaks, trace


def read(ctx):
    tr = ctx.trace
    if (tr is None or not ctx.iterations_traced
            or tr.window[1] <= tr.window[0] or trace.busy_ns(tr) <= 0):
        return None
    flops = kernel_cost.als_iteration_flops(rank=ctx.rank, **ctx.work)
    seconds = (tr.window[1] - tr.window[0]) / 1e9
    peak = peaks.peaks_for(ctx.device_kind)["flops_per_s"]
    return 100.0 * flops * ctx.iterations_traced / seconds / peak
