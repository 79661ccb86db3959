"""Share of its HBM roofline that the mode-2 segment sum reaches, in %: the
least time the chip could take to move the sum's bytes at its peak HBM
bandwidth (``chipbench.peaks``), over the device time of the ops under
``als/scatter/segsum`` (what ``segsum_ms`` reads). Moves ``iter_s``.

The least bytes of one iteration (``least_bytes``) read the cohort's real
compact rows once: one f32 row of rank R per distinct column of each
subject, whatever implements the sum and however the rows are padded. The
write of M2 (J x R f32 per bucket) is left out, because the reader is not
given J; so the share can only read low."""
from chipbench import peaks, trace
from chipbench.metrics.segsum_ms import is_segsum

F32 = 4


def least_bytes(distinct_cols: int, rank: int) -> int:
    """HBM bytes one iteration's segment sums must move at the least."""
    return distinct_cols * rank * F32


def read(ctx):
    if ctx.trace is None or not ctx.iterations_traced:
        return None
    ns = trace.op_time(ctx.trace, is_segsum)
    if ns <= 0:
        return None
    bw = peaks.peaks_for(ctx.device_kind)["hbm_bytes_per_s"]
    least_s = least_bytes(ctx.work["distinct_cols"], ctx.rank) / bw
    return 100.0 * least_s * ctx.iterations_traced / (ns / 1e9)
