"""Share of the traced window in which no op ran on the device: 1 - the
union of the op intervals over the window, in %. Moves ``iter_s``."""

from chipbench import trace


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices or tr.window[1] <= tr.window[0]:
        return None
    return 100.0 * (1.0 - trace.busy_ns(tr) / (tr.window[1] - tr.window[0]))
