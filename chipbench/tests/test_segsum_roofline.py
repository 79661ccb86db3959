"""``segsum_roofline``'s byte count and share on a hand-sized cohort, and
``segsum_ms`` on the ops of the ``als/scatter/segsum`` scope alone."""
import numpy as np
import pytest

from chipbench import gen, peaks, trace
from chipbench.cell import Context
from chipbench.metrics import segsum_ms, segsum_roofline

BODY = "jit(chunk)/while/body/closed_call/als/"


def _cohort():
    """Three subjects: columns {0, 2}, {1, 2, 3} and {3}, one of them rated
    twice on two rows; six distinct columns in all."""
    return gen.Cohort(subj=np.array([0, 0, 1, 1, 1, 1, 2], np.int32),
                      row=np.array([0, 1, 0, 0, 1, 2, 0], np.int32),
                      col=np.array([0, 2, 1, 2, 3, 3, 3], np.int32),
                      val=np.ones(7, np.float32),
                      n_rows=np.array([2, 3, 1], np.int32), n_cols=4)


def _ctx(distinct_cols, rank=40, iters=2, segsum_ns=1e6):
    op = lambda name, start, dur, op_name: trace.Op(
        name, float(start), float(dur), {"op_name": op_name})
    ops = [op("custom-call.1", 0, segsum_ns,
              BODY + "scatter/segsum/jit(segmented_cumsum_pallas)/pallas_call"),
           op("sort.2", segsum_ns, 5e5, BODY + "scatter/sort/sort"),
           op("gather.3", segsum_ns + 5e5, 5e5, BODY + "scatter/gather/gather")]
    tr = trace.Trace(devices={"/device:TPU:0": ops}, host=[],
                     window=(0.0, segsum_ns + 1e6))
    return Context(trace=tr, spans={}, compile_s=0.0, iterations_traced=iters,
                   buckets=[], rank=rank, device_kind="TPU v5 lite",
                   work={"distinct_cols": distinct_cols})


def test_least_bytes_read_each_real_compact_row_once():
    cohort = _cohort()
    distinct = int(cohort.distinct_cols().sum())
    assert distinct == 6
    assert segsum_roofline.least_bytes(distinct, 40) == 6 * 40 * 4


def test_share_and_time_read_the_segsum_ops_alone():
    ctx = _ctx(distinct_cols=1_000_000, iters=2, segsum_ns=4e6)
    assert segsum_ms.read(ctx) == pytest.approx(2.0)
    least_s = 1_000_000 * 40 * 4 / peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    assert segsum_roofline.read(ctx) == pytest.approx(
        100.0 * least_s * 2 / 4e-3)


def test_nothing_to_read_without_the_scope():
    ctx = _ctx(distinct_cols=10)
    ctx.trace.devices["/device:TPU:0"].pop(0)
    assert segsum_ms.read(ctx) is None and segsum_roofline.read(ctx) is None
