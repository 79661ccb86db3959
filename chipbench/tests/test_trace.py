"""The trace reducer, on hand-made ops and on a small trace recorded on a
TPU v5e (``data/choa-r40.cc.xplane.pb``, with the name stacks of its ops in
``data/choa-r40.cc.op_names.json``: a traced window of rank-40 chunks on
1,000 CHOA subjects as CC buckets through the fused kernels)."""
import json
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.metrics import eigh_ms, fused_roofline, sort_ms

DATA = Path(__file__).resolve().parent / "data" / "choa-r40.cc.xplane.pb"
OPS = DATA.with_name("choa-r40.cc.op_names.json")


def _op(name, s, d, **stats):
    return trace.Op(name, float(s), float(d), stats)


def test_busy_is_the_union_of_nested_ops():
    ops = [_op("while", 0, 100), _op("a", 10, 20), _op("b", 25, 10),
           _op("c", 150, 50)]
    tr = trace.Trace(devices={"/device:TPU:0": ops}, host=[], window=(0, 300))
    assert trace.busy_ns(tr) == 150.0
    # leaf ops only: the while loop that wraps a and b is not counted
    assert trace.op_time(tr, lambda o: True) == 80.0
    gaps = trace.idle_gaps(tr)
    assert [g[1] for g in gaps] == pytest.approx([100e-9, 50e-9])


def test_gaps_take_the_host_span():
    ops = [_op("a", 0, 10), _op("b", 50, 10)]
    tr = trace.Trace(devices={"/device:TPU:0": ops},
                     host=[("window", 0, 100), ("sync", 5, 40)],
                     window=(0, 100))
    (a, ta), (b, tb) = trace.idle_gaps(tr)
    assert (a, b) == ("sync", "other")
    assert (ta, tb) == pytest.approx((40e-9, 40e-9))


@pytest.fixture(scope="module")
def recorded():
    if not DATA.exists():
        pytest.fail(f"missing recorded trace {DATA}")
    with open(OPS) as f:
        return trace.load(str(DATA), op_names=json.load(f))


def test_recorded_trace(recorded):
    tr = recorded
    assert list(tr.devices) == ["/device:TPU:0"]
    lo, hi = tr.window
    busy = trace.busy_ns(tr)
    assert 0 < busy <= hi - lo
    assert any(n == "dispatch" for n, _, _ in tr.host)
    assert any(n == "sync" for n, _, _ in tr.host)
    top = trace.top_ops(tr)
    assert 0 < len(top) <= 10 and all(t > 0 for _, t in top)
    gaps = trace.idle_gaps(tr)
    assert all(label in ("dispatch", "sync", "other") for label, _ in gaps)


def test_recorded_trace_names(recorded):
    """eigh, sort and the four fused kernels are found by name."""
    assert trace.op_time(recorded, eigh_ms.is_eigh) > 0
    assert trace.op_time(recorded, sort_ms.is_sort) > 0
    for kernel in fused_roofline.KERNELS.values():
        assert trace.op_time(
            recorded, lambda o: o.instr.startswith(kernel + ".")) > 0
    assert trace.op_time(recorded, fused_roofline.is_fused) > 0


def test_hlo_op_names():
    hlo = (
        '  %sort.33 = (s32[96000]{0}, s32[96000]{0}) sort(s32[96000]{0} %b), '
        'dimensions={0}, is_stable=true, to_apply=%r, metadata={op_name='
        '"jit(chunk)/while/body/argsort" source_file="a.py" source_line=3}\n'
        '  ROOT %custom-call.2 = f32[2]{0} custom-call(), custom_call_target='
        '"Eigh", metadata={op_name="jit(chunk)/eigh"}\n'
        '  %add.1 = f32[] add(f32[] %x, f32[] %y)\n')
    names = trace.hlo_op_names(hlo)
    assert names == {"sort.33": "jit(chunk)/while/body/argsort",
                     "custom-call.2": "jit(chunk)/eigh"}
    op = _op("%custom-call.2 = f32[2]{0} custom-call()", 0, 1,
             op_name=names["custom-call.2"])
    assert op.instr == "custom-call.2"
    assert eigh_ms.is_eigh(op) and not sort_ms.is_sort(op)
    srt = _op("%sort.33 = (s32[96000]{0}) sort()", 0, 1,
              op_name=names["sort.33"])
    assert sort_ms.is_sort(srt) and not eigh_ms.is_eigh(srt)


def test_step_mfu_is_the_iterations_work_over_the_window():
    from chipbench import cell, kernel_cost, peaks
    from chipbench.metrics import step_mfu

    work = {"subjects": 10, "rows": 50, "nnz": 400, "distinct_cols": 80}
    tr = trace.Trace(devices={"/device:TPU:0": [_op("a", 0, 5e8)]}, host=[],
                     window=(0.0, 2e9))
    ctx = cell.Context(trace=tr, spans={}, compile_s=0.0, iterations_traced=4,
                       buckets=[], rank=8, device_kind="TPU v5 lite",
                       work=work)
    flops = kernel_cost.als_iteration_flops(rank=8, **work)
    peak = peaks.peaks_for("TPU v5 lite")["flops_per_s"]
    assert step_mfu.read(ctx) == pytest.approx(100 * flops * 4 / 2.0 / peak)
    assert step_mfu.read(cell.Context(None, {}, 0.0, 0, [], 8,
                                      "TPU v5 lite", work)) is None
