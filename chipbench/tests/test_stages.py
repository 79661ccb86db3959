"""The per-stage readers, on a hand-made trace whose ops carry the stage
scopes of the program (``.../als/<stage>/...``), a while loop that wraps
them, and one op under no stage."""
import pytest

from chipbench import stages, trace
from chipbench.cell import Context
from chipbench.metrics import (eigh_ms, mode1_ms, mode2_ms, mode3_ms, pack_s,
                               polar_ms, procrustes_b_ms, scatter_ms,
                               solve_ms, sort_ms, unscoped_share)

BODY = "jit(chunk)/while/body/closed_call/"
STAGE_READERS = {procrustes_b_ms: ("procrustes_b", "project"),
                 polar_ms: ("polar",), mode1_ms: ("mode1",),
                 mode2_ms: ("mode2",), scatter_ms: ("scatter",),
                 mode3_ms: ("ykv", "mode3"),
                 solve_ms: ("h_solve", "v_solve", "w_solve")}
ITERS = 2


def _op(name, start, dur, op_name):
    return trace.Op(name, float(start), float(dur), {"op_name": op_name})


def _trace(scoped=True):
    """Two iterations, each one op per stage (stage i takes (i + 1) ms),
    inside one while op; then a 5 ms copy under no stage."""
    ops, t = [], 0.0
    for _ in range(ITERS):
        for i, st in enumerate(stages.STAGES):
            name = f"{BODY}als/{st}/dot_general" if scoped else f"{BODY}dot"
            dur = (i + 1) * 1e6
            ops.append(_op(f"fusion.{len(ops)}", t, dur, name))
            t += dur
    # the eigh custom-call and the argsort, where the program puts them
    ops.append(_op("custom-call.1", t, 3e6,
                   f"{BODY}als/polar/jit(eigh)/eigh" if scoped
                   else f"{BODY}jit(eigh)/eigh"))
    ops.append(_op("sort.2", t + 3e6, 1e6,
                   f"{BODY}als/scatter/sort" if scoped else f"{BODY}sort"))
    t += 4e6
    ops.append(_op("while.3", 0.0, t, "jit(chunk)/while"))
    ops.append(_op("copy.4", t, 5e6, ""))
    tr = trace.Trace(devices={"/device:TPU:0": ops}, host=[],
                     window=(0.0, t + 5e6))
    return Context(trace=tr, spans={}, compile_s=0.0,
                   iterations_traced=ITERS, buckets=[], rank=40,
                   device_kind="TPU v5 lite", work={})


def _expected_ms(names):
    ms = sum(stages.STAGES.index(n) + 1 for n in names)
    ms += 3 * ("polar" in names) / ITERS + ("scatter" in names) / ITERS
    return ms


@pytest.mark.parametrize("reader", list(STAGE_READERS),
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_stage_reader(reader):
    assert reader.read(_trace()) == pytest.approx(
        _expected_ms(STAGE_READERS[reader]))


def test_stages_fit_and_unscoped_make_up_the_leaf_time():
    ctx = _trace()
    total_ms = trace.op_time(ctx.trace, lambda o: True) / 1e6 / ITERS
    fit_ms = stages.stage_ms(ctx, "fit")
    unscoped_ms = unscoped_share.read(ctx) / 100.0 * total_ms
    summed = sum(m.read(ctx) for m in STAGE_READERS) + fit_ms + unscoped_ms
    assert summed == pytest.approx(total_ms)
    assert unscoped_ms == pytest.approx(5.0 / ITERS)
    # every stage is read by exactly one metric, or is ``fit``
    named = [s for v in STAGE_READERS.values() for s in v] + ["fit"]
    assert sorted(named) == sorted(stages.STAGES)


def test_stage_metrics_hold_the_outside_in_metrics():
    ctx = _trace()
    assert polar_ms.read(ctx) >= eigh_ms.read(ctx) == pytest.approx(1.5)
    assert scatter_ms.read(ctx) >= sort_ms.read(ctx) == pytest.approx(0.5)


def test_a_program_without_stage_scopes_reads_nothing():
    ctx = _trace(scoped=False)
    for reader in list(STAGE_READERS) + [unscoped_share]:
        assert reader.read(ctx) is None
    assert eigh_ms.read(ctx) == pytest.approx(1.5)


def test_stage_of_needs_the_als_scope():
    op = _op("fusion.1", 0, 1, "jit(f)/procrustes_b/gather")
    assert stages.stage_of(op) is None
    op = _op("fusion.1", 0, 1, f"{BODY}als/mode2/jit(take_along_axis)/gather")
    assert stages.stage_of(op) == "mode2"
    op = _op("fusion.1", 0, 1, f"{BODY}als/mode2x/gather")
    assert stages.stage_of(op) is None


def test_pack_s_reads_the_self_time_of_layout_pack():
    from repro.core import spans

    spans.reset_spans()
    try:
        assert pack_s.read(_trace()) is None
        with spans.span("layout.pack"):
            pass
        with spans.span("layout.pack"):
            pass
        assert pack_s.read(_trace()) == pytest.approx(
            spans.span_totals()["layout.pack"]["self_s"])
    finally:
        spans.reset_spans()
