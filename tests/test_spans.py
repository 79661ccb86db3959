"""The program's instrumentation: the ALS stage scopes in the compiled step,
the stage names of ``dispatch_tally``, and the host spans of the layout and
the fitting loops (``repro.core.spans``)."""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import Parafac2Options, bucketize, init_state
from repro.core import backend as backend_mod
from repro.core.backend import dispatch_tally, get_backend
from repro.core.engine import als_chunk_fn, fit_device
from repro.core.parafac2 import STAGES, als_step
from repro.core.spans import reset_spans, span, span_totals
from repro.launch import decompose
from repro.sparse import random_irregular

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_STAGE = re.compile(r"(?:^|/)als/(" + "|".join(STAGES) + r")(?:/|$)")
# the ops of the step that do the work: what a profile sums per stage
_WORK = ("dot", "gather", "sort", "custom-call", "reduce-window")


def _cohort():
    return random_irregular(n_subjects=24, n_cols=60, max_rows=10,
                            avg_nnz_per_subject=20, seed=1)


def _hlo_ops(text):
    """[(opcode, op_name)] of every instruction of an HLO module's text. An
    instruction of a fused computation without metadata of its own takes
    its fusion's, as a profiler trace names the fusion and not its parts."""
    comp, ops, fusion_name = None, [], {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            meta = _OP_NAME.search(line)
            name = meta.group(1) if meta else ""
            calls = _CALLS.search(line)
            if m.group(2) == "fusion" and calls:
                fusion_name[calls.group(1)] = name
            ops.append((comp, m.group(2), name))
            continue
        c = _COMP.match(line)
        if c:
            comp = c.group(1)
    return [(op, name or fusion_name.get(comp, "")) for comp, op, name in ops]


def _chunk(fmt, backend):
    bt = bucketize(_cohort(), max_buckets=2, format=fmt, dtype=jnp.float32)
    opts = Parafac2Options(rank=4, backend=backend, engine="scan",
                           check_every=2, dtype=jnp.float32)
    state = init_state(bt, opts, 0)
    return jax.jit(als_chunk_fn(opts, 2)).lower(bt, state)


# the SCOO route carries Q as its projection: no op runs under ``project``
@pytest.mark.parametrize("fmt,backend,stages", [
    ("scoo", "auto", tuple(s for s in STAGES if s != "project")),
    ("cc", "jnp", STAGES)])
def test_every_stage_scope_reaches_the_compiled_chunk(fmt, backend, stages):
    ops = _hlo_ops(_chunk(fmt, backend).compile().as_text())
    found = {m.group(1) for _, n in ops for m in [_STAGE.search(n)] if m}
    assert found == set(stages)
    work = [(op, n) for op, n in ops if op in _WORK]
    assert {op for op, _ in work} >= {"dot", "gather", "sort"}
    # The CPU compiler rewrites a cumsum into reduce-windows that carry no
    # metadata, nor does their fusion: those are the only ops without a
    # name, and every op with a name lies in a stage.
    assert all(_STAGE.search(n) or (op == "reduce-window" and not n)
               for op, n in work), [
        (op, n) for op, n in work if not _STAGE.search(n)]


@pytest.mark.parametrize("fmt,route", [
    ("cc", "jnp"), ("cc", "fused"), ("scoo", "scoo")])
def test_dispatch_tally_counts_stages_by_their_names(fmt, route, monkeypatch):
    bt = bucketize(_cohort(), max_buckets=2, format=fmt, dtype=jnp.float32)
    # the fused route: the router's platform test answers as on a TPU, at
    # a rank on the sublane quantum
    monkeypatch.setattr(backend_mod, "_on_tpu", lambda: True)
    rank = 8 if route == "fused" else 4
    opts = Parafac2Options(rank=rank, dtype=jnp.float32,
                           backend="jnp" if route == "jnp" else "auto")
    assert {get_backend(opts.backend).route(b, rank)
            for b in bt.buckets} == {route}
    s0 = init_state(bt, opts, 0)
    with dispatch_tally() as tally:
        jax.eval_shape(lambda s: als_step(bt, s, opts), s0)
    # a key is a stage, or a choice made inside one (``polar.gram_rows``)
    assert tally and {k.split(".")[0] for k in tally} <= set(STAGES)


@pytest.mark.parametrize("fmt,backend", [("cc", "jnp"), ("scoo", "auto")])
def test_scatter_segsum_ticks_once_per_bucket(fmt, backend):
    bt = bucketize(_cohort(), max_buckets=2, format=fmt, dtype=jnp.float32)
    opts = Parafac2Options(rank=4, backend=backend, dtype=jnp.float32)
    s0 = init_state(bt, opts, 0)
    with dispatch_tally() as tally:
        jax.eval_shape(lambda s: als_step(bt, s, opts), s0)
    assert len(bt.buckets) == 2
    assert tally["scatter.segsum"] == len(bt.buckets)


def test_scatter_runs_its_sort_gather_and_segsum_in_their_scopes():
    """The argsort, the permutation gather and the segment sum each lie in
    their own scope under ``als/scatter``, which ``scatter_ms`` sums."""
    ops = _hlo_ops(_chunk("scoo", "auto").compile().as_text())
    subs = {}
    for op, n in ops:
        m = re.search(r"als/scatter/(\w+)/", n)
        if m:
            subs.setdefault(m.group(1), set()).add(op)
    assert {"sort", "gather", "segsum"} <= set(subs)
    assert "sort" in subs["sort"] and "gather" in subs["gather"]


def test_span_totals_count_seconds_and_self_time():
    reset_spans()
    with span("outer"):
        with span("inner"):
            pass
        with span("inner"):
            pass
    tot = span_totals()
    reset_spans()
    assert tot["outer"]["count"] == 1 and tot["inner"]["count"] == 2
    assert tot["inner"]["s"] <= tot["outer"]["s"]
    assert tot["outer"]["self_s"] == pytest.approx(
        tot["outer"]["s"] - tot["inner"]["s"])
    assert span_totals() == {}


@pytest.mark.parametrize("engine,check_every,calls", [
    ("scan", 2, 3), ("scan", 0, 1), ("host", 1, 6)])
def test_fit_loops_record_one_dispatch_and_sync_per_call(engine, check_every,
                                                         calls):
    bt = bucketize(_cohort(), max_buckets=2, dtype=jnp.float32)
    opts = Parafac2Options(rank=3, engine=engine, check_every=check_every,
                           dtype=jnp.float32)
    reset_spans()
    if engine == "host":
        from repro.core import fit
        _, hist = fit(bt, opts, max_iters=6, tol=0.0)
    else:
        _, hist = fit_device(bt, opts, max_iters=6, tol=0.0)
    tot = span_totals()
    reset_spans()
    assert len(hist) == 6
    assert tot["fit.dispatch"]["count"] == tot["fit.sync"]["count"] == calls


def test_build_buckets_records_layout_and_its_children():
    reset_spans()
    decompose.build_buckets(_cohort(), format="auto", engine="scan",
                            max_buckets=2)
    tot = span_totals()
    reset_spans()
    children = ("layout.plan", "layout.route", "layout.pack",
                "layout.to_device")
    assert tot["layout"]["count"] == 1
    assert set(tot) == {"layout", *children}
    assert sum(tot[c]["s"] for c in children) <= tot["layout"]["s"]
    assert tot["layout.pack"]["count"] == tot["layout.to_device"]["count"] == 2


def test_decompose_json_has_a_spans_block(tmp_path):
    path = tmp_path / "out.json"
    out = decompose.main(["--dataset", "synthetic", "--scale", "0.002",
                          "--rank", "3", "--iters", "4", "--tol", "0",
                          "--engine", "scan", "--check-every", "2",
                          "--json", str(path)])
    spans = json.loads(path.read_text())["spans"]
    assert spans == out["spans"]
    assert spans["fit.dispatch"]["count"] == spans["fit.sync"]["count"] == 2
    assert spans["layout"]["count"] == 1
    for rec in spans.values():
        assert set(rec) == {"count", "s", "self_s"}
        assert 0.0 <= rec["self_s"] <= rec["s"] + 1e-9
