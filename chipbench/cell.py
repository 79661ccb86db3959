"""One run of one benchmark cell: set-up, measured window, check.

A cell (an entry of ``workloads`` in BENCHMARK.json) names a configuration
(``configs/<name>.json``: the cohort's sizes and the fit's rank and
constraints) and a traffic mix (``traffic/<name>.json``: the device format,
backend, engine and bucket count the fit runs with). Its limits for the
check are in ``limits/<cell>.json``. Nothing here names a cell.

Set-up builds what ``repro.launch.decompose`` builds: the cohort from the
seed, the layout from ``build_buckets``, ``Parafac2Options``,
``init_state`` and the compiled chunk of ``make_als_chunk``. It drives that
chunk through the first ``STEPS`` iterations (the first compiles), keeping
each iteration's factors on the host for the check, and reads the compiled
chunk's ``memory_analysis()``. The window then dispatches the same chunk
back to back, each dispatch ending in the one host sync that
``engine.fit_device`` makes, until ``seconds`` have passed. Afterwards the
plain reference follows the first ``STEPS`` iterations from the same start
and the check compares them.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from chipbench import gen, reference, trace as trace_mod  # noqa: E402
from chipbench.compile_clock import CompileClock  # noqa: E402

STEPS = 3          # iterations the check compares
GIB = 2.0 ** 30


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    entry: dict        # the BENCHMARK.json workload entry
    cfg: dict          # configuration
    traffic: dict      # traffic mix
    limits: dict       # {number: limit}
    bench: dict        # the whole BENCHMARK.json


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(entries)}")
    entry = entries[name]
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    with open(root / conf["file"]) as f:
        cfg = json.load(f)
    with open(HERE / "traffic" / f"{entry['traffic']}.json") as f:
        traffic = json.load(f)
    with open(HERE / "limits" / f"{name}.json") as f:
        limits = json.load(f)["limits"]
    return Cell(name, entry, cfg, traffic, limits, bench)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def to_program(cohort: "gen.Cohort"):
    """The cohort as the program's host-side ``IrregularCOO``."""
    from repro.sparse.coo import IrregularCOO, SubjectCOO

    offs = cohort.subject_offsets()
    J = cohort.n_cols
    subs = [SubjectCOO(rows=cohort.row[a:b], cols=cohort.col[a:b],
                       vals=cohort.val[a:b], n_rows=int(n), n_cols=J)
            for a, b, n in zip(offs[:-1], offs[1:], cohort.n_rows)]
    return IrregularCOO(subjects=subs, n_cols=J)


def init_seed(cfg: dict) -> int:
    """The seed ``init_state`` gets: the configuration's own, like the rest
    of what the fit computes (``--seed`` draws only the subjects' order; see
    ``chipbench.gen``). Its PRNGKey keeps 32 bits."""
    return int(cfg["geometry"]["seed"]) % (2 ** 31 - 1)


def initial_v(cfg: dict) -> np.ndarray:
    """The start V the program draws, drawn again by the public PRNG."""
    import jax

    key = jax.random.PRNGKey(init_seed(cfg))
    return np.asarray(jax.random.uniform(
        key, (cfg["n_cols"], cfg["rank"]), np.float32), dtype=np.float64)


@dataclasses.dataclass
class Program:
    bt: object
    opts: object
    state: object
    compiled: object          # the chunk, compiled for this device
    buckets: List[dict]

    def chunk(self, state):
        """One dispatch of the compiled chunk: ``state -> (state, fits)``."""
        return self.compiled(self.bt, state)


def build_program(cell: Cell, cohort, spans: Dict[str, float]) -> Program:
    """The fit as ``repro.launch.decompose`` sets it up, with the chunk of
    ``repro.core.engine.make_als_chunk`` (the same ``als_chunk_fn`` under
    the same ``jax.jit`` and donation) compiled ahead of time, once: the
    window runs that executable, and its ``memory_analysis()`` and HLO come
    from it without a second trace or cache read."""
    import jax
    from repro.core import Parafac2Options, init_state
    from repro.core.backend import get_backend
    from repro.core.engine import als_chunk_fn
    from repro.launch import decompose

    tr, cfg = cell.traffic, cell.cfg
    if tr["engine"] != "scan":
        raise ValueError(f"engine {tr['engine']!r}: the harness drives the "
                         f"one-device scan engine")
    t = time.perf_counter()
    data = to_program(cohort)
    spans["to_program_s"] = time.perf_counter() - t
    t = time.perf_counter()
    bt, stats, _ = decompose.build_buckets(
        data, format=tr["format"], engine=tr["engine"],
        max_buckets=tr["buckets"])
    spans["bucketize_s"] = time.perf_counter() - t
    del data
    opts = Parafac2Options(rank=cfg["rank"], constraints=cfg["constraints"],
                           backend=tr["backend"], engine=tr["engine"],
                           check_every=cfg["check_every"])
    be = get_backend(opts.backend, opts.precision)
    for rec, b in zip(stats, bt.buckets):
        rec["route"] = be.route(b, opts.rank)
        rec["kb"] = int(b.kb)
    t = time.perf_counter()
    state = init_state(bt, opts, init_seed(cfg))
    spans["init_state_s"] = time.perf_counter() - t
    t = time.perf_counter()
    donate = (1,) if jax.default_backend() != "cpu" else ()
    compiled = jax.jit(als_chunk_fn(opts, opts.check_every),
                       donate_argnums=donate).lower(bt, state).compile()
    spans["compile_chunk_s"] = time.perf_counter() - t
    return Program(bt, opts, state, compiled, stats)


def chunk_memory(prog: Program) -> dict:
    """``memory_analysis()`` of the compiled chunk the window drives."""
    ma = prog.compiled.memory_analysis()
    out = {k: int(getattr(ma, k, 0) or 0) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["peak"] = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                   + out["temp_size_in_bytes"] - out["alias_size_in_bytes"])
    return out


def snapshot(state, fits) -> dict:
    return {"H": np.array(state.H, dtype=np.float64),
            "V": np.array(state.V, dtype=np.float64),
            "W": np.array(state.W, dtype=np.float64),
            "fits": [float(f) for f in np.asarray(fits)]}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def compare(snaps: List[dict], refs: List["reference.Factors"]) -> dict:
    """The numbers the check compares, with the leaf each comes from.

    ``fit1_gap``: the gap between the program's fit and the reference's
    after the first iteration. ``fit_gap``: the widest such gap over the
    compared iterations. ``h_gap``: the relative Frobenius gap
    ||H_p - H_r|| / ||H_r|| after the first chunk: H is the first factor an
    iteration solves, from the Procrustes step and the mode-1 product.
    ``factor_gap``: the widest relative Frobenius gap of H, V or W after
    any compared chunk."""
    fits = [f for s in snaps for f in s["fits"]]
    gaps = [abs(p - r.fit) if math.isfinite(p) else math.inf
            for p, r in zip(fits, refs)]
    worst, where = 0.0, ""
    it = 0
    for s in snaps:
        it += len(s["fits"])
        r = refs[it - 1]
        for leaf in ("H", "V", "W"):
            p, q = s[leaf], getattr(r, leaf)
            d = np.linalg.norm(p - q) / max(np.linalg.norm(q), 1e-300)
            d = float(d) if np.isfinite(d) else math.inf
            if d > worst or not where:
                worst, where = d, f"{leaf}@{it}"
    r1 = refs[len(snaps[0]["fits"]) - 1]
    h_gap = float(np.linalg.norm(snaps[0]["H"] - r1.H) / np.linalg.norm(r1.H))
    return {"fit1_gap": gaps[0], "fit_gap": max(gaps),
            "h_gap": h_gap if math.isfinite(h_gap) else math.inf,
            "factor_gap": worst, "factor_gap_leaf": where}


def judge(numbers: dict, limits: dict):
    """(correct, [(name, number, limit)])."""
    rows = [(k, numbers[k], float(v)) for k, v in limits.items()]
    ok = all(math.isfinite(n) and n <= lim for _, n, lim in rows)
    return ok, rows


# ---------------------------------------------------------------------------
# metrics of a traced run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    trace: Optional["trace_mod.Trace"]
    spans: Dict[str, float]
    compile_s: float
    iterations_traced: int
    buckets: List[dict]
    rank: int
    device_kind: str
    work: Dict[str, int]     # the cohort's unpadded sizes (kernel_cost)


def per_layer(cell: Cell, ctx: Context) -> dict:
    out = {}
    for m in cell.bench["per_layer"]:
        if cell.name not in m.get("workloads", [cell.name]):
            continue
        mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, traced: bool, t0: float,
        fault: Optional[Callable] = None, log=print) -> dict:
    """One run; returns the result line's object. ``fault`` (tests only)
    wraps the compiled chunk."""
    import jax

    clock = CompileClock().install()
    dev = jax.devices()[0]
    spans: Dict[str, float] = {}

    t = time.perf_counter()
    cohort = gen.generate(cell.cfg, seed)
    spans["generate_s"] = time.perf_counter() - t
    log(f"[cohort] K={cohort.n_subjects} J={cohort.n_cols} nnz={cohort.nnz} "
        f"({spans['generate_s']:.2f} s)")

    prog = build_program(cell, cohort, spans)
    log("[buckets] " + json.dumps(prog.buckets))
    chunk = prog.chunk if fault is None else fault(prog)
    op_names = trace_mod.hlo_op_names(prog.compiled.as_text()) if traced else {}

    # the first iterations, through the window's own call
    t = time.perf_counter()
    snaps = []
    state = prog.state
    while sum(len(s["fits"]) for s in snaps) < STEPS:
        state, fits = chunk(state)
        snaps.append(snapshot(state, fits))
    spans["checked_steps_s"] = time.perf_counter() - t
    mem = chunk_memory(prog)
    prog.state = None
    setup_s = time.perf_counter() - t0
    compile_setup = clock.seconds
    log(f"[setup] {setup_s:.3f} s; compile {compile_setup:.3f} s "
        f"({json.dumps(clock.by_event)}) in {clock.compiles} backend "
        f"compiles, {clock.cache_hits} cache hits; spans {json.dumps(spans)}")

    # the window
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    events_before = clock.events
    iters = chunks = failed = 0
    if traced:
        jax.profiler.start_trace(tmp)
        # the first chunk under a fresh profiler starts on the device over a
        # second late (a TPU v5e trace showed a 1.4 s gap); it stays out of
        # the traced window, whose idle share is the program's own
        state, fits = chunk(state)
        np.asarray(fits)
    with jax.profiler.TraceAnnotation(trace_mod.HOST_PREFIX + "window"):
        start = time.perf_counter()
        marks = [start]
        while marks[-1] - start < seconds:
            with jax.profiler.TraceAnnotation(trace_mod.HOST_PREFIX + "dispatch"):
                state, fits = chunk(state)
            with jax.profiler.TraceAnnotation(trace_mod.HOST_PREFIX + "sync"):
                fits = np.asarray(fits)      # the one host sync per chunk
            chunks += 1
            iters += fits.size
            failed += int(np.sum(~np.isfinite(fits)))
            marks.append(time.perf_counter())
    end = marks[-1]
    if traced:
        jax.profiler.stop_trace()
    in_window = clock.events - events_before
    last_fit = float(fits[-1])
    stats = dev.memory_stats() or {}
    peak_in_use = stats.get("peak_bytes_in_use")
    log(f"[window] {iters} iterations in {chunks} chunks, {end - start:.3f} s;"
        f" compilations in window: {in_window}; last fit {last_fit!r}; "
        f"chunk seconds {[round(b - a, 4) for a, b in zip(marks, marks[1:])]}")
    log(f"[memory] memory_analysis {json.dumps(mem)}; "
        f"peak_bytes_in_use {peak_in_use}")

    metrics: Dict[str, dict] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "device_kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": int(mem["peak"])}
    breakdown = None
    if traced:
        tr = trace_mod.load(tmp, op_names=op_names)
        shutil.rmtree(tmp, ignore_errors=True)
        busy = trace_mod.busy_ns(tr) / 1e9
        window_s = (tr.window[1] - tr.window[0]) / 1e9
        device["busy_s"] = busy
        device["window_s"] = window_s
        ctx = Context(trace=tr, spans=spans, compile_s=compile_setup,
                      iterations_traced=iters, buckets=prog.buckets,
                      rank=prog.opts.rank, device_kind=dev.device_kind,
                      work={"subjects": cohort.n_subjects,
                            "rows": int(cohort.n_rows.sum()),
                            "nnz": cohort.nnz,
                            "distinct_cols": int(cohort.distinct_cols().sum())})
        metrics = per_layer(cell, ctx)
        breakdown = {"device_ops": trace_mod.top_ops(tr),
                     "idle_gaps": trace_mod.idle_gaps(tr)}
        log(f"[trace] busy {busy!r} s of {window_s!r} s; "
            f"{sum(len(v) for v in tr.devices.values())} device ops")
    else:
        metrics = {
            "iter_s": {"value": (end - start) / iters, "unit": "s"},
            "hbm_peak_gib": {"value": mem["peak"] / GIB, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }

    # free the program's state before the reference runs
    del state, chunk, prog
    gc.collect()

    t = time.perf_counter()
    v0 = initial_v(cell.cfg)
    refs = reference.run(cohort, v0, sum(len(s["fits"]) for s in snaps))
    ref_s = time.perf_counter() - t
    numbers = compare(snaps, refs)
    correct, rows = judge(numbers, cell.limits)
    log(f"[check] reference {ref_s:.2f} s; worst factor gap at "
        f"{numbers['factor_gap_leaf']}; fits program "
        f"{[f for s in snaps for f in s['fits']]} reference "
        f"{[r.fit for r in refs]}")
    checks = {k: {"value": n, "limit": lim} for k, n, lim in rows}
    result = {"correct": bool(correct),
              "attempted": iters, "failed": failed, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
