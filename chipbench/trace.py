"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes: the op
events of each device plane ("XLA Ops" line) and the benchmark's own host
spans (``chipbench.*`` TraceAnnotations). The reductions:

* ``busy_ns``: the union of the intervals in which an op ran on a device.
  Ops nest on TPU lines (a while loop and its body's ops); the union counts
  each instant once.
* ``op_time``: device time per op, summed by a predicate on the op.
* ``idle_gaps``: the gaps between busy intervals inside the traced window,
  each labelled by the host span the benchmark was in at the gap's middle.

A TPU op event is named by its HLO instruction (``%fusion.12 = ...``) and
carries no name stack. ``hlo_op_names`` reads the name stack of every
instruction (``metadata={op_name=...}``) from the compiled program's HLO
text, and ``load`` puts it on each op as ``op_name``, so that a metric can
find the ops a JAX primitive (``eigh``, ``sort``) lowered to.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

__all__ = ["Op", "Trace", "load", "busy_intervals", "busy_ns", "hlo_op_names",
           "idle_gaps", "op_time", "top_ops"]

HOST_PREFIX = "chipbench."


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def instr(self) -> str:
        """The HLO instruction name (``fusion.12`` of ``%fusion.12 = ...``)."""
        return instr_name(self.name)

    @property
    def op_name(self) -> str:
        """The JAX name stack the instruction came from ("" if unknown)."""
        return str(self.stats.get("op_name", ""))


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_META = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def instr_name(text: str) -> str:
    m = _INSTR.match(text)
    return m.group(1) if m else text.split(" ")[0].lstrip("%")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name metadata} of every instruction of an HLO
    module's text that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            meta = _META.search(line)
            if meta:
                out[m.group(1)] = meta.group(1)
    return out


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]            # device plane -> op events
    host: List[Tuple[str, float, float]]    # (span, start_ns, end_ns)
    window: Tuple[float, float]             # traced window (start, end) ns


def _stats(ev) -> Dict[str, object]:
    out = {}
    try:
        for k, v in ev.stats:
            out[str(k)] = v
    except Exception:   # a stat the bindings cannot convert
        pass
    return out


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, op_names: Optional[Dict[str, str]] = None) -> Trace:
    """Read a trace file (or the newest one under a directory); ``op_names``
    (from ``hlo_op_names``) gives each op its name stack."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                ops = devices.setdefault(plane.name, [])
                for ev in line.events:
                    st = _stats(ev)
                    if op_names:
                        st["op_name"] = op_names.get(instr_name(ev.name), "")
                    ops.append(Op(ev.name, ev.start_ns, ev.duration_ns, st))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name[len(HOST_PREFIX):], ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    host.sort(key=lambda h: h[1])
    spans = [(s, e) for n, s, e in host if n == "window"]
    if spans:
        window = spans[0]
    else:
        ops = [o for v in devices.values() for o in v]
        window = ((min(o.start_ns for o in ops), max(o.end_ns for o in ops))
                  if ops else (0.0, 0.0))
    return Trace(devices=devices, host=host, window=window)


def busy_intervals(ops: List[Op], window: Tuple[float, float]):
    """Union of the ops' intervals, clipped to the window, sorted."""
    lo, hi = window
    iv = sorted((max(o.start_ns, lo), min(o.end_ns, hi)) for o in ops
                if o.end_ns > lo and o.start_ns < hi)
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(tr: Trace) -> float:
    """Busy time averaged over the traced devices."""
    if not tr.devices:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(ops, tr.window))
              for ops in tr.devices.values())
    return tot / len(tr.devices)


def _leaf_ops(ops: List[Op]) -> List[Op]:
    """Ops that contain no other op of the same line: the ones that do the
    work, not a while loop or call that wraps them."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns))
    leaves = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start_ns < o.end_ns and nxt.end_ns <= o.end_ns:
            continue    # o encloses the next op
        leaves.append(o)
    return leaves


def op_time(tr: Trace, pred: Callable[[Op], bool]) -> float:
    """Device ns of the leaf ops matching ``pred`` inside the window,
    averaged over devices."""
    if not tr.devices:
        return 0.0
    lo, hi = tr.window
    tot = 0.0
    for ops in tr.devices.values():
        for o in _leaf_ops(ops):
            if o.start_ns >= lo and o.end_ns <= hi and pred(o):
                tot += o.dur_ns
    return tot / len(tr.devices)


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The n leaf ops that took the most device time, [[name, seconds]]."""
    lo, hi = tr.window
    tot: Dict[str, float] = {}
    for ops in tr.devices.values():
        for o in _leaf_ops(ops):
            if o.start_ns >= lo and o.end_ns <= hi:
                key = f"{o.instr} {o.op_name}".strip()
                tot[key] = tot.get(key, 0.0) + o.dur_ns
    nd = max(len(tr.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in best]


def _host_label(tr: Trace, t: float) -> str:
    label = "other"
    for name, s, e in tr.host:
        if name != "window" and s <= t <= e:
            label = name
    return label


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The n longest device-idle gaps in the window, [[host span, seconds]],
    on the first device."""
    if not tr.devices:
        return []
    ops = tr.devices[sorted(tr.devices)[0]]
    lo, hi = tr.window
    busy = busy_intervals(ops, tr.window)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_label(tr, (s + e) / 2), (e - s) / 1e9] for s, e in gaps[:n]]
