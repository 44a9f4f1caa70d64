"""Reduce a JAX profiler trace of the measured window to what metrics need.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load_xplane`` reads it with ``jax.profiler.ProfileData`` into plain lists:

* per device plane (``/device:TPU:<i>``): its ``XLA Ops`` events (one per
  HLO instruction run; the event name is the instruction's HLO text, which
  carries its operand shapes) and its ``XLA Modules`` events (one per
  program run);
* the host spans whose name starts with ``prefix`` (the benchmark's own
  ``jax.profiler.TraceAnnotation``\\ s, e.g. ``bench.window``).

All times are seconds on the trace's clock. ``summarize`` then gives, over a
window:

* busy seconds, the length of the union of the op intervals, clipped to the
  window and averaged over the devices, and the idle share ``1 - busy/window``;
* device self time per op (less the ops nested in it, as a ``while`` holds
  its body), keyed ``<program>/<instruction>`` with numeric suffixes
  dropped (``jit__fused_descent/h_index_pallas``);
* the ``n_gaps`` longest idle gaps of device 0, each named after the
  innermost host span that contains its midpoint (``no span`` where none
  does).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Op", "Span", "TraceData", "Summary", "load_xplane", "find_xplane",
           "op_base", "module_base", "busy_intervals", "self_seconds",
           "summarize"]

_OP_RE = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=")
_MOD_RE = re.compile(r"^(.*?)(?:\(\d+\))?$")


@dataclass(frozen=True)
class Op:
    name: str  # the instruction's HLO text as the trace records it
    start: float
    dur: float


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class TraceData:
    ops: List[List[Op]]  # per device, sorted by start
    modules: List[List[Op]]  # per device, sorted by start
    spans: List[Span]  # host spans, sorted by start


@dataclass
class Summary:
    window_s: float
    busy_s: float
    op_seconds: Dict[str, float]
    gaps: List[Tuple[str, float]]  # the longest idle gaps, longest first
    # (key, op) of every op that began in the window
    ops: List[Tuple[str, Op]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_base(text: str) -> str:
    """``%h_index_pallas.6 = s32[..] custom-call(..)`` -> ``h_index_pallas``."""
    m = _OP_RE.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def module_base(name: str) -> str:
    """``jit__cold(7902813226705472258)`` -> ``jit__cold``."""
    return _MOD_RE.match(name).group(1)


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {len(paths)}")
    return paths[0]


def load_xplane(path: str, prefix: str = "bench.") -> TraceData:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            for key, out in (("XLA Ops", ops), ("XLA Modules", modules)):
                line = lines.get(key)
                evs = [] if line is None else [
                    Op(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events
                ]
                out.append(sorted(evs, key=lambda o: o.start))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        spans.append(Span(e.name, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
    spans.sort(key=lambda s: s.start)
    return TraceData(ops=ops, modules=modules, spans=spans)


def busy_intervals(ops: Sequence[Op], lo: float, hi: float):
    """Union of the op intervals, clipped to ``[lo, hi]``, sorted."""
    merged: List[List[float]] = []
    for o in sorted(ops, key=lambda o: o.start):
        a, b = max(o.start, lo), min(o.start + o.dur, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def self_seconds(ops: Sequence[Op]) -> List[float]:
    """Each op's duration less the time of the ops nested in it.

    A ``while`` or ``conditional`` op spans the ops of its body on the same
    line; its own time is what is left over.
    """
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].dur))
    own = [o.dur for o in ops]
    stack: List[int] = []
    for i in order:
        o = ops[i]
        while stack and ops[stack[-1]].start + ops[stack[-1]].dur <= o.start:
            stack.pop()
        top = ops[stack[-1]] if stack else None
        if top is not None and o.start + o.dur <= top.start + top.dur:
            own[stack[-1]] -= o.dur
        stack.append(i)
    return own


def _innermost(spans: Sequence[Span], t: float) -> Optional[Span]:
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best


def _module_of(modules: Sequence[Op], starts: Sequence[float],
               t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i].start + modules[i].dur:
        return module_base(modules[i].name)
    return "no program"


def summarize(data: TraceData, lo: float, hi: float,
              spans: Sequence[Span] = (), n_gaps: int = 10) -> Summary:
    """Busy/idle, time per op and named idle gaps over ``[lo, hi]``.

    ``spans`` adds host spans beyond the trace's own (the program's spans,
    mapped onto the trace clock) for naming the gaps.
    """
    if hi <= lo:
        raise ValueError("empty window")
    if not data.ops:
        raise RuntimeError("the trace holds no device plane")
    all_spans = [s for s in list(data.spans) + list(spans)
                 if s.end >= lo and s.start <= hi
                 and s.name not in ("bench.window",)]
    busy_total = 0.0
    op_seconds: Dict[str, float] = {}
    in_window: List[Tuple[str, Op]] = []
    gaps: List[Tuple[str, float]] = []
    for dev, (ops, mods) in enumerate(zip(data.ops, data.modules)):
        merged = busy_intervals(ops, lo, hi)
        busy_total += sum(b - a for a, b in merged)
        starts = [m.start for m in mods]
        for o, own in zip(ops, self_seconds(ops)):
            if o.start < lo or o.start > hi:
                continue
            key = f"{_module_of(mods, starts, o.start)}/{op_base(o.name)}"
            op_seconds[key] = op_seconds.get(key, 0.0) + own
            in_window.append((key, o))
        if dev == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            holes = sorted(((a, b) for a, b in zip(edges[0::2], edges[1::2])
                            if b > a), key=lambda ab: ab[0] - ab[1])
            for a, b in holes[:n_gaps]:
                s = _innermost(all_spans, 0.5 * (a + b))
                gaps.append((s.name if s else "no span", b - a))
    return Summary(window_s=hi - lo, busy_s=busy_total / len(data.ops),
                   op_seconds=op_seconds, gaps=gaps, ops=in_window)
