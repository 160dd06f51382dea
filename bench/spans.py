"""Reduce a profiler trace by program and by span.

``xplane.read`` keeps the benchmark's own ``bench.*`` spans and the
device's busy time. This reduction also reads the program's own spans
(``aqua.*``: the engine step and its phases, parks and restores with their
cause, tier moves, mesh legs) with their attributes, and the name of every
device program (``jit_aqua_step_mixed(…)`` is the program
``aqua_step_mixed``), so that

- device 0's idle time is split by the innermost span the host was in at
  each instant (of the spans covering it, the one started last, the
  shorter on a tie; ``host`` where none was), and each idle gap, kept
  whole, is labelled with the span that holds the largest part of it;
- each device operation is attributed to the program whose execution
  holds it, and each program's executions are timed;
- spans can be counted and timed by name and attribute.

Everything is on the profiler's one clock and inside the window: the
extent of the ``bench.window`` span, or of the device's programs without
it. Reading a trace of a program that has no such spans or names gives
empty tables, never an error.

The metric readers reach the reduction through ``of(run, root)``, which
finds the run's trace under ``<root>/.bench_trace/<cell>-<seed>/`` (the
newest, and only if its window is the one ``run.trace`` holds), reduces it
once and logs its tables.
"""
from __future__ import annotations

import bisect
import heapq
import re
import statistics
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import xplane

PREFIXES = ("bench.", "aqua.")
WINDOW = "bench.window"
STEP = "aqua.step"
STEP_PROGRAM = "aqua_step_"
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(module: str) -> str:
    """``jit_aqua_step_mixed(8655483430299075520)`` -> ``aqua_step_mixed``."""
    return _MODULE.match(module.strip()).group(1)


@dataclass
class Span:
    name: str
    start: int
    end: int
    args: Dict[str, object] = field(default_factory=dict)

    @property
    def ns(self) -> int:
        return self.end - self.start


@dataclass
class Trace:
    """What the per-layer metrics read from one trace."""
    window: Tuple[int, int]
    spans: List[Span]                                  # host, all names
    runs: Dict[int, List[Tuple[str, int, int]]]        # device: executions
    op_ns: Dict[int, Dict[Tuple[str, str], int]]       # device: (prog, op)
    idle: Dict[str, int]                               # device 0, per span
    gaps: List[Tuple[str, int]]                        # device 0, labelled
    idle_iv: List[Tuple[int, int]]                     # device 0 idle
    programs: Set[str] = field(default_factory=set)    # every name seen

    def has_device(self, device: int = 0) -> bool:
        return device in self.runs

    def in_window(self, *names: str) -> List[Span]:
        """Spans of these names that start inside the window."""
        lo, hi = self.window
        return [s for s in self.spans
                if s.name in names and lo <= s.start < hi]

    def program_ns(self, device: int = 0) -> Dict[str, int]:
        """Device time per program: its executions, clipped to the
        window."""
        lo, hi = self.window
        out: Dict[str, int] = defaultdict(int)
        for prog, a, b in self.runs.get(device, []):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                out[prog] += b - a
        return dict(out)

    def kind_ns(self, device: int = 0) -> Dict[str, int]:
        """Device time per fused-step kind (``aqua_step_<kind>``)."""
        return {p[len(STEP_PROGRAM):]: ns
                for p, ns in self.program_ns(device).items()
                if p.startswith(STEP_PROGRAM)}

    def executions(self, *programs: str, device: int = 0) -> List[int]:
        """Durations of the executions of these programs that start
        inside the window."""
        lo, hi = self.window
        return [b - a for p, a, b in self.runs.get(device, [])
                if p in programs and lo <= a < hi]

    def op_in(self, op: str, programs: Iterable[str],
              device: int = 0) -> int:
        """Device time of operations named ``op`` inside these programs."""
        progs = set(programs)
        return sum(ns for (p, o), ns in self.op_ns.get(device, {}).items()
                   if o == op and p in progs)

    def idle_inside(self, spans: Sequence[Span]) -> int:
        """Device-0 idle time that falls inside these spans."""
        cover = xplane.union([(s.start, s.end) for s in spans])
        out, j = 0, 0
        for a, b in self.idle_iv:
            while j < len(cover) and cover[j][1] <= a:
                j += 1
            k = j
            while k < len(cover) and cover[k][0] < b:
                out += max(0, min(b, cover[k][1]) - max(a, cover[k][0]))
                k += 1
        return out

    def longest_gaps(self, k: int = 10) -> List[list]:
        return [[n, ns / 1e9] for n, ns in
                sorted(self.gaps, key=lambda g: -g[1])[:k]]


def read(path: Path, window_span: str = WINDOW) -> Trace:
    """Reduce the trace at ``path``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    modules: Dict[int, List[Tuple[str, int, int]]] = {}
    ops: Dict[int, List[Tuple[str, int, int]]] = {}
    spans: List[Span] = []
    host_modules: Set[str] = set()
    on_device = any(xplane._DEVICE.match(p.name) for p in pd.planes)
    for plane in pd.planes:
        m = xplane._DEVICE.match(plane.name)
        if m:
            i = int(m.group(1))
            modules.setdefault(i, [])
            ops.setdefault(i, [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[i] += [(program_name(e.name), int(e.start_ns),
                                    int(e.start_ns + e.duration_ns))
                                   for e in line.events]
                elif line.name == "XLA Ops":
                    ops[i] += [(xplane.op_name(e.name), int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        args = ({k: v for k, v in e.stats
                                 if not k.startswith("_")}
                                if e.name.startswith("aqua.") else {})
                        spans.append(Span(e.name, int(e.start_ns),
                                          int(e.start_ns + e.duration_ns),
                                          args))
                    elif not on_device:
                        # on the CPU the compiled programs run on host
                        # threads; their operations name the module
                        for k, v in e.stats:
                            if k == "hlo_module":
                                host_modules.add(program_name(str(v)))
    t = reduce(modules, ops, spans, window_span)
    t.programs |= host_modules
    return t


def reduce(modules: Dict[int, List[Tuple[str, int, int]]],
           ops: Dict[int, List[Tuple[str, int, int]]],
           spans: List[Span], window_span: str = WINDOW) -> Trace:
    """``modules``: per device, ``(program, start, end)`` of each
    execution; ``ops``: per device, ``(op, start, end)``; ``spans``: the
    host spans."""
    win = [s for s in spans if s.name == window_span]
    if win:
        lo, hi = min(s.start for s in win), max(s.end for s in win)
    else:
        ends = [x for runs in modules.values() for _, a, b in runs
                for x in (a, b)]
        lo, hi = (min(ends), max(ends)) if ends else (0, 0)
    runs = {i: sorted(r, key=lambda x: x[1]) for i, r in modules.items()}
    op_ns: Dict[int, Dict[Tuple[str, str], int]] = {}
    for i, dev_ops in ops.items():
        starts = [a for _, a, _ in runs.get(i, [])]
        acc: Dict[Tuple[str, str], int] = defaultdict(int)
        for name, a, b in dev_ops:
            if name in xplane.CONTAINERS:
                continue
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            acc[(_holder(runs.get(i, []), starts, a), name)] += b - a
        op_ns[i] = dict(acc)
    idle: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[str, int]] = []
    idle_iv: List[Tuple[int, int]] = []
    if 0 in runs:
        busy = xplane.clip(xplane.union([(a, b) for _, a, b in runs[0]]),
                           lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle_iv = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        timeline = innermost([s for s in spans if s.name != window_span],
                             lo, hi)
        j = 0
        for a, b in idle_iv:
            while j < len(timeline) and timeline[j][1] <= a:
                j += 1
            part: Dict[str, int] = defaultdict(int)
            shortest: Dict[str, int] = {}
            covered, k = 0, j
            while k < len(timeline) and timeline[k][0] < b:
                s, e, span = timeline[k]
                ov = min(b, e) - max(a, s)
                if ov > 0:
                    part[span.name] += ov
                    shortest[span.name] = min(
                        shortest.get(span.name, span.ns), span.ns)
                    covered += ov
                k += 1
            if b - a > covered:
                part["host"] += b - a - covered
                shortest.setdefault("host", hi - lo)
            for n, ns in part.items():
                idle[n] += ns
            label = max(part, key=lambda n: (part[n], -shortest[n]))
            gaps.append((label, b - a))
    programs = {p for r in runs.values() for p, _, _ in r}
    return Trace((lo, hi), spans, runs, op_ns, dict(idle), gaps, idle_iv,
                 programs)


def _holder(runs, starts, t: int) -> str:
    """The program whose execution holds instant ``t`` (``?`` if none)."""
    k = bisect.bisect_right(starts, t) - 1
    if k >= 0 and runs[k][1] <= t < runs[k][2]:
        return runs[k][0]
    return "?"


def innermost(spans: List[Span], lo: int, hi: int
              ) -> List[Tuple[int, int, Span]]:
    """Disjoint stretches of ``[lo, hi)`` each with the innermost span
    covering it: of the spans covering it, the one started last (the
    shorter on a tie). Stretches no span covers are left out."""
    spans = sorted((s for s in spans if s.end > lo and s.start < hi),
                   key=lambda s: s.start)
    bounds = sorted({min(max(x, lo), hi) for s in spans
                     for x in (s.start, s.end)})
    out: List[Tuple[int, int, Span]] = []
    heap: List[tuple] = []
    j = 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(spans) and spans[j].start <= a:
            heapq.heappush(heap, (-spans[j].start, spans[j].ns, j))
            j += 1
        while heap and spans[heap[0][2]].end <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        span = spans[heap[0][2]]
        if out and out[-1][2] is span and out[-1][1] == a:
            out[-1] = (out[-1][0], b, span)
        else:
            out.append((a, b, span))
    return out


# ---------------------------------------------------------------------------
# the metric readers' entry point
# ---------------------------------------------------------------------------
def find(run, root: Path) -> Optional[Path]:
    """The newest trace of the run's cell under ``root``."""
    name = run.cell.get("name", "")
    found = list(Path(root).glob(
        f".bench_trace/{name}-*/plugins/profile/*/*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


def of(run, root: Path) -> Optional[Trace]:
    """The run's trace, reduced once per run; None for an untraced run or
    when no trace of its window is found."""
    cached = getattr(run, "spans", None)
    if cached is not None:
        return cached or None
    t = None
    path = find(run, root) if run.trace is not None else None
    if path is not None:
        t = read(path)
        if tuple(t.window) != tuple(run.trace.window):
            t = None
    run.spans = t if t is not None else False
    if t is not None:
        for line in tables(t):
            print(line, flush=True)
    return t


def tables(t: Trace, k: int = 10) -> List[str]:
    """Device time by program, ``copy`` operations by program, device-0
    idle time and the longest idle gaps by innermost span, and the
    program's parks and restores by cause, as log lines."""
    out = []
    progs = t.program_ns()
    if progs:
        n_exec = defaultdict(int)
        for p, a, _ in t.runs.get(0, []):
            if t.window[0] <= a < t.window[1]:
                n_exec[p] += 1
        out.append("device time by program (device 0, s, executions): "
                   + ", ".join(f"{p} {ns / 1e9:.4f} ({n_exec[p]})"
                               for p, ns in sorted(progs.items(),
                                                   key=lambda x: -x[1])[:k]))
    copies = {p: t.op_in("copy", [p]) for p in progs}
    if any(copies.values()):
        out.append("copy ops by program (device 0, s): "
                   + ", ".join(f"{p} {ns / 1e9:.4f}" for p, ns in
                               sorted(copies.items(), key=lambda x: -x[1])
                               if ns))
    if t.idle:
        out.append("idle by innermost span (device 0, s): "
                   + ", ".join(f"{n} {ns / 1e9:.4f}" for n, ns in
                               sorted(t.idle.items(), key=lambda x: -x[1])))
        out.append("longest idle gaps by innermost span (device 0, s): "
                   + ", ".join(f"{n} {sec:.4f}"
                               for n, sec in t.longest_gaps(k)))
    moves: Dict[str, List[int]] = defaultdict(lambda: [0, 0, 0])
    for s in t.in_window("aqua.kv.park", "aqua.kv.restore"):
        key = f"{s.name[len('aqua.kv.'):]}/{s.args.get('cause', '?')}"
        moves[key][0] += 1
        moves[key][1] += int(s.args.get("pages", 0))
        moves[key][2] += s.ns
    if moves:
        out.append("parks and restores by cause (count, pages, s): "
                   + ", ".join(f"{c} {n} {p} {ns / 1e9:.4f}"
                               for c, (n, p, ns) in sorted(moves.items())))
    return out


def median_ms(ns: List[int]) -> Optional[float]:
    return statistics.median(ns) / 1e6 if ns else None
