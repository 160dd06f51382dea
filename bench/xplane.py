"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

A device is a plane named ``/device:TPU:<n>``. Its ``XLA Modules`` line
holds one event per program execution, its ``XLA Ops`` line one per
operation (nested: a ``while`` contains its body's operations). The host's
plane ``/host:CPU`` holds the benchmark's own spans
(``jax.profiler.TraceAnnotation``), named ``bench.<what>``. Every event is
on one clock, in nanoseconds from the start of the trace.

- busy time: the union of a device's program intervals inside the window;
- a kernel's time: the summed durations of the operations named after it;
- top operations: summed durations by operation name, leaving out the
  control-flow operations that only contain others;
- idle gaps: the stretches between busy intervals, each named after the
  benchmark span that overlaps it most.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

CONTAINERS = {"while", "conditional", "call", "copy-start", "copy-done",
              "async-start", "async-done"}
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)?(\s*=|$)")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def op_name(event_name: str) -> str:
    """``%paged_mixed_attention_pool.9 = bf16[...] custom-call(...)`` ->
    ``paged_mixed_attention_pool``."""
    m = _OP.match(event_name.strip())
    return m.group(1) if m else event_name.split("=")[0].strip()


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclass
class Device:
    index: int
    modules: List[Tuple[int, int]] = field(default_factory=list)
    ops: List[Tuple[str, int, int]] = field(default_factory=list)


@dataclass
class Reduced:
    """What the metrics read from one trace."""
    window: Tuple[int, int]
    busy_ns: Dict[int, int]                 # per device index
    op_ns: Dict[int, Dict[str, int]]        # per device, per op name
    gaps: List[Tuple[str, int]]             # device 0: (span name, ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def has_device(self, device: int = 0) -> bool:
        return device in self.busy_ns

    def busy_s(self, device: int = 0) -> float:
        return self.busy_ns.get(device, 0) / 1e9

    def kernel_s(self, name: str, device: int = 0) -> Optional[float]:
        """Device seconds of operations named ``name``; None when none
        ran."""
        ns = self.op_ns.get(device, {}).get(name)
        return None if ns is None else ns / 1e9

    def top_ops(self, k: int = 10, device: int = 0) -> List[list]:
        ops = self.op_ns.get(device, {})
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns / 1e9] for n, ns in top]

    def longest_gaps(self, k: int = 10) -> List[list]:
        return [[n, ns / 1e9] for n, ns in
                sorted(self.gaps, key=lambda g: -g[1])[:k]]


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read(path: Path, window_span: str = "bench.window") -> Reduced:
    """Reduce the trace at ``path``. The window is the extent of the host
    span ``window_span``; without it, the extent of the device events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[int, Device] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device(int(m.group(1))))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules += [(int(e.start_ns),
                                     int(e.start_ns + e.duration_ns))
                                    for e in line.events]
                elif line.name == "XLA Ops":
                    dev.ops += [(op_name(e.name), int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events
                          if e.name.startswith("bench.")]
    return reduce(devices, spans, window_span)


def reduce(devices: Dict[int, Device], spans: List[Tuple[str, int, int]],
           window_span: str = "bench.window") -> Reduced:
    win = [(a, b) for n, a, b in spans if n == window_span]
    if win:
        lo, hi = min(a for a, _ in win), max(b for _, b in win)
    else:
        ends = [x for d in devices.values() for iv in d.modules for x in iv]
        lo, hi = (min(ends), max(ends)) if ends else (0, 0)
    busy, op_ns = {}, {}
    for i, dev in devices.items():
        busy[i] = sum(b - a for a, b in clip(union(dev.modules), lo, hi))
        acc: Dict[str, int] = defaultdict(int)
        for name, a, b in dev.ops:
            if name in CONTAINERS:
                continue
            a, b = max(a, lo), min(b, hi)
            if b > a:
                acc[name] += b - a
        op_ns[i] = dict(acc)
    gaps: List[Tuple[str, int]] = []
    host = [(n, a, b) for n, a, b in spans if n != window_span]
    if 0 in devices:
        busy0 = clip(union(devices[0].modules), lo, hi)
        edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label(host, a, b), b - a))
    return Reduced((lo, hi), busy, op_ns, gaps)


def _label(spans, a: int, b: int) -> str:
    """The name of the span overlapping ``[a, b)`` most (innermost spans
    win ties by being shorter)."""
    best, best_ov = "host", 0
    for name, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov > best_ov:
            best, best_ov = name, ov
    return best
