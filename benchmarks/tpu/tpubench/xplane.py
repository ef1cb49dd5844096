"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

``load`` keeps three kinds of intervals, all on the profiler's clock in
nanoseconds: each device plane's operations (line ``XLA Ops``) and
programs (line ``XLA Modules``), and the host's spans (the events of the
Python thread's line on the ``/host:CPU`` plane — the program's tracer
enters a ``TraceAnnotation`` per span when its JAX bridge is on, and the
benchmark marks its window as ``bench.window``).

The reductions:

* ``union`` — seconds in which some operation ran, within a window;
* ``gaps`` — the idle intervals between them;
* ``innermost`` — the shortest host span open at an instant, which names
  what the host was doing during an idle gap;
* ``op_seconds`` — device time per operation (HLO name and kind).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int, str]          # (start_ns, end_ns, name)

WINDOW_SPAN = "bench.window"
OUTSIDE = "outside any program span"


@dataclass
class DevicePlane:
    name: str
    ops: List[Interval] = field(default_factory=list)
    modules: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DevicePlane]
    host: List[Interval]

    def window(self, name: str = WINDOW_SPAN) -> Optional[Tuple[int, int]]:
        spans = [(s, e) for s, e, n in self.host if n == name]
        return max(spans, key=lambda x: x[1] - x[0]) if spans else None

    def spans(self, name: str, lo: int, hi: int) -> List[Interval]:
        return [iv for iv in self.host
                if iv[2] == name and iv[0] >= lo and iv[1] <= hi]


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: List[DevicePlane] = []
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dp = DevicePlane(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dp.ops = _intervals(line)
                elif line.name == "XLA Modules":
                    dp.modules = _intervals(line)
            if dp.ops:
                devices.append(dp)
        elif plane.name == "/host:CPU":
            # the Python thread's line: the program's spans, the window
            # mark, and what the interpreter did around them
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend(_intervals(line))
    host.sort()
    return Trace(devices, host)


def _intervals(line) -> List[Interval]:
    out = []
    for ev in line.events:
        s = int(ev.start_ns)
        out.append((s, s + int(ev.duration_ns), ev.name))
    out.sort()
    return out


def clip(ivs: Sequence[Interval], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for s, e, _ in ivs:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def merged(ivs: Sequence[Interval], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Disjoint, sorted intervals covering ``ivs`` within [lo, hi]."""
    out: List[List[int]] = []
    for s, e in sorted(clip(ivs, lo, hi)):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union(ivs: Sequence[Interval], lo: int, hi: int) -> float:
    """Seconds of [lo, hi] covered by at least one interval."""
    return sum(e - s for s, e in merged(ivs, lo, hi)) / 1e9


def gaps(ivs: Sequence[Interval], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The idle intervals of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merged(ivs, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(host: Sequence[Interval], t: int,
              skip: Sequence[str] = (WINDOW_SPAN,)) -> str:
    """Name of the shortest host span open at ``t``."""
    best = None
    for s, e, n in host:
        if s > t:
            break
        if e >= t and n not in skip and (best is None
                                         or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else OUTSIDE


_OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9_\-]*)\(")


def op_name(name: str) -> str:
    """An HLO op event's name without its shapes: ``%fusion.25 = s8[...]
    fusion(...), kind=kCustom, ...`` -> ``%fusion.25 fusion kCustom``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:200]
    m = _OPCODE.search(rest)
    parts = [head, m.group(1) if m else ""]
    if ", kind=" in rest:
        parts.append(rest.split(", kind=")[1].split(",")[0])
    return " ".join(p for p in parts if p)[:200]


def op_seconds(ivs: Sequence[Interval], lo: int, hi: int) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for s, e, n in ivs:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            n = op_name(n)
            out[n] = out.get(n, 0.0) + (e - s) / 1e9
    return out


def busy_in(trace: Trace, lo: int, hi: int) -> float:
    """Device-busy seconds within [lo, hi], averaged over device planes."""
    if not trace.devices:
        return 0.0
    return sum(union(d.ops, lo, hi) for d in trace.devices) \
        / len(trace.devices)


def breakdown(trace: Trace, lo: int, hi: int, top: int = 10):
    """The device operations that took most time, and the longest idle
    gaps named by the host span open at their middle (first device)."""
    ops: Dict[str, float] = {}
    for d in trace.devices:
        for n, sec in op_seconds(d.ops, lo, hi).items():
            ops[n] = ops.get(n, 0.0) + sec / len(trace.devices)
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    idle: List[Tuple[str, float]] = []
    if trace.devices:
        gs = sorted(gaps(trace.devices[0].ops, lo, hi),
                    key=lambda g: g[0] - g[1])[:top]
        idle = [(innermost(trace.host, (s + e) // 2), (e - s) / 1e9)
                for s, e in gs]
    return ([[n, s] for n, s in device_ops], [[n, s] for n, s in idle])
