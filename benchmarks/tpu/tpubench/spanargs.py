"""The arguments of the program's host spans in a traced run.

The program's tracer hands each span's numeric arguments (those given
when the span opens) to the profiler's ``TraceAnnotation``, which keeps
them as the stats of the host event, under the span's bare name.
``xplane.load`` keeps names and times only; ``of`` reads, once per run,
the spans inside the window together with their stats, from the same
``.xplane.pb``.  The readers of the per-layer metrics that count bytes,
waits and sweep work share it.  A trace whose spans carry no such
arguments, or lack the spans, gives readers nothing to read, and they
return ``None``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import xplane


@dataclass(frozen=True)
class Span:
    start: int                     # ns, on the profiler's clock
    end: int
    name: str
    args: Dict[str, Any]

    @property
    def ms(self) -> float:
        return (self.end - self.start) / 1e6


def load(path: str, lo: int, hi: int) -> List[Span]:
    """The events of the Python thread's line on ``/host:CPU`` that lie
    within [lo, hi], with their stats."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if not line.name.startswith("python"):
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if s >= lo and e <= hi:
                    out.append(Span(s, e, ev.name, dict(ev.stats)))
    out.sort(key=lambda sp: (sp.start, sp.end))
    return out


def trace_path(ctx) -> Optional[str]:
    """The run's ``.xplane.pb``, where the harness's profiler wrote it."""
    from . import harness
    return xplane.find_xplane(str(harness.OUT_DIR / "trace" / ctx.cell.name))


# the harness hands each reader a fresh Context over one Outcome, so the
# run's spans are kept beside the Outcome they were read for
_loaded: Tuple[Any, List[Span]] = (None, [])


def of(ctx) -> List[Span]:
    """The window's host spans with their arguments; empty without a
    trace.  Loaded once per run (per ``ctx.out``)."""
    global _loaded
    if _loaded[0] is not ctx.out:
        spans: List[Span] = []
        path = trace_path(ctx) if ctx.window is not None else None
        if path is not None:
            spans = load(path, *ctx.window)
        _loaded = (ctx.out, spans)
    return _loaded[1]


def named(spans: Sequence[Span], name: str) -> List[Span]:
    return [sp for sp in spans if sp.name == name]


def mean_ms(spans: Sequence[Span]) -> Optional[float]:
    return sum(sp.ms for sp in spans) / len(spans) if spans else None


def arg_values(spans: Sequence[Span], name: str, arg: str) -> List[float]:
    """``arg`` of every ``name`` span that carries it."""
    return [sp.args[arg] for sp in spans if sp.name == name and arg in sp.args]
