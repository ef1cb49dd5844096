"""One run of one cell: set up, warm up, measure a window, check, report.

The system under test is reached only through its public served path:
``make_engine`` builds the engine, ``SlotScheduler`` and ``AsyncServer``
serve it, ``AsyncServer.submit`` takes the requests, and each settled
ticket's answer is what is checked.  The
program's tracer (``repro.obs.trace``) is switched on, with its JAX
bridge, only in a traced run, where the profiler records the window.

A run:

1. builds the configuration's data from its generator (the graph is
   the deployment's, fixed by the configuration; ``--seed`` draws the
   traffic), the reference's index of it (which prices the requests the
   traffic lays out, and checks the answers), and the engine over it;
2. warms up through the served path: bursts of queries that make every
   slot-tick shape the window can use be dispatched once, then the
   mix's ``cached`` requests, whose answers stay in the result cache;
3. measures ``seconds`` of open-loop traffic: each request is submitted
   at its due time by a coroutine beside the server's pump, and timed
   from that due time to its final answer;
4. waits for every answer (at most ``drain_s`` past the close), reads the
   device's peak memory, frees the program's state, and checks a sample
   of the answers against the plain reference.
"""
from __future__ import annotations

import asyncio
import gc
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import reference as ref
from . import spec as specmod
from . import traffic as tr
from . import xplane

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DRAIN_S = 60.0
OUT_DIR = specmod.BENCH_DIR / "out"


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Record:
    req: tr.Request
    submitted: Optional[float] = None
    done: Optional[float] = None
    answer: Optional[set] = None
    cache_hit: bool = False
    plan: str = ""
    error: Optional[str] = None


@dataclass
class Window:
    t0: float
    seconds: float
    records: List[Record]
    compiles: List[str] = field(default_factory=list)


class CompileLog:
    """Counts backend compiles (persistent-cache loads included) from
    JAX's monitoring events; ``names`` since ``mark()``."""

    def __init__(self):
        import jax
        self.events: List[Tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((time.monotonic(), str(kw.get("fun_name"))))

    def since(self, t: float) -> List[str]:
        return [n for ts, n in self.events if ts >= t]


def setup_env() -> str:
    """Compile cache inside the checkout, or where the environment says.
    Call before JAX is imported."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(specmod.ROOT / ".cache" / "jax-compile"))
    # cache every program, not only those that took a second to compile
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    src = specmod.ROOT / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the program is not in this checkout "
                                f"({src / 'repro'} missing)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def check_devices(chips: int) -> List[Any]:
    import jax
    from .peaks import lookup
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    lookup(devs[0].device_kind)
    return devs[:chips]


def to_graph(data):
    from repro.core.ring import LabeledGraph
    return LabeledGraph(s=data["s"], p=data["p"], o=data["o"],
                        num_nodes=int(data["num_nodes"]),
                        num_preds=int(data["num_preds"]),
                        pred_names=data.get("pred_names"))


def _query(r: tr.Request):
    from repro.core.engines import Query
    return Query(r.expr, r.subject, r.obj)


async def _sleep_until(t: float) -> None:
    dt = t - time.monotonic()
    if dt > 0:
        await asyncio.sleep(dt)


async def _burst(server, reqs: List[tr.Request]) -> None:
    tickets = [await server.submit(_query(r)) for r in reqs]
    for t in tickets:
        await t.result()


async def _request(server, rec: Record, t0: float) -> None:
    from repro.core.scheduler import Backpressure
    await _sleep_until(t0 + rec.req.due)
    rec.submitted = time.monotonic()
    try:
        at = await server.submit(_query(rec.req))
    except Backpressure:
        rec.error = "shed"
        return
    try:
        rec.answer = await at.result()
    except Exception as e:          # noqa: BLE001 — recorded as a failure
        rec.error = f"{type(e).__name__}: {e}"
    rec.done = time.monotonic()
    rec.cache_hit = at.ticket.stats.result_cache_hits > 0
    rec.plan = at.ticket.stats.plan_mode or ""


async def _window(server, plan: tr.Plan, seconds: float, drain_s: float,
                  compiles: CompileLog, on_start=None,
                  on_close=None) -> Window:
    t0 = time.monotonic() + 0.05
    win = Window(t0, seconds, [Record(r) for r in plan.requests])
    tasks = [asyncio.ensure_future(_request(server, rec, t0))
             for rec in win.records]
    await _sleep_until(t0)
    if on_start is not None:
        on_start()
    await _sleep_until(t0 + seconds)
    win.compiles = compiles.since(t0)
    if on_close is not None:
        on_close()
    done, pending = await asyncio.wait(tasks, timeout=drain_s)
    for t in pending:
        t.cancel()
    for t in done:
        t.result()
    for rec in win.records:
        if rec.answer is None and rec.error is None:
            rec.error = "never answered"
    return win


@dataclass
class Outcome:
    """What a run hands to the report."""

    setup_s: float
    win: Window
    peak_bytes: int
    graph: ref.Graph
    trace: Optional[xplane.Trace] = None
    trace_window: Optional[Tuple[int, int]] = None


class Profile:
    """The traced run's profiler session: the program's tracer with its
    JAX bridge on, and the window marked as ``bench.window``."""

    def __init__(self, log_dir: Optional[str]):
        self.log_dir = log_dir
        self._ann = None

    def start(self) -> None:
        if self.log_dir is None:
            return
        import jax
        from repro.obs import trace as otrace
        otrace.TRACER.clear()
        otrace.TRACER.enable(jax_annotations=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # annotations, not runtime internals
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def open_window(self) -> None:
        if self.log_dir is not None:
            import jax
            self._ann = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            self._ann.__enter__()

    def close_window(self) -> None:
        if self.log_dir is not None:
            import jax
            from repro.obs import trace as otrace
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            otrace.TRACER.disable()

    def read(self, out: "Outcome") -> None:
        path = xplane.find_xplane(self.log_dir) if self.log_dir else None
        if path is not None:
            out.trace = xplane.load(path)
            out.trace_window = out.trace.window()


def _peak_bytes(stats: Dict[str, Any]) -> int:
    """Peak device memory: buffers in use plus the space the runtime
    reserves for compiled programs' temporaries, which the TPU runtime
    counts apart (``peak_bytes_reserved``) and which are most of it."""
    return int(stats.get("peak_bytes_in_use", 0)) + \
        int(stats.get("peak_bytes_reserved", 0))


class Session:
    """One served system: built and warmed up by ``open``; ``window``
    measures one window of traffic on it; ``close`` stops the server and
    reads the device's peak memory."""

    def __init__(self, cell: specmod.Cell, data, started: float):
        self.cell, self.data, self.started = cell, data, started
        self.mix = cell.mix
        self.compiles = CompileLog()
        self.setup_s = 0.0

    async def open(self, cached: List[tr.Request] = ()) -> None:
        from repro.core.engines import make_engine
        from repro.core.scheduler import AsyncServer, SlotScheduler
        e = self.cell.config["engine"]
        engine = make_engine(to_graph(self.data), e["kind"],
                             **e.get("kwargs", {}))
        self.server = AsyncServer(SlotScheduler(engine,
                                                max_slots=e["max_slots"]))
        await self.server.__aenter__()
        for burst in tr.warmup_bursts(self.mix, self.data, e["max_slots"]):
            await _burst(self.server, burst)
        await _burst(self.server, list(cached))
        self.setup_s = time.monotonic() - self.started

    async def window(self, plan: tr.Plan, seconds: float,
                     trace_dir: Optional[str] = None,
                     drain_s: float = DRAIN_S) -> Tuple[Window, "Profile"]:
        prof = Profile(trace_dir)
        prof.start()
        win = await _window(self.server, plan, seconds, drain_s,
                             self.compiles, prof.open_window,
                             prof.close_window)
        return win, prof

    async def close(self, settled: bool) -> int:
        """Stop the server (when every request settled; otherwise its
        pump never stops and is cancelled with the event loop), free the
        program's state, and return the peak device bytes."""
        import jax
        if settled:
            await self.server.__aexit__(None, None, None)
        peak = max(_peak_bytes(d.memory_stats() or {})
                   for d in jax.local_devices()[:self.cell.chips])
        del self.server
        gc.collect()
        return peak


async def serve(cell: specmod.Cell, data, seed: int, seconds: float,
                trace_dir: Optional[str], started: float,
                rate: Optional[float] = None,
                drain_s: float = DRAIN_S, before_window=None) -> Outcome:
    """One run's set-up and window.  ``before_window`` (a test's way to
    plant a fault under the timed path) is called once set-up is done."""
    graph = reference_graph(data)
    plan = tr.window_plan(cell.mix, data, seconds, seed, graph, rate=rate)
    sess = Session(cell, data, started)
    await sess.open(plan.cached)
    if before_window is not None:
        before_window()
    win, prof = await sess.window(plan, seconds, trace_dir, drain_s)
    peak = await sess.close(all(r.error is None for r in win.records))
    out = Outcome(sess.setup_s, win, peak, graph)
    prof.read(out)
    return out


# -- the check --------------------------------------------------------------


def reference_graph(data) -> ref.Graph:
    return ref.Graph(data["s"], data["p"], data["o"], data["num_nodes"],
                     data["num_preds"], pred_names=data.get("pred_names"))


def check(cell, data, out: Outcome, seed: int,
          control: bool = False) -> Dict[str, Dict[str, Any]]:
    """The numbers compared, each with its limit.  With ``control`` the
    reference with a broken guarantee takes the program's place."""
    win = out.win
    recs = win.records
    answered = [i for i, r in enumerate(recs) if r.answer is not None]
    must: List[int] = []
    seen = set()
    for i in answered:
        r = recs[i]
        for key in (("plan", r.plan), ("cache", r.cache_hit),
                    ("template", r.req.template)):
            if key not in seen:
                seen.add(key)
                must.append(i)
    sizes = [len(recs[i].answer) for i in answered]
    k = int(cell.mix["check"]["sample"])
    pick = [answered[j] for j in tr.check_sample(
        len(answered), sizes, k, seed,
        must=[answered.index(i) for i in must])]
    graph = out.graph
    wrong = 0
    for i in pick:
        r = recs[i]
        want = ref.answer(graph, r.req.expr, r.req.subject, r.req.obj)
        # the control: every closure one level short of its fixpoint
        got = ref.answer(graph, r.req.expr, r.req.subject, r.req.obj,
                         truncate_closures=True) if control else r.answer
        ok, missing, extra = ref.pairs_equal(got, want)
        if not ok:
            wrong += 1
            print(f"check: {r.req.template} ({r.req.subject}, {r.req.expr}, "
                  f"{r.req.obj}): {missing} pairs missing, {extra} extra",
                  file=sys.stderr)
    # a request shed at the door (``Backpressure``) is unanswered too
    unanswered = sum(r.error is not None for r in recs)
    return {"wrong_answers": {"value": wrong, "limit": 0,
                              "checked": len(pick)},
            "unanswered": {"value": unanswered, "limit": 0}}


# -- the report ---------------------------------------------------------------


def pct(xs, q: float) -> Optional[float]:
    """Exact percentile (linear between order statistics)."""
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q)) \
        if len(xs) else None


@dataclass
class Context:
    """What a per-layer metric reader reads."""

    cell: specmod.Cell
    out: Outcome

    @property
    def trace(self) -> Optional[xplane.Trace]:
        return self.out.trace

    @property
    def window(self) -> Optional[Tuple[int, int]]:
        return self.out.trace_window

    def host_spans(self, name: str):
        if self.trace is None or self.window is None:
            return []
        return self.trace.spans(name, *self.window)


def end_to_end(cell, out: Outcome) -> Dict[str, Dict[str, Any]]:
    win = out.win
    lat = [(r.done - win.t0 - r.req.due) * 1e3 for r in win.records
           if r.answer is not None]
    values = {"setup_s": out.setup_s, "p50_ms": pct(lat, 50),
              "p90_ms": pct(lat, 90)}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if values.get(m["name"]) is not None}


def per_layer(cell, out: Outcome) -> Dict[str, Dict[str, Any]]:
    ctx = Context(cell, out)
    res = {}
    for m in cell.per_layer:
        v = m.reader.read(ctx)
        if v is not None:
            res[m.name] = {"value": float(v), "unit": m.unit}
    return res


def device_info(devs, out: Outcome) -> Dict[str, Any]:
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": out.peak_bytes}
    if out.trace is not None and out.trace_window is not None:
        lo, hi = out.trace_window
        info["busy_s"] = xplane.busy_in(out.trace, lo, hi)
        info["window_s"] = (hi - lo) / 1e9
    return info


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        started: float, require_tpu: bool = True,
        cell: Optional[specmod.Cell] = None, control: bool = False,
        rate: Optional[float] = None, drain_s: float = DRAIN_S,
        before_window=None) -> Dict[str, Any]:
    """One run; returns the result line's object."""
    setup_env()
    import jax
    cell = cell if cell is not None else specmod.resolve(cell_name)
    devs = check_devices(cell.chips) if require_tpu \
        else jax.devices()[:cell.chips]
    data = cell.generator.build(cell.config)
    trace_dir = None
    if trace:
        trace_dir = str(OUT_DIR / "trace" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = asyncio.run(serve(cell, data, seed, seconds, trace_dir, started,
                            rate=rate, drain_s=drain_s,
                            before_window=before_window))
    win = out.win
    lat_n = sum(r.answer is not None for r in win.records)
    print(f"{cell.name}: seed {seed}; {len(win.records)} requests in "
          f"{seconds} s, {lat_n} answered ({sum(r.cache_hit for r in win.records)} "
          f"from the result cache); latency samples {lat_n}; "
          f"compiles in the window "
          f"{len(win.compiles)} {sorted(set(win.compiles))}",
          file=sys.stderr)
    checks = check(cell, data, out, seed, control=control)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": correct,
        "attempted": len(win.records),
        "failed": sum(r.error is not None for r in win.records),
        "metrics": per_layer(cell, out) if trace else end_to_end(cell, out),
        "device": device_info(devs, out),
    }
    if trace and out.trace is not None and out.trace_window is not None:
        ops, idle = xplane.breakdown(out.trace, *out.trace_window)
        result["breakdown"] = {"device_ops": ops, "idle_gaps": idle}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    return result
