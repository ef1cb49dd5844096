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

A mix with ``writes`` also sends the configuration's update stream
through ``AsyncServer.submit_update``: set-up applies the stream's head
as one batch before it warms up, and in the window a writer coroutine
beside the pump applies the inserts that fell due in each
``commit_s`` seconds as one batch, each insert timed from its due time
to the return of the call that carried it.  Batches are numbered from 1
in the order they were applied, the set-up's first; each answer is
checked against the reference at its ticket's epoch (the base and the
first ``epoch`` batches), every read is checked to have been admitted at
an epoch no lower than the batches acknowledged before it was
submitted, and after the window a sample of the acknowledged inserts is
read back through the served path.
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
    epoch: Optional[int] = None     # the ticket's admission epoch
    min_epoch: int = 0              # last epoch acknowledged at submit


class Writes:
    """The write side of a run: the plan, and the batches applied so
    far, each a range ``[a, b)`` of stream operations, with the epoch
    its call returned and, for the window's operations, when it
    returned."""

    def __init__(self, plan: tr.WritePlan, stream: Dict[str, Any]):
        self.plan, self.stream = plan, stream
        self.batches: List[Tuple[int, int]] = []
        self.acks: List[int] = []
        self.ack_times: List[float] = []
        self.acked_at = np.full(plan.due.size, np.nan)
        self.error: Optional[str] = None

    def apply(self, server, a: int, b: int) -> None:
        s, p, o = tr.stream_edges(self.stream, a, b)
        try:
            epoch = server.submit_update(
                add=list(zip(s.tolist(), p.tolist(), o.tolist())))
        except Exception as e:      # noqa: BLE001 — recorded as a failure
            self.error = f"{type(e).__name__}: {e}"
            return
        now = time.monotonic()
        self.batches.append((a, b))
        self.acks.append(int(epoch))
        self.ack_times.append(now)
        lo, hi = max(a, self.plan.head), b
        if hi > lo:
            self.acked_at[lo - self.plan.head:hi - self.plan.head] = now

    def latencies_ms(self, t0: float) -> np.ndarray:
        """Each acknowledged window insert, from due time to the return
        of the call that carried it."""
        ok = ~np.isnan(self.acked_at)
        return (self.acked_at[ok] - t0 - self.plan.due[ok]) * 1e3

    def edge_epochs(self) -> np.ndarray:
        """Per stream edge: the number of the batch that applied it, or
        ``NEVER``."""
        off = self.stream["off"]
        ep = np.full(int(off[-1]), ref.NEVER, dtype=np.int64)
        for j, (a, b) in enumerate(self.batches, start=1):
            ep[off[a]:off[b]] = j
        return ep


@dataclass
class Window:
    t0: float
    seconds: float
    records: List[Record]
    compiles: List[str] = field(default_factory=list)
    writes: Optional[Writes] = None


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


async def _request(server, rec: Record, t0: float,
                   writes: Optional[Writes] = None) -> None:
    from repro.core.scheduler import Backpressure
    await _sleep_until(t0 + rec.req.due)
    rec.submitted = time.monotonic()
    if writes is not None and writes.acks:
        rec.min_epoch = writes.acks[-1]
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
    rec.epoch = at.ticket.epoch


async def _writer(server, writes: Writes, t0: float) -> None:
    """Every ``commit_s`` seconds, one batch of the operations that fell
    due before then (none: no call)."""
    plan = writes.plan
    i, k = plan.head, 1
    while i < plan.end:
        t = k * plan.commit_s
        await _sleep_until(t0 + t)
        j = plan.head + int(np.searchsorted(plan.due, t, side="left"))
        if j > i:
            writes.apply(server, i, j)
            i = j
        k += 1


async def _window(server, plan: tr.Plan, seconds: float, drain_s: float,
                  compiles: CompileLog, on_start=None,
                  on_close=None, writes: Optional[Writes] = None) -> Window:
    t0 = time.monotonic() + 0.05
    win = Window(t0, seconds, [Record(r) for r in plan.requests],
                 writes=writes)
    tasks = [asyncio.ensure_future(_request(server, rec, t0, writes))
             for rec in win.records]
    if writes is not None:
        tasks.append(asyncio.ensure_future(_writer(server, writes, t0)))
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
    # inserts read back after the window: (s, p, o) -> seen at the end
    read_back: Optional[Dict[Tuple[int, int, int], bool]] = None


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

    async def open(self, cached: List[tr.Request] = (),
                   writes: Optional[Writes] = None) -> None:
        from repro.core.engines import make_engine
        from repro.core.scheduler import AsyncServer, SlotScheduler
        e = self.cell.config["engine"]
        engine = make_engine(to_graph(self.data), e["kind"],
                             **e.get("kwargs", {}))
        self.server = AsyncServer(SlotScheduler(engine,
                                                max_slots=e["max_slots"]))
        await self.server.__aenter__()
        data = self.data
        if writes is not None:
            # the window's edge rows, then warm-up at nodes that no
            # insert of the run touches
            writes.apply(self.server, 0, writes.plan.head)
            s, _, o = tr.stream_edges(data["stream"], 0, writes.plan.end)
            data = dict(data, s=np.concatenate([data["s"], s]),
                        o=np.concatenate([data["o"], o]))
        for burst in tr.warmup_bursts(self.mix, data, e["max_slots"]):
            await _burst(self.server, burst)
        await _burst(self.server, list(cached))
        self.setup_s = time.monotonic() - self.started

    async def window(self, plan: tr.Plan, seconds: float,
                     trace_dir: Optional[str] = None,
                     drain_s: float = DRAIN_S,
                     writes: Optional[Writes] = None
                     ) -> Tuple[Window, "Profile"]:
        prof = Profile(trace_dir)
        prof.start()
        win = await _window(self.server, plan, seconds, drain_s,
                             self.compiles, prof.open_window,
                             prof.close_window, writes)
        return win, prof

    async def read_back(self, writes: Writes, k: int, seed: int,
                        timeout_s: float = DRAIN_S
                        ) -> Dict[Tuple[int, int, int], bool]:
        """Up to ``k`` of the window's acknowledged inserts, drawn from
        ``seed``, each read back with a one-hop query anchored at both
        ends; an insert whose answer lacks it, or never comes, is not
        seen."""
        off = writes.stream["off"]
        rows = np.concatenate(
            [np.arange(off[max(a, writes.plan.head)], off[b])
             for a, b in writes.batches if b > writes.plan.head] or
            [np.zeros(0, dtype=np.int64)])
        pick = rows[tr.rng_for(seed, tr.READ_BACK).permutation(
            rows.size)[:k]]
        names = self.data.get("pred_names")
        st = writes.stream
        edges = [(int(st["s"][i]), int(st["p"][i]), int(st["o"][i]))
                 for i in np.sort(pick)]
        from repro.core.engines import Query
        tickets = [await self.server.submit(
            Query(names[p] if names else str(p), s, o)) for s, p, o in edges]

        async def seen(at, s, o):
            try:
                return (s, o) in await at.result()
            except Exception:       # noqa: BLE001 — not seen
                return False
        found = await asyncio.wait_for(asyncio.gather(
            *[seen(at, s, o) for at, (s, _, o) in zip(tickets, edges)]),
            timeout_s)
        return dict(zip(edges, found))

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
                drain_s: float = DRAIN_S, before_window=None,
                graph: Optional[ref.Graph] = None) -> Outcome:
    """One run's set-up and window.  ``before_window`` (a test's way to
    plant a fault under the timed path) is called once set-up is done.
    ``graph`` is the reference's graph of ``data``, when already built."""
    wplan = tr.write_plan(cell.mix, data, seconds, rate)
    graph = graph if graph is not None else reference_graph(data)
    plan = tr.window_plan(cell.mix, data, seconds, seed, graph, rate=rate)
    writes = Writes(wplan, data["stream"]) if wplan is not None else None
    sess = Session(cell, data, started)
    await sess.open(plan.cached, writes)
    if before_window is not None:
        before_window()
    win, prof = await sess.window(plan, seconds, trace_dir, drain_s,
                                  writes)
    settled = all(r.error is None for r in win.records)
    read_back = None
    if writes is not None:
        k = int(cell.mix["check"].get("read_back", 0))
        read_back = {}
        if settled and writes.error is None:
            try:
                read_back = await sess.read_back(writes, k, seed, drain_s)
            except asyncio.TimeoutError:
                settled = False
    peak = await sess.close(settled)
    out = Outcome(sess.setup_s, win, peak, graph, read_back=read_back)
    prof.read(out)
    return out


# -- the check --------------------------------------------------------------


def reference_graph(data) -> ref.Graph:
    """The reference's graph of ``data``; with an update stream, every
    stream edge is in it too, at an epoch that a run assigns (until then
    never), and a query that names no epoch reads the base."""
    st = data.get("stream")
    if st is None:
        return ref.Graph(data["s"], data["p"], data["o"], data["num_nodes"],
                         data["num_preds"], pred_names=data.get("pred_names"))
    n = int(data["s"].size)
    graph = ref.Graph(
        np.concatenate([data["s"], st["s"]]),
        np.concatenate([data["p"], st["p"]]),
        np.concatenate([data["o"], st["o"]]), data["num_nodes"],
        data["num_preds"], pred_names=data.get("pred_names"),
        epochs=np.r_[np.zeros(n, dtype=np.int64),
                     np.full(st["s"].size, ref.NEVER, dtype=np.int64)])
    graph.at = 0
    return graph


def check(cell, data, out: Outcome, seed: int,
          control: bool = False) -> Dict[str, Dict[str, Any]]:
    """The numbers compared, each with its limit.  With ``control`` the
    reference with a broken guarantee takes the program's place."""
    win = out.win
    recs = win.records
    answered = [i for i, r in enumerate(recs) if r.answer is not None]
    must: List[int] = []
    seen = set()
    k = int(cell.mix["check"]["sample"])
    if win.writes is not None:
        # reads in flight while a batch was applied: where snapshot
        # isolation can fail; up to half the sample, drawn from the seed
        acks = np.asarray(win.writes.ack_times[1:])
        straddle = [i for i in answered if np.any(
            (acks > recs[i].submitted) & (acks < recs[i].done))]
        order = tr.rng_for(seed, tr.STRADDLE).permutation(len(straddle))
        must += sorted(straddle[int(j)] for j in order[:k // 2])
    for i in answered:
        r = recs[i]
        for key in (("plan", r.plan), ("cache", r.cache_hit),
                    ("template", r.req.template)):
            if key not in seen:
                seen.add(key)
                must.append(i)
    sizes = [len(recs[i].answer) for i in answered]
    pick = [answered[j] for j in tr.check_sample(
        len(answered), sizes, k, seed,
        must=[answered.index(i) for i in must])]
    graph = out.graph
    writes = win.writes
    if writes is not None:
        n_base = graph.epochs.size - writes.edge_epochs().size
        graph.epochs[n_base:] = writes.edge_epochs()
    # the control: every closure one level short of its fixpoint, or,
    # where the mix says so, the reference blind to the window's writes
    kind = cell.mix["check"].get("control", "truncate_closures")
    wrong = 0
    for i in pick:
        r = recs[i]
        q = (graph, r.req.expr, r.req.subject, r.req.obj)
        # at the ticket's epoch (a graph without writes has no epochs)
        want = ref.answer(*q, epoch=r.epoch)
        if not control:
            got = r.answer
        elif kind == "ignore_writes":
            got = ref.answer(*q, epoch=1)   # the base and set-up's batch
        else:
            got = ref.answer(*q, epoch=r.epoch, truncate_closures=True)
        ok, missing, extra = ref.pairs_equal(got, want)
        if not ok:
            wrong += 1
            print(f"check: {r.req.template} ({r.req.subject}, {r.req.expr}, "
                  f"{r.req.obj}) at epoch {r.epoch}: {missing} pairs "
                  f"missing, {extra} extra", file=sys.stderr)
    # a request shed at the door (``Backpressure``) is unanswered too
    unanswered = sum(r.error is not None for r in recs)
    checks = {"wrong_answers": {"value": wrong, "limit": 0,
                                "checked": len(pick)},
              "unanswered": {"value": unanswered, "limit": 0}}
    if writes is None:
        return checks
    # an insert due in the window that no call acknowledged is unanswered
    checks["unanswered"]["value"] += int(np.isnan(writes.acked_at).sum())
    stale = [r for r in recs if r.epoch is not None
             and r.epoch < r.min_epoch]
    for r in stale[:5]:
        print(f"check: {r.req.template} submitted after epoch "
              f"{r.min_epoch} was acknowledged, admitted at epoch "
              f"{r.epoch}", file=sys.stderr)
    k = int(cell.mix["check"].get("read_back", 0))
    seen = out.read_back or {}
    # an insert that could not be read back (the server never settled)
    # counts as lost
    lost = sum(not v for v in seen.values()) + max(0, min(
        k, int((~np.isnan(writes.acked_at)).sum())) - len(seen))
    checks["stale_reads"] = {"value": len(stale), "limit": 0}
    checks["lost_writes"] = {"value": lost, "limit": 0,
                             "read_back": len(seen)}
    return checks


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
    if win.writes is not None:
        values["write_p90_ms"] = pct(win.writes.latencies_ms(win.t0), 90)
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
    wr = win.writes
    if wr is not None:
        off = data["stream"]["off"]
        print(f"{cell.name}: set-up applied {wr.plan.head} update "
              f"operations ({int(off[wr.plan.head])} inserts); the window "
              f"sent {wr.plan.due.size} of {wr.plan.asked} asked "
              f"({int(off[wr.plan.end] - off[wr.plan.head])} inserts) in "
              f"{len(wr.batches) - 1} batches, insert buffer "
              f"{wr.plan.cap} rows; acknowledged epochs {wr.acks[:1]}.."
              f"{wr.acks[-1:]}; write latency samples "
              f"{int((~np.isnan(wr.acked_at)).sum())}"
              + (f"; error {wr.error}" if wr.error else ""),
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
