"""Open-loop serving benchmark: continuous-batching slots vs bucket
flushing, under Poisson arrivals at a fixed offered QPS.

    PYTHONPATH=src python -m benchmarks.serving [--smoke] [--json PATH]

The experiment the slot scheduler exists for: requests arrive on an
*open-loop* Poisson process (arrival times are drawn up front and do not
wait for the server — the honest way to measure tail latency, since a
closed loop self-throttles exactly when the server is slow), mixing
cheap single-label probes with expensive closure queries.  Two servers
answer the identical trace on identically-fresh engines:

  * ``bucket`` — the pre-scheduler baseline: admit into a bucket,
    flush through ``eval_many`` at ``max_batch`` requests or
    ``max_wait_ms``, every request in a bucket waits for the whole
    batch (head-of-line blocking behind the slowest automaton);
  * ``slot`` — :class:`repro.core.scheduler.SlotScheduler`: requests
    join the in-flight wavefront between supersteps and retire the
    superstep they converge, so a cheap probe admitted next to a
    monster closure finishes in milliseconds regardless.

Rows (latency in ms — lower is better; ``p99_speedup`` = bucket p99 /
slot p99, higher is better):

    serving/<engine>/qps<q>/slot_p50_ms
    serving/<engine>/qps<q>/slot_p99_ms
    serving/<engine>/qps<q>/bucket_p50_ms
    serving/<engine>/qps<q>/bucket_p99_ms
    serving/<engine>/qps<q>/p99_speedup

Per-phase latency attribution (from the tickets' ``QueryStats``; the
split the end-to-end percentiles can't show — where a slow p99 went):

    serving/<engine>/qps<q>/slot_queue_wait_p50_ms   (and _p99_ms)
    serving/<engine>/qps<q>/slot_service_p50_ms      (and _p99_ms)

Instrumentation overhead (ratio, gated < 1.02 by benchmarks/compare.py):

    serving/<engine>/tracer_off_overhead

— mean burst slot latency with the tracer disabled (the production
default: every span call site is one global read + branch) over the
same with the call sites hard-bypassed (``repro.obs.trace.bypass()``,
the closest runtime stand-in for deleting the instrumentation).

    serving/<engine>/recorder_on_overhead

— the same construction for the always-on flight recorder: the default
bounded ring buffer over a scheduler with the recording path disabled
entirely (the pre-recorder baseline).  Gated by the same absolute
< 1.02 bound.

Histogram cross-check (the ``--json`` fix): the end-to-end percentiles
are *also* re-derived from the scheduler's log-bucketed
``rpq_e2e_seconds`` histogram and asserted within its documented
``sqrt(growth)`` factor of the exact sample percentiles — the raw rows
and the ``metrics_snapshot()`` exposition can no longer silently
disagree:

    serving/<engine>/qps<q>/slot_hist_p50_ms   (and _p99_ms)

Admission-policy comparison (informational, never gated): preempt rate
of one deadline-mixed burst under FIFO vs earliest-deadline-first
admission on identically-fresh engines:

    serving/<engine>/admission_fifo_preempt_rate
    serving/<engine>/admission_edf_preempt_rate

``--smoke`` / BENCH_SMOKE=1 shrinks the fixture and trace for CI.
``--trace PATH`` / ``--metrics PATH`` additionally run a small traced
demo over BOTH engines and export the Chrome trace-event JSON and a
Prometheus metrics snapshot (the CI serving job uploads both).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

if __package__ in (None, ""):                       # direct-script run
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))

import numpy as np


def _workload(g, n, rng):
    """``n`` queries, ~1-in-4 expensive: closure expressions over the
    hub predicates reach a large fraction of a scale-free graph, single
    labels touch a handful of nodes — the mix where head-of-line
    blocking hurts."""
    from repro.core.engines import Query
    cheap = ["4", "5/6", "^2", "7"]
    heavy = ["(0|1)+", "0/(1|2)*", "(0|1|2)+"]
    out = []
    for i in range(n):
        exprs = heavy if rng.random() < 0.25 else cheap
        expr = exprs[int(rng.integers(0, len(exprs)))]
        out.append(Query(expr, obj=int(rng.integers(0, g.num_nodes))))
    return out


def _arrivals(n, qps, rng):
    """Open-loop Poisson offsets (seconds from trace start), as plain
    floats so the serving loops do no conversions."""
    gaps = rng.exponential(1.0 / qps, size=n)
    t = np.cumsum(gaps) - gaps[0]
    return [float(x) for x in t]


def _run_slot(eng, queries, arrivals, max_slots=8, prep=None,
              **sched_kwargs):
    """Serve the trace through the slot scheduler; per-request latency =
    ticket completion - scheduled arrival (includes queueing).  Returns
    (latencies, settled tickets, scheduler) — the tickets carry the
    per-phase attribution (``stats.queue_wait_s`` / ``service_s``), the
    scheduler its metrics registry and flight recorder.  ``prep`` (if
    given) runs on the freshly built scheduler before serving; extra
    keyword arguments reach the :class:`SlotScheduler` constructor
    (``recorder_capacity``, ``admission_policy``, ...)."""
    from repro.core.scheduler import SlotScheduler
    sched = SlotScheduler(eng, max_slots=max_slots,
                          max_queue=len(queries) + 1, **sched_kwargs)
    if prep is not None:
        prep(sched)
    n = len(queries)
    tickets = [None] * n
    lat = [0.0] * n
    i = 0
    t0 = time.monotonic()
    while i < n or sched.pending():
        now = time.monotonic() - t0
        while i < n and arrivals[i] <= now:
            tickets[i] = sched.submit(queries[i])
            i += 1
        progressed = sched.step()
        if not progressed and i < n:
            # idle server, next arrival in the future: sleep up to it
            time.sleep(max(0.0, arrivals[i] - (time.monotonic() - t0)))
    for j in range(n):
        lat[j] = tickets[j].finished_at - t0 - arrivals[j]
    return lat, tickets, sched


def _run_bucket(eng, queries, arrivals, max_batch=32, max_wait_s=0.004):
    """The pre-scheduler baseline: flush a bucket through ``eval_many``
    at ``max_batch`` or ``max_wait_s``; every request's latency runs to
    its *bucket's* completion."""
    n = len(queries)
    lat = [0.0] * n
    i = 0
    bucket = []          # indices
    bucket_t0 = None     # arrival of the oldest queued request
    t0 = time.monotonic()
    while i < n or bucket:
        now = time.monotonic() - t0
        while i < n and arrivals[i] <= now:
            if not bucket:
                bucket_t0 = arrivals[i]
            bucket.append(i)
            i += 1
        flush = len(bucket) >= max_batch or \
            (bucket and now - bucket_t0 >= max_wait_s) or \
            (bucket and i >= n)
        if flush:
            batch, bucket = bucket, []
            eng.eval_many([queries[j] for j in batch])
            done = time.monotonic() - t0
            for j in batch:
                lat[j] = done - arrivals[j]
        elif i < n:
            wait = arrivals[i] - (time.monotonic() - t0)
            if bucket_t0 is not None and bucket:
                wait = min(wait, bucket_t0 + max_wait_s
                           - (time.monotonic() - t0))
            time.sleep(max(0.0, wait))
    return lat


def _pct(lat, q):
    return sorted(lat)[min(len(lat) - 1, int(q * len(lat)))]


def _exact_pct(samples, q):
    """Exact sample quantile under the histogram's rank convention
    (the ``ceil(q*n)``-th smallest observation) — the comparable ground
    truth for :meth:`repro.obs.metrics.Histogram.quantile`."""
    import math
    s = sorted(samples)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def _hist_check(tag, tickets, sched, rows):
    """The ``--json`` fix: this module re-derives latency percentiles
    from raw samples while ``metrics_snapshot()`` reports the
    log-bucketed ``rpq_e2e_seconds`` histogram.  Emit BOTH and assert
    they agree within the estimator's documented ``sqrt(growth)``
    factor (see ``Histogram.quantile``) — a disagreement means the
    Prometheus exposition is lying about the tail and fails the suite
    loudly (surfaces as ``serving/ERROR``)."""
    import math
    h = sched.metrics.histogram("rpq_e2e_seconds")
    samples = [t.finished_at - t.submitted_at for t in tickets]
    bound = math.sqrt(h.growth) * (1 + 1e-9)
    for q, name in ((0.50, "p50"), (0.99, "p99")):
        est = h.quantile(q)
        exact = _exact_pct(samples, q)
        rows.append((f"{tag}/slot_hist_{name}_ms", est * 1e3))
        # below min_value every observation shares bucket 0 and the
        # factor guarantee does not apply (never the case for real
        # end-to-end latencies, but keep the gate honest)
        if exact <= h.min_value:
            continue
        if not (exact / bound <= est <= exact * bound):
            raise RuntimeError(
                f"{tag}: histogram {name} {est * 1e3:.4f}ms disagrees "
                f"with exact {exact * 1e3:.4f}ms beyond the "
                f"sqrt(growth)={bound:.4f} bound")


def _tracer_off_overhead(eng, queries, reps=2):
    """Price the disabled instrumentation: mean burst slot latency with
    the module tracer off (production default — every span call site is
    a global read + branch returning NULL_SPAN) over the same run with
    the call sites hard-bypassed.  Interleaved best-of-``reps`` per mode
    on the same warmed engine, so system noise hits both modes alike."""
    from repro.obs import trace as otrace
    burst = [0.0] * len(queries)

    def mean_lat(ctx):
        with ctx:
            eng.results.clear()
            lat, _, _ = _run_slot(eng, queries, burst)
        return sum(lat) / len(lat)

    off, byp = [], []
    for _ in range(reps):
        off.append(mean_lat(contextlib.nullcontext()))
        byp.append(mean_lat(otrace.bypass()))
    return min(off) / max(min(byp), 1e-9)


def _recorder_on_overhead(eng, queries, reps=2):
    """Price the always-on flight recorder the same way: mean burst
    slot latency with the default bounded ring buffer over the same run
    with the whole recording path disabled (no record dicts built, no
    ring writes — the closest runtime stand-in for the pre-recorder
    scheduler).  Interleaved best-of-``reps`` on the same warmed
    engine, mirroring :func:`_tracer_off_overhead`."""
    burst = [0.0] * len(queries)

    def _disable(sched):
        sched._record_ticket = lambda *a, **k: None

    def mean_lat(prep):
        eng.results.clear()
        lat, _, _ = _run_slot(eng, queries, burst, prep=prep)
        return sum(lat) / len(lat)

    on, off = [], []
    for _ in range(reps):
        on.append(mean_lat(None))
        off.append(mean_lat(_disable))
    return min(on) / max(min(off), 1e-9)


def _admission_compare(g, kind, queries, service_p50_s):
    """One deadline-mixed burst under FIFO vs earliest-deadline-first
    admission on identically-fresh single-slot schedulers: alternate
    requests carry a deadline a few median service times out, so FIFO
    lets them expire in the queue behind deadline-less traffic while
    EDF pulls them forward.  Returns ``{policy: preempt_rate}`` —
    informational rows (the rate is fixture- and load-dependent, so it
    never gates), the FIFO-vs-EDF gap is the point."""
    from repro.core.engines import make_engine
    from repro.core.scheduler import SlotScheduler
    deadline_s = max(1e-3, 8.0 * service_p50_s)
    out = {}
    for policy in ("fifo", "edf"):
        eng = make_engine(g, kind)
        eng.eval_many(queries)          # compiles out of the timed burst
        eng.results.clear()
        sched = SlotScheduler(eng, max_slots=1,
                              max_queue=len(queries) + 1,
                              admission_policy=policy)
        for i, q in enumerate(queries):
            sched.submit(q, deadline_s=deadline_s if i % 2 else None)
        sched.drain()
        out[policy] = sched.preempted / max(1, len(queries))
    return out


def _traced_demo(trace_path, metrics_path):
    """A tiny traced serving run over BOTH engines: exports the Chrome
    trace-event JSON (admission/superstep/retire spans for ring AND
    dense) and the dense scheduler's Prometheus snapshot — the CI
    serving job's observability artifacts."""
    from repro.core.engines import make_engine
    from repro.core.fixtures import scale_free_graph
    from repro.core.scheduler import SlotScheduler
    from repro.obs import trace as otrace

    g = scale_free_graph(120, 8, 960, seed=23)
    queries = _workload(g, 8, np.random.default_rng(5))
    tr = otrace.Tracer()
    tr.enable()
    prom = ""
    with otrace.use(tr):
        for kind in ("ring", "dense"):
            eng = make_engine(g, kind)
            sched = SlotScheduler(eng, max_slots=4)
            for q in queries:
                sched.submit(q)
            sched.drain()
            prom = sched.prometheus_text()
    if trace_path:
        tr.export(trace_path)
        print(f"wrote {trace_path} ({len(tr.events)} events)",
              file=sys.stderr)
    if metrics_path:
        with open(metrics_path, "w") as f:
            f.write(prom)
        print(f"wrote {metrics_path}", file=sys.stderr)


# per-engine scale: offered QPS must sit below the engine's service
# capacity (an open-loop trace above capacity measures queue drain, not
# scheduling) — the ring's host-side bit-parallel traversal serves ~2
# q/s on this mix, the dense engine's compiled BFS >100 q/s
_FULL = {
    "dense": dict(V=3_000, E=24_000, n=120, qps=(50, 200)),
    "ring": dict(V=800, E=6_400, n=40, qps=(2,)),
}
_SMOKE = {
    "dense": dict(V=500, E=4_000, n=24, qps=(100,)),
}


def run():
    from repro.core.engines import make_engine
    from repro.core.fixtures import scale_free_graph

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    configs = _SMOKE if smoke else _FULL
    rows = []
    for kind, cfg in configs.items():
        n = cfg["n"]
        g = scale_free_graph(cfg["V"], 8, cfg["E"], seed=23)
        queries = _workload(g, n, np.random.default_rng(3))
        overhead_eng = None
        for qps in cfg["qps"]:
            arrivals = _arrivals(n, qps, np.random.default_rng(17))
            per_mode = {}
            slot_tickets = []
            for mode, runner in (("slot", _run_slot),
                                 ("bucket", _run_bucket)):
                # fresh engine per mode: identical compile/cache state,
                # and no cross-mode result-cache pollution.  Warm through
                # the runner as a burst (the batched BFS compiles per
                # (rows, S_pad, steps) shape, and each mode dispatches
                # its own shapes), then sweep small pow2 batch sizes —
                # timed bucket boundaries jitter with the clock, and an
                # unseen batch shape mid-run would bill one compile to
                # one request.
                eng = make_engine(g, kind)
                runner(eng, queries, [0.0] * n)
                k = 1
                while k <= min(32, n):
                    eng.results.clear()
                    eng.eval_many(queries[:k])
                    k *= 2
                eng.results.clear()
                out = runner(eng, queries, arrivals)
                if mode == "slot":
                    per_mode[mode], slot_tickets, slot_sched = out
                    overhead_eng = eng   # warmed + slot-shaped: reuse below
                else:
                    per_mode[mode] = out
            tag = f"serving/{kind}/qps{qps}"
            for mode, lat in per_mode.items():
                rows.append((f"{tag}/{mode}_p50_ms", _pct(lat, 0.50) * 1e3))
                rows.append((f"{tag}/{mode}_p99_ms", _pct(lat, 0.99) * 1e3))
            rows.append((f"{tag}/p99_speedup",
                         _pct(per_mode["bucket"], 0.99)
                         / max(_pct(per_mode["slot"], 0.99), 1e-9)))
            # per-phase attribution: where a request's end-to-end
            # latency went (queue wait vs in-slot service)
            for phase in ("queue_wait", "service"):
                vals = [getattr(t.stats, f"{phase}_s") for t in slot_tickets]
                rows.append((f"{tag}/slot_{phase}_p50_ms",
                             _pct(vals, 0.50) * 1e3))
                rows.append((f"{tag}/slot_{phase}_p99_ms",
                             _pct(vals, 0.99) * 1e3))
            # raw-vs-histogram percentile reconciliation (raises on
            # disagreement beyond the estimator's documented factor)
            _hist_check(tag, slot_tickets, slot_sched, rows)
        if overhead_eng is not None:
            rows.append((f"serving/{kind}/tracer_off_overhead",
                         _tracer_off_overhead(overhead_eng, queries)))
            rows.append((f"serving/{kind}/recorder_on_overhead",
                         _recorder_on_overhead(overhead_eng, queries)))
            # admission-policy comparison on a bounded subset (the ring
            # serves ~2 q/s — keep the extra burst affordable)
            sub = queries[:min(n, 16)]
            p50 = _exact_pct([t.stats.service_s for t in slot_tickets], 0.50)
            for policy, rate in _admission_compare(g, kind, sub, p50).items():
                rows.append((f"serving/{kind}/admission_{policy}"
                             "_preempt_rate", rate))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixture/trace (sets BENCH_SMOKE=1)")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write rows as a JSON document (the shape "
                         "benchmarks/run.py emits, for benchmarks/compare.py)")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="run a small traced demo over both engines and "
                         "export Chrome trace-event JSON to PATH")
    ap.add_argument("--metrics", type=str, default=None, metavar="PATH",
                    help="write the traced demo's Prometheus metrics "
                         "snapshot to PATH")
    args = ap.parse_args()
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    if args.trace or args.metrics:
        _traced_demo(args.trace, args.metrics)
    doc = {"smoke": bool(args.smoke), "suites": {}, "rows": {}}
    print("name,us_per_call,derived")
    t0 = time.time()
    try:
        rows = run()
    except Exception as e:   # mirror benchmarks.run: emit the doc, exit 1
        print(f"serving/ERROR,,{type(e).__name__}:{e}")
        doc["suites"]["serving"] = {"error": f"{type(e).__name__}:{e}"}
        rows = []
    for key, val in rows:
        doc["rows"][key] = float(val)
        print(f"{key},,{val}")
    if rows:
        doc["suites"]["serving"] = {"seconds": round(time.time() - t0, 2)}
        print(f"serving/_suite_seconds,,{time.time() - t0:.1f}", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}", file=sys.stderr)
    if "error" in doc["suites"].get("serving", {}):
        sys.exit(1)


if __name__ == "__main__":
    main()
