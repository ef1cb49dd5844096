"""Continuous-batching RPQ serving: slot scheduler + async streaming,
with live graph updates interleaved into the same stream.

    PYTHONPATH=src python examples/serve_rpq.py
    # mesh-sharded: partition the batched BFS over 4 forced host devices
    PYTHONPATH=src python examples/serve_rpq.py --force-host-devices 4 --shards 4

The full serving stack the engines are built for — since the slot
scheduler landed, this is *continuous* batching, not bucket flushing:

  * requests arrive one at a time on an asyncio loop and join the
    in-flight wavefront **between supersteps** — a pool of ``max_slots``
    fixed-capacity slots (:class:`repro.core.scheduler.SlotScheduler`),
    so a new request never waits for the current batch to drain, and a
    finished request frees its slot the superstep it converges (no
    head-of-line blocking behind a slow automaton);
  * every occupied slot advances in the SAME batched dispatch per
    superstep (heterogeneous plan bundles, pow2 slot-bucket padding
    keeps compiled signatures bounded under churn), and each slot
    *streams* newly-discovered result pairs back through an async
    iterator while its BFS is still running;
  * a replayed request never reaches the BFS at all — it is answered
    straight from the result cache;
  * **graph mutations** (``submit_update``) ride the same stream with
    *snapshot isolation per query*: the live overlay is swapped for a
    copy-on-write clone before the mutation applies, so in-flight slots
    keep reading their admission epoch — writes never stall reads, and
    every ticket records the epoch its answer is exact at;
  * the AsyncServer's HTTP sidecar serves ``/metrics`` (Prometheus),
    ``/flight`` (the always-on flight-recorder ring as a versioned
    JSONL workload), and ``/explain?expr=...`` (per-query plan report)
    — the timed wave scrapes all three;
  * ``--record PATH`` dumps the timed wave's flight recorder as a
    replayable workload (``python -m benchmarks.replay --workload
    PATH``); ``--explain`` prints a full EXPLAIN and ANALYZE report for
    one representative request.
"""
import argparse
import asyncio
import json
import sys
import time
from urllib.parse import quote

sys.path.insert(0, "src")

_ap = argparse.ArgumentParser()
_ap.add_argument("--shards", type=int, default=None,
                 help="partition the batched BFS over N devices "
                      "(make_engine(..., shards=N))")
_ap.add_argument("--force-host-devices", type=int, default=None,
                 help="force N virtual CPU devices (must be set before "
                      "jax imports, hence an argument of this script)")
_ap.add_argument("--slots", type=int, default=8,
                 help="in-flight slot pool size")
_ap.add_argument("--trace", default=None, metavar="PATH",
                 help="enable the obs span tracer for the timed waves and "
                      "export Chrome trace-event JSON to PATH (open in "
                      "Perfetto / chrome://tracing)")
_ap.add_argument("--record", default=None, metavar="PATH",
                 help="dump the timed wave's flight recorder as a "
                      "versioned JSONL workload (replay it with "
                      "`python -m benchmarks.replay --workload PATH`)")
_ap.add_argument("--explain", action="store_true",
                 help="print an EXPLAIN (plan only, no execution) and an "
                      "ANALYZE (plan + per-superstep timeline) report for "
                      "one representative request")
ARGS = _ap.parse_args()
# repro.launch.env imports no jax: both calls must precede the first jax
# import
from repro.launch.env import force_host_devices, use_compile_cache

use_compile_cache()
if ARGS.force_host_devices:
    # per-flag setdefault: appending to XLA_FLAGS by hand here used to
    # duplicate the flag on every invocation that inherited a non-empty
    # XLA_FLAGS
    force_host_devices(ARGS.force_host_devices)

import numpy as np

from repro import obs
from repro.core.engines import Query, QueryStats, make_engine
from repro.core.fixtures import scale_free_graph
from repro.core.scheduler import AsyncServer, SlotScheduler


async def _serve_wave(server: AsyncServer, queries, stagger_s: float):
    """Submit ``queries`` as a trickle-then-burst arrival pattern and
    await every final answer; returns (answers, per-request latencies,
    settled tickets)."""
    async def one(i, q):
        await asyncio.sleep((i % 8) * stagger_s)   # 8 staggered arrival slots
        t0 = time.monotonic()
        ticket = await server.submit(q)
        ans = await ticket.result()
        return ans, time.monotonic() - t0, ticket.ticket

    out = await asyncio.gather(*(one(i, q) for i, q in enumerate(queries)))
    return ([a for a, _, _ in out], [lat for _, lat, _ in out],
            [t for _, _, t in out])


def _p(lat, q):
    return sorted(lat)[min(len(lat) - 1, int(q * len(lat)))] * 1e3


def main():
    g = scale_free_graph(3000, 8, 24000, seed=23)
    eng = make_engine(g, "dense", source_batch=16, shards=ARGS.shards)
    if eng.sharded is not None:
        print(f"mesh-sharded engine: {eng.sharded.num_shards} shards over "
              f"axes {eng.sharded.data_axes}")

    # 96 "requests": 6 expressions of different shapes/sizes x 16 endpoints
    # -> the in-flight slot pool is a *mixed-automaton* batch
    rng = np.random.default_rng(0)
    exprs = ["0/1*/2", "(0|3)+", "^1/0*", "4", "(2/5)|(0/1)", "6+/7"]
    queries = [Query(e, obj=int(o))
               for e in exprs
               for o in rng.integers(0, g.num_nodes, 16)]

    # warm up untimed with the real slot shapes: the batched BFS traces
    # per (chunk, S_pad) shape, so a token warm-up would leave compilation
    # in the timed run.  Then clear the result cache so the timed wave
    # measures real evaluation, not replay.
    warm = SlotScheduler(eng, max_slots=ARGS.slots)
    for q in queries:
        warm.submit(q)
    warm.drain()
    eng.results.clear()

    if ARGS.trace:
        # trace the timed waves only — warm-up compilation would bury
        # the serving spans
        obs.trace.TRACER.enable()

    # the timed wave also exercises the HTTP sidecar: the AsyncServer
    # binds a free port (metrics_port=0) and we scrape /metrics,
    # /flight, and /explain over plain HTTP once the wave settles
    sched = SlotScheduler(eng, max_slots=ARGS.slots)
    targets = ("/metrics", "/flight",
               "/explain?expr=" + quote(queries[0].expr, safe="")
               + f"&obj={queries[0].obj}")
    t0 = time.time()
    answers, lat, tickets, scraped = asyncio.run(
        _run_wave(sched, queries, stagger_s=0.002, metrics_port=0,
                  scrape=targets))
    dt = time.time() - t0
    print(f"served {len(queries)} RPQ requests ({len(exprs)} mixed exprs) "
          f"through {ARGS.slots} continuous-batching slots: "
          f"{dt*1e3:.1f} ms total, p50 {_p(lat, 0.50):.2f} / "
          f"p99 {_p(lat, 0.99):.2f} ms request latency")

    # per-phase latency attribution, merged over every settled ticket
    # (one formatting path: QueryStats.merge + as_dict)
    d = QueryStats.merge(t.stats for t in tickets).as_dict()
    n = len(tickets)
    print(f"latency attribution over {n} tickets (mean/request): "
          f"queue wait {d['queue_wait_s']/n*1e3:.2f} ms, "
          f"service {d['service_s']/n*1e3:.2f} ms, "
          f"superstep dispatch {d['supersteps_s']/n*1e3:.2f} ms; "
          f"plan modes {d['plan_mode'] or 'n/a'}, "
          f"{d['results']} result pairs")

    print("scheduler metrics, scraped from the AsyncServer endpoint "
          "(Prometheus text exposition):")
    body = scraped["/metrics"].split("\r\n\r\n", 1)[1]
    print("\n".join(line for line in body.splitlines()
                    if line and not line.startswith("#")))

    # /flight serves the recorder ring as the versioned JSONL workload
    flight = scraped["/flight"].split("\r\n\r\n", 1)[1]
    fh = json.loads(flight.splitlines()[0])
    print(f"flight recorder over /flight: {fh['records']} records "
          f"(kind {fh['kind']} v{fh['version']}, "
          f"{fh['appended']} appended / {fh['dropped']} dropped)")
    plan = json.loads(scraped[targets[2]].split("\r\n\r\n", 1)[1])
    print(f"plan report over /explain for {queries[0].expr!r}: "
          f"mode {plan['plan']['mode']}, "
          f"{plan['automaton']['states']} automaton states, "
          f"est frontier {plan['plan']['est_frontier']}")

    if ARGS.record:
        # epoch-0 capture (pre-update waves): replays bit-for-bit against
        # the same fixture spec carried in the header
        sched.recorder.dump(ARGS.record, graph={
            "fixture": "scale_free_graph", "args": [3000, 8, 24000],
            "seed": 23})
        print(f"recorded {sched.recorder.occupancy} settled queries to "
              f"{ARGS.record} — replay with "
              f"`python -m benchmarks.replay --workload {ARGS.record}`")

    if ARGS.explain:
        q = queries[0]
        print(f"EXPLAIN {q.expr!r} (plan only, no execution):")
        print(json.dumps(eng.explain(q), indent=2, sort_keys=True))
        report = eng.explain(q, analyze=True)
        tl = report["execution"]["timeline"]
        print(f"ANALYZE {q.expr!r}: {report['execution']['results']} pairs "
              f"in {report['execution']['elapsed_ms']:.2f} ms, "
              f"{report['execution']['supersteps']} supersteps, "
              f"frontier est {report['execution']['est_frontier']} vs "
              f"actual {report['execution']['actual_frontier']} "
              f"(error {report['execution']['frontier_error']:+.2f}); "
              f"timeline frontiers "
              f"{[row['frontier'] for row in tl]}")

    # replay the exact stream: every answer comes from the result cache
    res_h0, res_m0 = eng.results.hits, eng.results.misses
    sched2 = SlotScheduler(eng, max_slots=ARGS.slots)
    t0 = time.time()
    replay, _, _, _ = asyncio.run(_run_wave(sched2, queries, stagger_s=0.0))
    dt_replay = time.time() - t0
    assert replay == answers
    print(f"replayed the stream from the result cache: "
          f"{dt_replay*1e3:.1f} ms total "
          f"({eng.results.hits - res_h0} hits / "
          f"{eng.results.misses - res_m0} misses)")

    # streaming: pairs arrive through the async iterator while the slot's
    # BFS is still running — the consumer sees them before result()
    async def stream_one():
        # fresh engine (empty result cache) so the pairs really stream
        # out of a live BFS rather than replaying a cached answer
        sched3 = SlotScheduler(make_engine(g, "dense", source_batch=16),
                               max_slots=2)
        demo = max(range(len(queries)), key=lambda i: len(answers[i]))
        async with AsyncServer(sched3) as server:
            ticket = await server.submit(queries[demo])
            seen = [pair async for pair in ticket]
            final = await ticket.result()
        return demo, seen, final

    demo, seen, final = asyncio.run(stream_one())
    assert set(seen) == final
    print(f"streamed {len(seen)} pairs incrementally for request {demo}; "
          f"union equals the final answer: ok.")

    # validate a few against the faithful engine
    ring_eng = make_engine(g, "ring")
    for i in [0, 17, 41, 90]:
        q = queries[i]
        want = ring_eng.eval(q.expr, obj=q.obj)
        assert answers[i] == want, (i, len(answers[i]), len(want))
    print("spot-checked 4 requests against the ring engine: agree. ok.")

    # live updates: interleave mutations into the same stream.  Writes
    # build the next epoch on a copy-on-write overlay clone while
    # in-flight slots keep reading their admission snapshot — each
    # ticket's .epoch records the version its answer is exact at.
    rng = np.random.default_rng(7)
    sched4 = SlotScheduler(eng, max_slots=ARGS.slots)
    inv0, ep0 = eng.results.invalidations, eng.epoch

    async def mixed_wave():
        async with AsyncServer(sched4) as server:
            async def one(i):
                await asyncio.sleep((i % 8) * 0.002)
                if i % 5 == 0:   # every 5th arrival is a write, not a read
                    s, o = rng.integers(0, g.num_nodes, 2)
                    p = int(rng.integers(0, g.num_preds))
                    if i % 10 == 0:
                        server.submit_update(add=[(int(s), p, int(o))])
                    else:
                        server.submit_update(remove=[(int(s), p, int(o))])
                    return None
                q = queries[i % len(queries)]
                ticket = await server.submit(q)
                return q, await ticket.result(), ticket.ticket.epoch

            out = await asyncio.gather(*(one(i) for i in range(80)))
        return [x for x in out if x is not None]

    t0 = time.time()
    served = asyncio.run(mixed_wave())
    dt = time.time() - t0
    epochs = sorted({ep for _, _, ep in served})
    print(f"mixed update/query wave: {len(served)} queries + "
          f"{sched4.updates} updates in {dt*1e3:.1f} ms; "
          f"epoch {ep0} -> {eng.epoch}, answers served at epochs "
          f"{epochs[0]}..{epochs[-1]} (snapshot isolation); "
          f"{eng.results.invalidations - inv0} cached answers invalidated "
          f"(footprint-precise), overlay size {eng.delta.size}")

    # every answer from the mutated engine must equal a from-scratch
    # evaluation of the final effective graph ONLY for queries whose
    # footprint saw no mutation after them — the last-finished answers,
    # re-asked at the final epoch, are exactly rebuild-fresh:
    fresh = eng.eval_many([q for q, _, _ in served[-8:]])
    rebuilt = make_engine(eng.effective_graph(), "dense")
    want = rebuilt.eval_many([q for q, _, _ in served[-8:]])
    assert fresh == want
    print("final-epoch answers match a from-scratch rebuild: ok.")

    if ARGS.trace:
        tr = obs.trace.TRACER
        tr.export(ARGS.trace)
        print(f"exported {len(tr.events)} trace events to {ARGS.trace} "
              f"(load in https://ui.perfetto.dev)")


async def _run_wave(sched: SlotScheduler, queries, stagger_s: float,
                    metrics_port=None, scrape=("/metrics",)):
    """Serve the wave; with a bound sidecar port, also scrape each
    ``scrape`` target over plain HTTP -> {target: raw response}."""
    async with AsyncServer(sched, metrics_port=metrics_port) as server:
        answers, lat, tickets = await _serve_wave(server, queries, stagger_s)
        scraped = None
        if metrics_port is not None:
            scraped = {}
            host, port = server.metrics_addr
            for target in scrape:
                reader, writer = await asyncio.open_connection(host, port)
                writer.write(f"GET {target} HTTP/1.0\r\n\r\n".encode())
                await writer.drain()
                scraped[target] = (await reader.read()).decode()
                writer.close()
        return answers, lat, tickets, scraped


if __name__ == "__main__":
    main()
