"""Continuous-batching slot scheduler: parity against one-shot
``eval_many`` / the oracle over random arrival interleavings (both
engines, including under interleaved updates at snapshot epochs),
admission backpressure, deadline preemption, incremental pair streaming,
the dynamic PlanBundle slot allocator, the async serving layer, and the
``benchmarks/compare.py`` perf-regression gate."""
import asyncio
import random

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.engines import PlanBundle, Query, eval_many, make_engine
from repro.core.fixtures import random_graph
from repro.core.oracle import eval_oracle
from repro.core.scheduler import (AsyncServer, Backpressure, QueryTicket,
                                  SlotScheduler)

EXPRS = ["0/1*", "(0|1)/2", "2+", "^1/0*", "0/1/2", "(0|2)*"]


def _random_query(rnd, V):
    expr = rnd.choice(EXPRS)
    shape = rnd.randrange(4)
    if shape == 0:
        return Query(expr, obj=rnd.randrange(V))
    if shape == 1:
        return Query(expr, subject=rnd.randrange(V))
    if shape == 2:
        return Query(expr, subject=rnd.randrange(V), obj=rnd.randrange(V))
    return Query(expr)            # unanchored — delegated synchronously


# ---------------------------------------------------------------------
# THE acceptance property: continuous admission/retirement returns
# exactly the one-shot eval_many answer sets, on both engines
# ---------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_scheduler_matches_eval_many_random_interleavings(seed):
    rnd = random.Random(seed)
    g = random_graph(12, 3, 40, seed=1 + seed % 7, pred_zipf=False)
    queries = [_random_query(rnd, g.num_nodes)
               for _ in range(rnd.randrange(4, 14))]
    for kind in ("ring", "dense"):
        eng = make_engine(g, kind)
        want = eval_many(make_engine(g, kind), queries)
        sched = SlotScheduler(eng, max_slots=rnd.randrange(1, 5))
        tickets: list = []
        i = 0
        # random arrival interleaving: submissions and ticks in any order
        while i < len(queries) or sched.pending():
            if i < len(queries) and rnd.random() < 0.5:
                tickets.append(sched.submit(queries[i]))
                i += 1
            else:
                sched.step()
        for q, t, w in zip(queries, tickets, want):
            assert t.result() == w, (kind, q)
            # streaming soundness: for unlimited queries the drained
            # pairs union to exactly the final answer
            if q.limit is None:
                assert t._emitted == w, (kind, q)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_scheduler_snapshot_isolation_under_updates(seed):
    """Interleave submit / step / submit_update arbitrarily: every
    ticket's answer must equal the oracle on the *effective graph at the
    ticket's admission epoch* — in-flight queries are never torn by a
    concurrent write (copy-on-write overlay clone)."""
    rnd = random.Random(seed)
    g = random_graph(11, 3, 35, seed=2 + seed % 5, pred_zipf=False)
    V, P = g.num_nodes, g.num_preds
    for kind in ("ring", "dense"):
        eng = make_engine(g, kind)
        sched = SlotScheduler(eng, max_slots=2)
        snapshots = {0: eng.effective_graph()}
        issued = []            # (ticket, query)
        for _ in range(rnd.randrange(10, 30)):
            op = rnd.random()
            if op < 0.45:
                issued.append((sched.submit(_random_query(rnd, V)), None))
                issued[-1] = (issued[-1][0], issued[-1][0].query)
            elif op < 0.65:
                adds = [(rnd.randrange(V), rnd.randrange(P),
                         rnd.randrange(V))
                        for _ in range(rnd.randrange(1, 3))]
                rems = [(rnd.randrange(V), rnd.randrange(P),
                         rnd.randrange(V))]
                ep = sched.submit_update(add=adds, remove=rems)
                snapshots[ep] = eng.effective_graph()
            else:
                sched.step()
        sched.drain()
        for ticket, q in issued:
            want = eval_oracle(snapshots[ticket.epoch], q.expr,
                               q.subject, q.obj)
            assert ticket.result() == want, (kind, q, ticket.epoch)


# ---------------------------------------------------------------------
# admission control, deadlines, streaming, limits
# ---------------------------------------------------------------------

def test_backpressure_rejects_at_max_queue():
    g = random_graph(10, 2, 20, seed=2, pred_zipf=False)
    sched = SlotScheduler(make_engine(g, "ring"), max_slots=1, max_queue=2)
    sched.submit(Query("0/1*", obj=1))
    sched.submit(Query("0/1*", obj=2))
    with pytest.raises(Backpressure):
        sched.submit(Query("0/1*", obj=3))
    assert sched.rejected == 1
    sched.drain()
    # queue drained -> admission opens again
    t = sched.submit(Query("0/1*", obj=3))
    sched.drain()
    assert t.result() == eval_oracle(g, "0/1*", None, 3)


def test_deadline_preempts_in_flight_slot_and_spares_stragglers():
    g = random_graph(12, 3, 40, seed=6, pred_zipf=False)
    clk = [0.0]
    for kind in ("ring", "dense"):
        sched = SlotScheduler(make_engine(g, kind), max_slots=1,
                              clock=lambda: clk[0])
        clk[0] = 0.0
        slow = sched.submit(Query("(0|1|2)*", obj=5), deadline_s=1.0)
        fast = sched.submit(Query("0/1*", obj=3))
        sched.step()                  # admits `slow` into the only slot
        assert slow.state == "running"
        clk[0] = 2.0                  # past the deadline mid-flight
        sched.drain()
        with pytest.raises(TimeoutError):
            slow.result()
        assert sched.preempted == 1 and sched.in_flight == 0
        # the preemption freed the slot for the query queued behind it
        assert fast.result() == eval_oracle(g, "0/1*", None, 3), kind


def test_deadline_expires_queued_ticket_before_admission():
    g = random_graph(10, 2, 20, seed=2, pred_zipf=False)
    clk = [0.0]
    sched = SlotScheduler(make_engine(g, "ring"), clock=lambda: clk[0])
    t = sched.submit(Query("0/1*", obj=1), deadline_s=0.5)
    clk[0] = 1.0
    sched.drain()
    with pytest.raises(TimeoutError):
        t.result()


def test_limit_queries_do_not_stream_and_truncate_sorted():
    g = random_graph(12, 3, 45, seed=19, pred_zipf=False)
    full = sorted(eval_oracle(g, "0/1*", None, 3))
    assert len(full) >= 2, "fixture must have enough results to truncate"
    for kind in ("ring", "dense"):
        sched = SlotScheduler(make_engine(g, kind))
        t = sched.submit(Query("0/1*", obj=3, limit=2))
        sched.drain()
        # a limited answer is the sorted prefix, so partial pairs cannot
        # stream (the first k discovered are not the k smallest)
        assert t.new_pairs() == []
        assert t.result() == set(full[:2]), kind


def test_result_cache_hit_completes_without_occupying_a_slot():
    g = random_graph(10, 2, 20, seed=2, pred_zipf=False)
    sched = SlotScheduler(make_engine(g, "ring"))
    a = sched.submit(Query("0/1*", obj=1))
    sched.drain()
    b = sched.submit(Query("0/1*", obj=1))
    sched.step()
    assert b.done and b.result() == a.result()
    assert sched.cache_hits == 1 and sched.admitted == 1


# ---------------------------------------------------------------------
# dynamic PlanBundle slots
# ---------------------------------------------------------------------

def test_plan_bundle_dynamic_slots_reuse_freed_blocks():
    class _G:                      # minimal stand-in with a state count
        def __init__(self, m):
            self.m = m

    class _P:
        def __init__(self, m):
            self.g = _G(m)

    b = PlanBundle.empty()
    p1, p2, p3 = _P(2), _P(6), _P(2)
    off1 = b.add_slot(p1, p1.g.m + 1)        # bucket 4
    off2 = b.add_slot(p2, p2.g.m + 1)        # bucket 8
    assert (off1, off2) == (0, 4)
    assert b.padded_total >= b.S_total
    b.free_slot(p1)
    # freed bucket-4 block is reused before growing the bundle
    assert b.add_slot(p3, p3.g.m + 1) == off1
    assert len(b.live_plans()) == 2
    # refcounting: the same plan object admitted twice frees once
    off2b = b.add_slot(p2, p2.g.m + 1)
    assert off2b == off2
    b.free_slot(p2)
    assert any(p is p2 for p, _ in b.live_plans())
    b.free_slot(p2)
    assert not any(p is p2 for p, _ in b.live_plans())


def test_plan_bundle_static_build_rejects_slot_ops():
    class _G:
        def __init__(self, m):
            self.m = m

    class _P:
        def __init__(self, m):
            self.g = _G(m)

    b = PlanBundle.build([_P(2)], [3])
    with pytest.raises(ValueError):
        b.add_slot(_P(2), 3)


# ---------------------------------------------------------------------
# async serving layer
# ---------------------------------------------------------------------

def test_async_server_streams_pairs_and_settles():
    g = random_graph(12, 3, 40, seed=6, pred_zipf=False)
    eng = make_engine(g, "dense")

    async def main():
        async with AsyncServer(SlotScheduler(eng, max_slots=2)) as server:
            t1 = await server.submit(Query("0/1*", obj=3))
            t2 = await server.submit(Query("(0|1)/2", subject=2))
            streamed = [p async for p in t1]
            return streamed, await t1.result(), await t2.result()

    streamed, r1, r2 = asyncio.run(main())
    assert set(streamed) == r1 == eval_oracle(g, "0/1*", None, 3)
    assert r2 == eval_oracle(g, "(0|1)/2", 2, None)


def test_async_server_interleaves_updates():
    g = random_graph(11, 3, 35, seed=23, pred_zipf=False)
    eng = make_engine(g, "ring")

    async def main():
        sched = SlotScheduler(eng, max_slots=2)
        async with AsyncServer(sched) as server:
            before = eng.effective_graph()
            t1 = await server.submit(Query("0/1*", obj=3))
            server.submit_update(add=[(0, 1, 3), (2, 0, 1)])
            after = eng.effective_graph()
            t2 = await server.submit(Query("0/1*", obj=3))
            return before, after, await t1.result(), await t2.result(), t1, t2

    before, after, r1, r2, t1, t2 = asyncio.run(main())
    assert r1 == eval_oracle(before if t1.ticket.epoch == 0 else after,
                             "0/1*", None, 3)
    assert t2.ticket.epoch == 1
    assert r2 == eval_oracle(after, "0/1*", None, 3)


# ---------------------------------------------------------------------
# observability: latency attribution, spans, metrics endpoint
# ---------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_latency_attribution_sums_under_random_interleavings(seed):
    """For every settled ticket, queue_wait_s + service_s equals the
    end-to-end latency (finished_at - submitted_at) under the injectable
    clock — across random submit/tick interleavings, cache hits,
    delegated queries, and both engines."""
    rnd = random.Random(seed)
    g = random_graph(12, 3, 40, seed=1 + seed % 7, pred_zipf=False)
    clk = [0.0]
    for kind in ("ring", "dense"):
        sched = SlotScheduler(make_engine(g, kind),
                              max_slots=rnd.randrange(1, 4),
                              clock=lambda: clk[0])
        queries = [_random_query(rnd, g.num_nodes)
                   for _ in range(rnd.randrange(3, 9))]
        tickets = []
        i = 0
        while i < len(queries) or sched.pending():
            clk[0] += rnd.random() * 0.01    # time passes between events
            if i < len(queries) and rnd.random() < 0.5:
                tickets.append(sched.submit(queries[i]))
                i += 1
            else:
                sched.step()
        for t in tickets:
            assert t.state == "done"
            s = t.stats
            assert s.queue_wait_s >= 0.0 and s.service_s >= 0.0
            assert s.queue_wait_s + s.service_s == pytest.approx(
                t.finished_at - t.submitted_at, rel=1e-12, abs=1e-12)
            # superstep dispatch time is a sub-interval of service
            assert s.supersteps_s <= s.service_s + 1e-12


def test_zero_slack_deadline_preempts_deterministically():
    """now == deadline preempts (the >= comparison) — both a queued
    ticket and one holding a slot — and preempted tickets record their
    queue wait in the metrics."""
    g = random_graph(12, 3, 40, seed=6, pred_zipf=False)
    clk = [0.0]
    sched = SlotScheduler(make_engine(g, "ring"), max_slots=1,
                          clock=lambda: clk[0])
    # mid-flight: admitted at 0.0, clock lands exactly on the deadline
    running = sched.submit(Query("(0|1|2)*", obj=5), deadline_s=1.0)
    sched.step()
    assert running.state == "running"
    # queued: the only slot is held, so this one waits in the queue
    queued = sched.submit(Query("0/1*", obj=3), deadline_s=1.0)
    clk[0] = 1.0
    sched.step()
    for t in (running, queued):
        assert t.state == "failed"
        with pytest.raises(TimeoutError):
            t.result()
    assert sched.preempted == 2
    assert queued.stats.queue_wait_s == pytest.approx(1.0)
    snap = sched.metrics_snapshot()
    assert snap["rpq_preempted_queue_wait_seconds"]["count"] == 2
    assert snap["rpq_preempted_queue_wait_seconds"]["max"] >= 1.0


def test_spans_cover_scheduler_and_both_engines():
    """A traced drain produces admission, harvest, and retire spans —
    plus the engine's own superstep span — for ring AND dense, and the
    result is a valid Chrome trace document."""
    import json
    from repro.obs import trace as otrace
    g = random_graph(12, 3, 40, seed=6, pred_zipf=False)
    for kind, eng_span in (("ring", "ring.superstep"),
                           ("dense", "dense.superstep")):
        tr = otrace.Tracer()
        tr.enable()
        with otrace.use(tr):
            sched = SlotScheduler(make_engine(g, kind), max_slots=2)
            sched.submit(Query("0/1*", obj=3))
            sched.submit(Query("(0|1)/2", subject=2))
            sched.drain()
        names = {e["name"] for e in tr.events}
        assert {"scheduler.tick", "scheduler.admit", "scheduler.harvest",
                "scheduler.retire", eng_span} <= names, (kind, names)
        assert "scheduler.superstep" not in names
        json.dumps(tr.chrome_trace())         # schema is JSON-able
    # and with the (default-off) module tracer, the same drain records
    # nothing and allocates no spans
    sched = SlotScheduler(make_engine(g, "ring"), max_slots=2)
    from repro.obs.trace import NULL_SPAN, TRACER
    assert not TRACER.enabled
    sched.submit(Query("0/1*", obj=3))
    sched.drain()
    assert TRACER.events == []


def _inside(inner, outer):
    return outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def _parent(ev, events, name):
    """The ``name`` span that contains ``ev`` (nested by time)."""
    found = [e for e in events if e["name"] == name and _inside(ev, e)]
    assert len(found) == 1, (ev["name"], name, len(found))
    return found[0]


def test_dense_tick_spans_nest_inside_the_tick():
    """A traced dense drain breaks each tick into restack, upload, the
    device wait and download, each inside ``dense.superstep`` inside
    ``scheduler.tick``; the harvest holds every slot retirement, and
    the retired superstep span is gone."""
    from repro.obs import trace as otrace
    g = random_graph(12, 3, 40, seed=6, pred_zipf=False)
    tr = otrace.Tracer()
    tr.enable()
    with otrace.use(tr):
        sched = SlotScheduler(make_engine(g, "dense"), max_slots=3)
        for q in (Query("0/1*", obj=3), Query("(0|1)/2", subject=2),
                  Query("2+", subject=1, obj=4), Query("^1/0*", obj=5)):
            sched.submit(q)
        sched.drain()
    evs = tr.events
    names = [e["name"] for e in evs]
    assert "scheduler.superstep" not in names
    phases = ("dense.restack", "dense.upload", "dense.bfs_chunk",
              "dense.download")
    counts = {n: names.count(n) for n in phases}
    assert counts["dense.bfs_chunk"] > 0
    assert len(set(counts.values())) == 1, counts    # one each per dispatch
    for e in evs:
        if e["name"] in phases:
            sup = _parent(e, evs, "dense.superstep")
            _parent(sup, evs, "scheduler.tick")
        if e["name"] == "scheduler.harvest":
            _parent(e, evs, "scheduler.tick")
            assert e["args"]["slots"] >= 1
    # the four phases of one dispatch run in order
    for chunk in (e for e in evs if e["name"] == "dense.bfs_chunk"):
        sup = _parent(chunk, evs, "dense.superstep")
        mine = sorted((e for e in evs if e["name"] in phases
                       and _inside(e, sup)), key=lambda e: e["ts"])
        assert [e["name"] for e in mine] == list(phases)
    retires = [e for e in evs if e["name"] == "scheduler.retire"]
    assert len(retires) == 4
    in_harvest = [e for e in retires
                  if any(h["name"] == "scheduler.harvest" and _inside(e, h)
                         for h in evs)]
    assert in_harvest and all("useful" in e["args"] for e in in_harvest)


@pytest.mark.parametrize("live", [False, True], ids=["read-only",
                                                     "overlay"])
def test_dense_chunk_span_counts_sorted_and_tail_rows(live):
    """``dense.bfs_chunk`` says how many edge rows its superstep reduced
    without a scatter (``sorted_rows``) and how many it still scattered
    (``tail_rows``): together the rows swept.  A read-only engine
    scatters none; a live overlay scatters its insert buffer only, and
    the answers stay the oracle's."""
    from repro.obs import trace as otrace
    g = random_graph(12, 3, 40, seed=6, pred_zipf=False)
    eng = make_engine(g, "dense")
    if live:
        eng.add_edges([(0, 0, 5), (3, 1, 7), (11, 2, 0)])
        eng.remove_edges([(int(g.s[0]), int(g.p[0]), int(g.o[0]))])
    queries = [Query("0/1*", obj=3), Query("(0|1)/2", subject=2),
               Query("2+", subject=1, obj=4), Query("^1/0*", obj=5)]
    tr = otrace.Tracer()
    tr.enable()
    with otrace.use(tr):
        sched = SlotScheduler(eng, max_slots=3)
        tickets = [sched.submit(q) for q in queries]
        sched.drain()
    E_base = int(eng.dg.subj.shape[0])
    E = int(eng._edges().subj.shape[0])
    assert (E > E_base) == live
    chunks = [e["args"] for e in tr.events if e["name"] == "dense.bfs_chunk"]
    assert chunks
    for c in chunks:
        assert c["sorted_rows"] == E_base
        assert c["tail_rows"] == E - E_base
        assert c["swept"] == c["rows"] * (c["sorted_rows"] + c["tail_rows"]) \
            * c["width"]
        assert (c["tail_rows"] > 0) == live
    eff = eng.effective_graph()
    for q, t in zip(queries, tickets):
        assert t.result() == eval_oracle(eff, q.expr, q.subject, q.obj), q


def test_admit_and_retire_spans_share_a_request_id():
    from repro.obs import trace as otrace
    g = random_graph(12, 3, 40, seed=6, pred_zipf=False)
    now = [0.0]
    tr = otrace.Tracer()
    tr.enable()
    with otrace.use(tr):
        sched = SlotScheduler(make_engine(g, "ring"), max_slots=1,
                              clock=lambda: now[0])
        tickets = [sched.submit(Query(e, obj=3)) for e in ("0/1*", "2+",
                                                           "(0|1)/2")]
        now[0] = 0.25
        sched.drain()
    assert [t.rid for t in tickets] == [0, 1, 2]
    admits = {e["args"]["rid"]: e["args"] for e in tr.events
              if e["name"] == "scheduler.admit"}
    retires = {e["args"]["rid"]: e["args"] for e in tr.events
               if e["name"] == "scheduler.retire"}
    assert sorted(admits) == sorted(retires) == [0, 1, 2]
    for t in tickets:
        assert admits[t.rid]["expr"] == retires[t.rid]["expr"] == t.query.expr
        assert admits[t.rid]["queue_wait_ms"] == pytest.approx(
            t.stats.queue_wait_s * 1e3)
    # the first waited from submission to the first tick; the others
    # for the one slot as well
    assert admits[0]["queue_wait_ms"] == pytest.approx(250.0)
    assert "useful" not in retires[0]            # the ring counts no sweep


def test_async_server_flush_is_a_span():
    from repro.obs import trace as otrace
    g = random_graph(12, 3, 40, seed=6, pred_zipf=False)
    eng = make_engine(g, "dense")
    tr = otrace.Tracer()
    tr.enable()

    async def main():
        async with AsyncServer(SlotScheduler(eng, max_slots=2)) as server:
            t1 = await server.submit(Query("0/1*", obj=3))
            t2 = await server.submit(Query("(0|1)/2", subject=2))
            return await t1.result(), await t2.result()

    with otrace.use(tr):
        r1, r2 = asyncio.run(main())
    assert r1 == eval_oracle(g, "0/1*", None, 3)
    assert r2 == eval_oracle(g, "(0|1)/2", 2, None)
    flushes = [e for e in tr.events if e["name"] == "server.flush"]
    assert flushes and all(1 <= e["args"]["tickets"] <= 2 for e in flushes)
    # a flush follows its tick, never inside it
    ticks = [e for e in tr.events if e["name"] == "scheduler.tick"]
    assert not any(_inside(f, t) for f in flushes for t in ticks)


def test_async_server_metrics_endpoint_scrapes():
    g = random_graph(10, 2, 20, seed=2, pred_zipf=False)
    eng = make_engine(g, "dense")

    async def main():
        sched = SlotScheduler(eng, max_slots=2)
        async with AsyncServer(sched, metrics_port=0) as server:
            t = await server.submit(Query("0/1*", obj=1))
            await t.result()
            host, port = server.metrics_addr
            reader, writer = await asyncio.open_connection(host, port)
            writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
            await writer.drain()
            data = await reader.read()
            writer.close()
            return data.decode()

    text = asyncio.run(main())
    head, body = text.split("\r\n\r\n", 1)
    assert "200 OK" in head
    assert "rpq_completed_total 1" in body
    assert 'rpq_e2e_seconds{quantile="0.5"}' in body


# ---------------------------------------------------------------------
# benchmarks/compare.py — the perf-regression gate
# ---------------------------------------------------------------------

def _compare_mod():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import compare
    return compare


def test_compare_gate_fails_on_injected_slowdown(tmp_path):
    compare = _compare_mod()
    prev = {"smoke": True, "suites": {}, "rows": {
        "serving/dense/qps100/slot_p99_ms": 10.0,
        "serving/dense/qps100/p99_speedup": 4.0,
        "updates/ingest/us_per_edge": 100.0,
        "updates/query/overlay64/overlay_rows": 64.0,   # not gated
    }}
    good = {"smoke": True, "suites": {}, "rows": {
        **prev["rows"],
        "serving/dense/qps100/slot_p99_ms": 12.0,       # +20% — within 25%
        "new/only_in_current_us": 5.0,                  # no baseline: skips
    }}
    bad = {"smoke": True, "suites": {}, "rows": {
        **prev["rows"],
        "serving/dense/qps100/slot_p99_ms": 12.6,       # +26% — regression
        "serving/dense/qps100/p99_speedup": 2.9,        # -27.5% — regression
        "updates/query/overlay64/overlay_rows": 1e9,    # ignored: not gated
    }}
    import json
    pf = tmp_path / "prev.json"
    pf.write_text(json.dumps(prev))
    gf = tmp_path / "good.json"
    gf.write_text(json.dumps(good))
    bf = tmp_path / "bad.json"
    bf.write_text(json.dumps(bad))
    assert compare.main(["--current", str(gf), "--previous", str(pf)]) == 0
    assert compare.main(["--current", str(bf), "--previous", str(pf)]) == 1
    regs = compare.compare_rows(prev["rows"], bad["rows"])
    assert {k for k, *_ in regs} == {"serving/dense/qps100/slot_p99_ms",
                                     "serving/dense/qps100/p99_speedup"}


def test_compare_gate_skips_without_previous(tmp_path, capsys, monkeypatch):
    compare = _compare_mod()
    import json
    cf = tmp_path / "cur.json"
    cf.write_text(json.dumps({"smoke": True, "suites": {}, "rows": {}}))
    # missing file baseline
    assert compare.main(["--current", str(cf),
                         "--previous", str(tmp_path / "absent.json")]) == 0
    # --fetch-previous without credentials
    monkeypatch.delenv("GITHUB_TOKEN", raising=False)
    monkeypatch.delenv("GITHUB_REPOSITORY", raising=False)
    assert compare.main(["--current", str(cf), "--fetch-previous"]) == 0
    out = capsys.readouterr().out
    assert "SKIPPED" in out
