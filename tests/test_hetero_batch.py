"""Heterogeneous-plan batched ``eval_many`` + the cross-request result
cache: padded/bundled batch results must be bit-identical to per-query
``eval`` on both engines, across mixed-size automata."""
import random

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.engines import PlanBundle, Query, ResultCache, make_engine
from repro.core.fixtures import metro_graph, random_graph
from repro.core.oracle import eval_oracle
from repro.core.ring import Ring
from repro.core.rpq import RingRPQ

# expression pool with automaton sizes m+1 from 2 to 9: crosses the dense
# engine's pow2 padding buckets (4 and 8) and gives the ring bundle
# distinct block widths
_MIXED_EXPRS = [
    "0", "^1", "0/1", "(0|2)", "2*/0", "^1/0*",
    "0/1/2*", "(0|1)/(2|0)+", "0/1/2/0*", "(0/1/2)|(2/1/0)",
]


def _mixed_batch(rnd, num_nodes, n):
    """All four query shapes over mixed-size expressions + one duplicate."""
    out = []
    for i in range(n):
        expr = _MIXED_EXPRS[rnd.randrange(len(_MIXED_EXPRS))]
        kind = i % 4
        if kind == 0:
            out.append(Query(expr, obj=rnd.randrange(num_nodes)))
        elif kind == 1:
            out.append(Query(expr, subject=rnd.randrange(num_nodes)))
        elif kind == 2:
            out.append(Query(expr, subject=rnd.randrange(num_nodes),
                             obj=rnd.randrange(num_nodes)))
        else:
            out.append(Query(expr))
    out.append(out[0])  # exact duplicate: collapses onto one evaluation
    return out


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_hetero_eval_many_matches_eval(seed):
    """Property: padded/bundled heterogeneous batches equal per-query eval
    (and the oracle) on both engines, across mixed-size automata."""
    rnd = random.Random(seed)
    V = rnd.randrange(8, 16)
    g = random_graph(V, 3, rnd.randrange(20, 60), seed=seed % 997,
                     pred_zipf=False)
    queries = _mixed_batch(rnd, V, 12)
    for kind in ("ring", "dense"):
        eng = make_engine(g, kind)
        batched = eng.eval_many(queries)
        for q, got in zip(queries, batched):
            want = eval_oracle(g, q.expr, subject=q.subject, obj=q.obj)
            assert got == want, (kind, q, sorted(got), sorted(want))
            assert eng.eval(q.expr, q.subject, q.obj) == got, (kind, q)


def test_hetero_ring_dense_cross_engine_parity():
    """Ring and dense engines agree on the same heterogeneous batch."""
    rnd = random.Random(424)
    g = random_graph(25, 3, 110, seed=24, pred_zipf=False)
    queries = _mixed_batch(rnd, 25, 32)
    ring_res = make_engine(g, "ring").eval_many(queries)
    dense_res = make_engine(g, "dense").eval_many(queries)
    assert ring_res == dense_res
    assert any(r for r in ring_res)


def test_hetero_dense_crosses_padding_buckets():
    """A batch whose automata straddle pow2 padding widths must dispatch
    the heterogeneous BFS and still match per-query eval."""
    g = random_graph(20, 3, 80, seed=31, pred_zipf=False)
    eng = make_engine(g, "dense")
    # m+1 = 2 (bucket 4) and m+1 = 9 (bucket 16) in one batch
    queries = [Query("0", obj=o) for o in range(4)] + \
              [Query("0/1/2/0/1/2/0/1", obj=o) for o in range(4)]
    res = eng.eval_many(queries)
    assert eng.hetero_dispatches > 0
    for q, got in zip(queries, res):
        assert got == eng.eval(q.expr, q.subject, q.obj), (q,)


def test_hetero_ring_kernel_bundle_fires():
    """kernel_threshold=1 must push the multi-plan wavefront through the
    block-diagonal nfa_step bundle (not per-plan fallbacks), with results
    identical to the scalar engine."""
    g = metro_graph()
    scalar = RingRPQ(Ring(g))
    kern = RingRPQ(Ring(g), kernel_threshold=1)
    queries = [Query("l5+/bus", obj=o) for o in range(g.num_nodes)] + \
              [Query("bus|(l5/l5)", obj=o) for o in range(g.num_nodes)]
    stats_out = []
    want = scalar.eval_many(queries)
    got = kern.eval_many(queries, stats_out=stats_out)
    assert got == want
    assert kern.bundle_kernel_batches > 0
    assert sum(s.kernel_tasks for s in stats_out) > 0


def test_plan_bundle_block_diagonal_layout():
    """Offsets tile the state space; the packed table confines each
    plan's transitions to its own block."""
    from repro.core.glushkov import build
    from repro.kernels.nfa_step import pack_block_diagonal
    gs = [build("0/1*"), build("(0|1)/0"), build("1")]   # S = 3, 4, 2
    bundle = PlanBundle.build(gs, [g.m + 1 for g in gs])
    assert bundle.offsets == [0, 3, 7]
    assert bundle.S_total == 9
    assert bundle.S_max == 4
    packed = pack_block_diagonal([g.pred_mask for g in gs],
                                 bundle.offsets, bundle.S_total)
    assert packed.shape == (bundle.S_total, (bundle.S_total + 31) // 32)
    # row (off + j) must only set bits inside [off, off + S_i)
    for g, off in zip(gs, bundle.offsets):
        S = g.m + 1
        block_mask = ((1 << S) - 1) << off
        for j in range(S):
            acc = 0
            for w in range(packed.shape[1]):
                acc |= int(packed[off + j, w]) << (32 * w)
            assert acc & ~block_mask == 0, (off, j)
            assert acc == g.pred_mask[j] << off, (off, j)


def test_result_cache_replay_and_counters():
    """Replayed eval_many answers come from the result cache, are equal,
    and are isolated from caller mutation."""
    g = metro_graph()
    for kind in ("ring", "dense"):
        eng = make_engine(g, kind)
        queries = [Query("l5+/bus", obj=o) for o in range(4)]
        first = eng.eval_many(queries)
        assert eng.results.hits == 0 and eng.results.misses == len(queries)
        first[0].add((-1, -1))  # caller mutation must not poison the cache
        replay = eng.eval_many(queries)
        assert eng.results.hits == len(queries), kind
        assert (-1, -1) not in replay[0]
        assert replay[1:] == first[1:]


def test_result_cache_ttl_and_lru_bounds():
    fake = [0.0]
    cache = ResultCache(max_entries=2, ttl_s=10.0, clock=lambda: fake[0])
    cache.put("a", {(1, 1)})
    cache.put("b", {(2, 2)})
    assert cache.get("a") == frozenset({(1, 1)})  # refreshes a to MRU
    cache.put("c", {(3, 3)})                      # evicts b (LRU), not a
    assert cache.get("b") is None
    assert cache.get("a") is not None
    assert cache.evictions == 1
    fake[0] = 11.0                                # TTL expires everything
    assert cache.get("a") is None
    assert cache.expirations == 1
    assert len(cache) <= 2


def test_result_cache_ttl_in_engine():
    """An engine with an expired result cache re-evaluates (and still
    returns the right answer)."""
    fake = [0.0]
    g = metro_graph()
    eng = make_engine(g, "dense",
                      result_cache=ResultCache(ttl_s=5.0,
                                               clock=lambda: fake[0]))
    q = [Query("l5+/bus", obj=3)]
    first = eng.eval_many(q)
    fake[0] = 100.0
    again = eng.eval_many(q)
    assert again == first
    assert eng.results.expirations == 1
    assert eng.results.misses == 2  # cold + post-expiry


def test_eval_many_stats_surface_result_cache():
    """Ring stats_out rows surface result-cache hits/misses per query."""
    g = metro_graph()
    eng = make_engine(g, "ring")
    queries = [Query("l5+/bus", obj=1), Query("l5+/bus", obj=1)]
    stats_out = []
    res = eng.eval_many(queries, stats_out=stats_out)
    assert [s.result_cache_misses for s in stats_out] == [1, 1]
    stats_out = []
    replay = eng.eval_many(queries, stats_out=stats_out)
    assert [s.result_cache_hits for s in stats_out] == [1, 1]
    assert replay == res
    assert [s.results for s in stats_out] == [len(r) for r in res]


# ---------------------------------------------------------------------
# the dense slot tick under the tracer: transfer bytes and sweep work
# ---------------------------------------------------------------------

def _traced_dense_drain(g, queries, max_slots):
    """Drain ``queries`` through a traced dense ``SlotScheduler``; also
    returns the slot handles in the order they were admitted."""
    from repro.core.scheduler import SlotScheduler
    from repro.obs import trace as otrace
    tr = otrace.Tracer()
    tr.enable()
    handles = []
    with otrace.use(tr):
        sched = SlotScheduler(make_engine(g, "dense"), max_slots=max_slots)
        admit = sched.slots.admit

        def keep(*args, **kw):
            handles.append(admit(*args, **kw))
            return handles[-1]

        sched.slots.admit = keep
        tickets = [sched.submit(q) for q in queries]
        sched.drain()
    return sched, tickets, tr.events, handles


def test_dense_tick_transfer_bytes_are_the_planes_nbytes():
    g = random_graph(20, 3, 80, seed=31, pred_zipf=False)
    queries = [Query("0", obj=o) for o in range(3)] + \
              [Query("0/1/2/0/1/2/0/1", obj=o) for o in range(2)]
    sched, _, evs, _ = _traced_dense_drain(g, queries, max_slots=5)
    eng = sched.engine
    V, L = g.num_nodes, eng.dg.num_labels
    ups = [e for e in evs if e["name"] == "dense.upload"]
    downs = [e for e in evs if e["name"] == "dense.download"]
    chunks = [e for e in evs if e["name"] == "dense.bfs_chunk"]
    restacks = [e for e in evs if e["name"] == "dense.restack"]
    assert len(ups) == len(downs) == len(chunks) == len(restacks) > 0
    E = int(eng.dg.subj.shape[0])
    for r, u, d, c in zip(restacks, ups, downs, chunks):
        C, S = r["args"]["rows"], r["args"]["width"]
        assert c["args"]["rows"] == C and c["args"]["width"] == S
        assert 1 <= r["args"]["live"] <= C
        # int8 planes: B [C, L+1, S], PRED [C, S, S], frontier and
        # visited [C, V, S] up; frontier and visited down
        assert u["args"]["bytes"] == C * (L + 1) * S + C * S * S \
            + 2 * C * V * S
        assert d["args"]["bytes"] == 2 * C * V * S
        assert c["args"]["swept"] == C * E * S
    assert {r["args"]["width"] for r in restacks} == {4, 16}
    assert eng.h2d_bytes == sum(u["args"]["bytes"] for u in ups)
    assert eng.d2h_bytes == sum(d["args"]["bytes"] for d in downs)
    snap = sched.metrics_snapshot()
    assert snap["rpq_dense_h2d_bytes_total"] == eng.h2d_bytes
    assert snap["rpq_dense_d2h_bytes_total"] == eng.d2h_bytes
    assert "rpq_dense_h2d_bytes_total" in sched.prometheus_text()


def test_useful_sweep_work_is_the_visited_in_degree():
    """``useful`` on a dense retirement is the sum, over the slot's
    visited (node, state) pairs, of the node's in-degree in the
    completed graph — and never more than the edge-states swept."""
    import numpy as np
    g = random_graph(25, 3, 110, seed=24, pred_zipf=False)
    queries = [Query("0/1*", obj=3), Query("(0|1)/(2|0)+", subject=2),
               Query("2*/0", obj=7), Query("^1/0*", subject=4)]
    sched, tickets, evs, handles = _traced_dense_drain(g, queries,
                                                       max_slots=2)
    assert len(handles) == len(queries)          # FIFO: one slot each
    _s, _p, o = g.completed_triples()
    indeg = np.bincount(o, minlength=g.num_nodes)
    retires = {e["args"]["rid"]: e["args"] for e in evs
               if e["name"] == "scheduler.retire"}
    for i, t in enumerate(tickets):
        vis = handles[i].visited
        want = sum(int(indeg[v]) for v, q in zip(*np.nonzero(vis)))
        assert retires[t.rid]["useful"] == want > 0, (i, t.query)
        # planes in another memory layout (as a device may hand them
        # back) count the same
        handles[i].visited = np.asfortranarray(vis)
        assert not handles[i].visited.flags.c_contiguous
        assert sched.slots.useful(handles[i]) == want
    swept = sum(e["args"]["swept"] for e in evs
                if e["name"] == "dense.bfs_chunk")
    assert 0 < sum(a["useful"] for a in retires.values()) <= swept
    # untraced, nothing is counted
    from repro.core.scheduler import SlotScheduler
    plain = SlotScheduler(make_engine(g, "dense"), max_slots=2)
    plain.submit(queries[0])
    plain.drain()
    assert plain.slots.stepper._indeg == {}
