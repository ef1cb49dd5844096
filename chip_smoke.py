"""Bring-up smoke: the served RPQ path end to end on a TPU.

    python chip_smoke.py                # one chip: dense, ring, update
    python chip_smoke.py --four-chips   # four chips: the sharded engines

One process drives the chip(s).  Phases, each of which must pass:

  dense   a Wikidata-shaped graph — ``scale_free_graph(2**20, 64, 2**22)``:
          hub-heavy degrees and Zipf predicate use, the paper's Sec. 5
          graph cut to what one chip holds — on ``make_engine(g, "dense")``,
          served through ``AsyncServer(SlotScheduler(eng, max_slots=8))``
          with the anchored queries of a Table-1 workload
          (``core/patterns.generate_workload``).  Every (rows, width)
          shape of the slot tick is compiled and warmed before the
          timed pass.  Answers equal ``core/oracle.eval_oracle``'s.
  ring    the ring engine on a graph its host-side traversal finishes
          in seconds, with the default ``kernel_threshold``: wavefronts
          of at least 64 tasks run the compiled ``kernels/nfa_step``
          kernel.  Answers equal the dense engine's and the oracle's.
  update  one write batch through ``submit_update`` on the dense engine
          at scale; final-epoch answers equal a rebuilt engine's.

``--four-chips`` runs only the sharded path and what it is compared
with: dense ``shards=4`` and ring task-sharded ``shards=4`` against the
one-device engines, with each device's bytes.

Exits non-zero on any failure, and when JAX finds no TPU.  The last line
of stdout is one JSON object, ``{"ok": true, "device": {...}}``; every
line before it is smoke output, not a benchmark metric.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# the deployment each phase drives: (nodes, predicates, raw edges)
DENSE_GRAPH = (2**20, 64, 2**22)
RING_GRAPH = (2**12, 16, 2**14)
GRAPH_SEED = 23
MAX_SLOTS = 8


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer or missed what it must show."""


def anchored_queries(g, num: int, seed: int):
    """The anchored queries (a constant subject or object) of a Table-1
    workload over ``g``."""
    from repro.core.engines import Query
    from repro.core.patterns import generate_workload
    wl = generate_workload(num, g.num_preds, g.num_nodes, seed=seed)
    return [Query(e, s, o) for e, s, o, _ in wl.queries
            if s is not None or o is not None]


async def _serve(sched, queries):
    from repro.core.scheduler import AsyncServer

    async def one(q):
        t0 = time.perf_counter()
        ticket = await server.submit(q)
        ans = await ticket.result()
        return ans, time.perf_counter() - t0, ticket.ticket

    async with AsyncServer(sched) as server:
        out = await asyncio.gather(*(one(q) for q in queries))
    return [a for a, _, _ in out], [t for _, t, _ in out], \
        [k for _, _, k in out]


def serve(sched, queries):
    """Every query through the scheduler's asyncio front end, submitted
    at once -> (answers, per-query latency seconds, settled tickets)."""
    return asyncio.run(_serve(sched, queries))


def oracle_answers(g, queries, out_edges):
    """The oracle's answer set of each anchored query.  A subject-free
    query (x, E, o) is asked as (o, ^E, x): one BFS per query."""
    from repro.core import regex as rx
    from repro.core.oracle import eval_oracle
    want = []
    for q in queries:
        if q.subject is not None:
            want.append(eval_oracle(g, q.expr, q.subject, q.obj,
                                    out_edges=out_edges))
        else:
            rev = str(rx.reverse(rx.parse(q.expr)))
            want.append({(s, o) for o, s in
                         eval_oracle(g, rev, q.obj, out_edges=out_edges)})
    return want


def expect_same(label: str, got, want, queries) -> None:
    bad = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    if len(got) != len(want) or bad:
        i = bad[0] if bad else 0
        raise SmokeFailure(
            f"{label}: {len(bad)} of {len(queries)} answer sets differ; "
            f"first {queries[i]}: {len(got[i])} vs {len(want[i])} pairs")
    log(f"{label}: {len(queries)} answer sets equal "
        f"({sum(len(a) for a in got)} pairs)")


def _ms(xs, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(xs) * 1e3, q))


def _row_buckets(max_slots: int):
    """The padded row counts a slot tick can dispatch (pow2, min 4)."""
    out, c = [], 4
    while True:
        out.append(c)
        if c >= max_slots:
            return out
        c *= 2


def dense_phase(nodes: int, preds: int, edges: int, num_queries: int,
                seed: int, max_slots: int = MAX_SLOTS):
    """Dense serving at scale -> (graph, engine, queries, answers)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import dense
    from repro.core import regex as rx
    from repro.core.engines import make_engine
    from repro.core.fixtures import scale_free_graph
    from repro.core.oracle import completed_out_edges
    from repro.core.scheduler import SlotScheduler

    t0 = time.perf_counter()
    g = scale_free_graph(nodes, preds, edges, seed=GRAPH_SEED)
    eng = make_engine(g, "dense")
    E = int(eng.dg.subj.shape[0])
    log(f"dense: graph scale_free_graph({nodes}, {preds}, {edges}, "
        f"seed={GRAPH_SEED}): {E} completed edges, "
        f"{eng.dg.num_labels} completed labels; built in "
        f"{time.perf_counter() - t0:.1f} s")
    queries = anchored_queries(g, num_queries, seed)
    widths = sorted({eng._pad_width(eng._plan(rx.parse(q.expr)).g.m + 1)
                     for q in queries})
    log(f"dense: {len(queries)} anchored queries, "
        f"{sum(q.subject is not None for q in queries)} subject-anchored; "
        f"state widths {widths}")

    # compile and run every (rows, width) shape of the slot tick once
    # with empty frontiers, so the timed pass compiles nothing
    t0 = time.perf_counter()
    V, L = g.num_nodes, eng.dg.num_labels
    e = eng._edges()
    sorted_kw = {"off": e.off, "n_sorted": e.n_sorted}
    shapes = [(C, S) for C in _row_buckets(max_slots) for S in widths]
    for C, S in shapes:
        args = (e.subj, e.pred, e.obj, jnp.zeros((C, L + 1, S), jnp.int8),
                jnp.zeros((C, S, S), jnp.int8),
                jnp.zeros((C, V, S), jnp.int8),
                jnp.zeros((C, V, S), jnp.int8))
        jax.block_until_ready(dense._bfs_chunk_hetero(*args, V, 1,
                                                      **sorted_kw))
        eng.traces.record("bfs_chunk_hetero", C, S)
    mem = dense._bfs_chunk_hetero.lower(*args, V, 1, **sorted_kw) \
        .compile().memory_analysis()
    log(f"dense: warmed {len(shapes)} slot-tick shapes (rows x width) "
        f"{shapes} in {time.perf_counter() - t0:.1f} s")
    log(f"dense: chunk program rows={C} width={S} compile-time "
        f"memory_analysis: temp {mem.temp_size_in_bytes} B, arguments "
        f"{mem.argument_size_in_bytes} B, outputs "
        f"{mem.output_size_in_bytes} B")

    # untimed warm pass through the scheduler: compiles what the
    # delegated (split-plan) queries dispatch
    t0 = time.perf_counter()
    warm = SlotScheduler(eng, max_slots=max_slots)
    for q in queries:
        warm.submit(q)
    warm.drain()
    eng.results.clear()
    log(f"dense: warm pass {time.perf_counter() - t0:.1f} s")

    sigs0 = eng.traces.retraces
    t0 = time.perf_counter()
    sched = SlotScheduler(eng, max_slots=max_slots)
    answers, lat, tickets = serve(sched, queries)
    wall = time.perf_counter() - t0
    modes = sorted({t.stats.plan_mode for t in tickets})
    log(f"dense: served {len(queries)} queries through AsyncServer("
        f"SlotScheduler(max_slots={max_slots})) in {wall:.3f} s; "
        f"smoke output, not a benchmark metric: p50 {_ms(lat, 50):.1f} ms, "
        f"p99 {_ms(lat, 99):.1f} ms; plan modes {modes}; "
        f"{sched.delegated} delegated; new dispatch signatures in the "
        f"timed pass: {eng.traces.retraces - sigs0}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"dense: device peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"of bytes_limit {stats.get('bytes_limit')}")

    t0 = time.perf_counter()
    out_edges = completed_out_edges(g)
    want = oracle_answers(g, queries, out_edges)
    del out_edges
    log(f"dense: oracle answered in {time.perf_counter() - t0:.1f} s")
    expect_same("dense vs oracle", answers, want, queries)
    return g, eng, queries, answers


def ring_phase(nodes: int, preds: int, edges: int, num_queries: int,
               seed: int, kernel_threshold=None, compiled: bool = True,
               max_slots: int = MAX_SLOTS) -> None:
    """The ring engine's served path with wavefronts through the
    ``nfa_step`` kernel.  ``compiled``: also require that the kernel
    lowers to a Mosaic custom call (false only where the backend
    interprets it)."""
    import jax
    import numpy as np
    from repro.core.engines import make_engine
    from repro.core.fixtures import scale_free_graph
    from repro.core.oracle import completed_out_edges
    from repro.core.scheduler import SlotScheduler
    from repro.kernels import ops

    if compiled:
        hlo = jax.jit(ops.nfa_step).lower(
            np.zeros((64, 1), np.uint32), np.zeros((8, 1), np.uint32)
        ).as_text()
        if "tpu_custom_call" not in hlo:
            raise SmokeFailure("ops.nfa_step does not lower to a Mosaic "
                               "kernel (tpu_custom_call)")
        log("ring: ops.nfa_step lowers to tpu_custom_call (compiled)")
    g = scale_free_graph(nodes, preds, edges, seed=GRAPH_SEED)
    eng = make_engine(g, "ring", kernel_threshold=kernel_threshold)
    queries = anchored_queries(g, num_queries, seed)
    t0 = time.perf_counter()
    answers, _, tickets = serve(SlotScheduler(eng, max_slots=max_slots),
                                queries)
    sigs = sorted(k[1:] for k in eng.traces.signatures if k[0] == "nfa_step")
    tasks = sum(t.stats.kernel_tasks for t in tickets)
    log(f"ring: graph scale_free_graph({nodes}, {preds}, {edges}); served "
        f"{len(queries)} anchored queries in "
        f"{time.perf_counter() - t0:.1f} s; nfa_step dispatch shapes "
        f"(tasks, words) {sigs}; {tasks} tasks through the kernel")
    if not sigs or not tasks:
        raise SmokeFailure("ring: no wavefront went through nfa_step")
    dense_answers, _, _ = serve(
        SlotScheduler(make_engine(g, "dense"), max_slots=max_slots), queries)
    expect_same("ring vs dense", answers, dense_answers, queries)
    want = oracle_answers(g, queries, completed_out_edges(g))
    expect_same("ring vs oracle", answers, want, queries)


def update_phase(g, eng, queries, answers, seed: int, num_edges: int = 64,
                 num_checked: int = 16, max_slots: int = MAX_SLOTS) -> None:
    """One write batch over the predicates of the ``num_checked``
    queries with the largest answers, then those queries' final-epoch
    answers against a from-scratch engine over the effective graph."""
    import numpy as np
    from repro.core import regex as rx
    from repro.core.engines import make_engine
    from repro.core.scheduler import SlotScheduler

    rng = np.random.default_rng(seed)
    top = sorted(range(len(queries)), key=lambda i: -len(answers[i]))
    top = sorted(top[:num_checked])
    queries = [queries[i] for i in top]
    before = [answers[i] for i in top]
    hot = sorted(set().union(*(eng._footprint(rx.parse(q.expr))
                               for q in queries)))
    adds = [(int(s), int(p), int(o)) for s, p, o in zip(
        rng.integers(0, g.num_nodes, num_edges),
        rng.choice(hot, num_edges),
        rng.integers(0, g.num_nodes, num_edges))]
    rows = rng.choice(np.nonzero(np.isin(g.p, hot))[0], num_edges,
                      replace=False)
    removes = [(int(g.s[i]), int(g.p[i]), int(g.o[i])) for i in rows]
    sched = SlotScheduler(eng, max_slots=max_slots)
    epoch0 = eng.epoch
    t0 = time.perf_counter()
    epoch = sched.submit_update(add=adds, remove=removes)
    log(f"update: {num_edges} inserts + {num_edges} deletes over "
        f"predicates {hot}: epoch {epoch0} -> {epoch} in "
        f"{time.perf_counter() - t0:.1f} s")
    got, _, tickets = serve(sched, queries)
    epochs = {t.epoch for t in tickets}
    if epochs != {epoch}:
        raise SmokeFailure(f"update: answers at epochs {epochs}, "
                           f"not the final epoch {epoch}")
    log(f"update: {sum(a != b for a, b in zip(got, before))} of "
        f"{len(queries)} answer sets changed by the write batch")
    rebuilt = make_engine(eng.effective_graph(), "dense")
    want, _, _ = serve(SlotScheduler(rebuilt, max_slots=max_slots), queries)
    expect_same("update vs rebuild", got, want, queries)


def _device_bytes(arrays):
    """device id -> bytes of the given arrays' shards on that device."""
    out = {}
    for a in arrays:
        for sh in a.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(out.items()))


def four_chip_phase(dense_graph, ring_graph, num_queries: int, seed: int,
                    shards: int = 4, max_slots: int = MAX_SLOTS) -> None:
    """Dense ``shards=N`` and ring task-sharded ``shards=N`` engines
    against the one-device engines on the same queries."""
    import jax
    from repro.core.engines import make_engine
    from repro.core.fixtures import scale_free_graph
    from repro.core.scheduler import SlotScheduler

    g = scale_free_graph(*dense_graph, seed=GRAPH_SEED)
    queries = anchored_queries(g, num_queries, seed)
    t0 = time.perf_counter()
    want, _, _ = serve(SlotScheduler(make_engine(g, "dense"),
                                     max_slots=max_slots), queries)
    log(f"four-chip: one-device dense served {len(queries)} queries in "
        f"{time.perf_counter() - t0:.1f} s")
    sh = make_engine(g, "dense", shards=shards)
    t0 = time.perf_counter()
    got = sh.eval_many(queries)
    ex = sh.sharded
    edges = (ex.sg.pred != ex.sg.num_labels).sum(axis=1).tolist()
    log(f"four-chip: dense shards={shards} eval_many in "
        f"{time.perf_counter() - t0:.1f} s, {ex.supersteps} sharded "
        f"supersteps; completed edges per shard {edges}, padded to "
        f"{ex.sg.pred.shape[1]}; edge bytes per device "
        f"{_device_bytes([ex._subj, ex._pred, ex._obj])}")
    expect_same(f"dense shards={shards} vs one device", got, want, queries)

    rg = scale_free_graph(*ring_graph, seed=GRAPH_SEED)
    rq = anchored_queries(rg, num_queries, seed)
    want, _, _ = serve(SlotScheduler(make_engine(rg, "ring"),
                                     max_slots=max_slots), rq)
    ring = make_engine(rg, "ring", shards=shards)
    got, _, _ = serve(SlotScheduler(ring, max_slots=max_slots), rq)
    log(f"four-chip: ring shards={shards}: "
        f"{ring.sharded_kernel_batches} task-sharded nfa_step batches")
    if not ring.sharded_kernel_batches:
        raise SmokeFailure("ring: no wavefront went through the "
                           "task-sharded nfa_step")
    expect_same(f"ring shards={shards} vs one device", got, want, rq)
    for d in jax.devices():
        st = d.memory_stats() or {}
        log(f"four-chip: device {d.id} bytes_in_use "
            f"{st.get('bytes_in_use')} peak_bytes_in_use "
            f"{st.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engines, on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the query workloads and the write batch")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.env import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    want_count = 4 if args.four_chips else 1
    if len(devices) < want_count:
        print(f"chip_smoke: needs {want_count} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(DENSE_GRAPH, RING_GRAPH, 48, args.seed)
        log(f"phase four-chip passed ({time.perf_counter() - t0:.1f} s)")
    else:
        g, eng, queries, answers = dense_phase(*DENSE_GRAPH, 48, args.seed)
        log(f"phase dense passed ({time.perf_counter() - t0:.1f} s)")
        t1 = time.perf_counter()
        ring_phase(*RING_GRAPH, 24, args.seed)
        log(f"phase ring passed ({time.perf_counter() - t1:.1f} s)")
        t1 = time.perf_counter()
        update_phase(g, eng, queries, answers, args.seed)
        log(f"phase update passed ({time.perf_counter() - t1:.1f} s)")
        stats = dev.memory_stats() or {}
        log(f"device peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
