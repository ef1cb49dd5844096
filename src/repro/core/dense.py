"""TPU-native dense RPQ engine: frontier-synchronous product-graph BFS.

The paper's two "simultaneity" tricks map onto the two dimensions of a
dense tile (DESIGN.md §2):

  * bit-parallelism  (all NFA states of a node at once)  -> the S = m+1
    state axis;
  * range-parallelism (many graph nodes/labels at once)  -> the V node
    axis / the E edge axis.

One BFS superstep over the *backward* product graph is

    X[e]       = frontier[obj[e]] & B[label[e]]          (Fact 1 filter)
    Y[e]       = T'[X[e]]  =  X[e] @ PRED                (bit-matrix step)
    new[v]     = OR_{e : subj[e]=v} Y[e]  & ~visited[v]  (segment-OR)
    visited   |= new ; frontier = new

where PRED[j,i] = 1 iff state i reaches state j in one NFA step.  With
boolean planes this is an int8 matmul and a sorted-segment OR: the edges
are sorted by subject, so the OR over a node's run of rows is a running
count along the edge axis read at the node's segment offsets — no
scatter (rows appended after the sorted prefix, an insert buffer, are
still scattered).
A node is an *answer* when its state-0 (initial) plane lights up, exactly
as the ring engine reports subjects (Sec. 4.2).

Work bound: a node re-enters the frontier only with new NFA states
(monotone ``visited``), so total activations = |G'_E| node-states, the
Theorem-4.1 quantity; the dense engine pays extra only for touched
all-edge sweeps per superstep (tile slack — measured in benchmarks).

Multi-source batching: a leading batch axis B turns (x,E,y) phase-2 into
B simultaneous BFS runs — the TPU analogue of the wavelet tree working on
a *range* of objects at once (Sec. 4.4).

Heterogeneous batching (``eval_many``): queries with *different*
automata also share the batch axis.  Each plan's bool-plane tables are
padded to the bucket's state width (buckets quantize m+1 up to a power
of two, so retracing stays bounded) and stacked: row r of the batch
carries its own B[labels, S_pad] and PRED[S_pad, S_pad] operands, and one
vmapped BFS (``_bfs_hetero``) runs every plan at once.  Padding states
have empty B columns and zero PRED rows, so they can never activate —
per-row results are bit-identical to a solo run.

Live updates (:mod:`repro.core.delta`): the masked-plane path.  Plane
tables carry one extra all-zero *inert* label row; a mutation relabels
tombstoned base edges to it (they can never fire) and appends the
overlay's insert buffer as extra edge rows (pow2-padded so compiled BFS
shapes are reused while the buffer grows) — every BFS shape then runs
the effective edge set unchanged, and sharded engines re-partition the
same arrays (``ShardedDenseExec.refresh_edges``).  See
``add_edges``/``remove_edges``/``compact``.

Mesh sharding (``mesh=``/``shards=N``): the node axis of every one of
these BFS shapes is range-partitioned over a device mesh's data axes and
the supersteps run shard-local with one frontier all-gather per step
(:class:`repro.core.distributed.ShardedDenseExec`); results are
identical to single-device evaluation.  ``deadline_s`` switches the BFS
to host-driven compiled chunks of supersteps so the wall clock is
checked every few supersteps (sharded runs are host-stepped per
superstep).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import delta as dl
from . import planner as qp
from . import regex as rx
from ..obs import trace as otrace
from .engines import (PlanCache, QueryLike, QueryStats, ResultCache,
                      TraceTracker, as_query, normalized_key,
                      probe_result_cache, publish_result, result_key,
                      truncate_result)
from .glushkov import Glushkov
from .ring import LabeledGraph
from .stats import GraphStats


class EdgeSet(NamedTuple):
    """The edge rows one BFS sweeps: the first ``n_sorted`` rows are
    sorted by ``subj``, with ``off`` [V + 1] their segment offsets
    (``off[v]`` = first row whose subject is >= v), so the superstep
    ORs them with no scatter; rows after them (a live overlay's insert
    buffer) are scattered."""

    subj: jnp.ndarray  # [E] int32
    pred: jnp.ndarray  # [E] int32
    obj: jnp.ndarray   # [E] int32
    off: jnp.ndarray   # [V + 1] int32
    n_sorted: int

    @property
    def tail_rows(self) -> int:
        return int(self.subj.shape[0]) - self.n_sorted


@dataclass
class DenseGraph:
    """Device-resident completed graph, edges sorted by backward-push
    destination (= subject) for the sorted-segment OR, with their
    segment offsets."""

    subj: jnp.ndarray  # [E] int32, sorted ascending
    pred: jnp.ndarray  # [E] int32 in [0, 2P)
    obj: jnp.ndarray   # [E] int32
    off: jnp.ndarray   # [V + 1] int32: off[v] = first row with subj >= v
    num_nodes: int
    num_labels: int    # 2P

    @classmethod
    def from_graph(cls, g: LabeledGraph) -> "DenseGraph":
        P = g.num_preds
        s, p, o = g.completed_triples()
        order = np.argsort(s, kind="stable")
        s = s[order]
        return cls(
            subj=jnp.asarray(s, dtype=jnp.int32),
            pred=jnp.asarray(p[order], dtype=jnp.int32),
            obj=jnp.asarray(o[order], dtype=jnp.int32),
            off=jnp.asarray(np.searchsorted(s, np.arange(g.num_nodes + 1)),
                            dtype=jnp.int32),
            num_nodes=g.num_nodes,
            num_labels=2 * P,
        )

    @functools.cached_property
    def edges(self) -> EdgeSet:
        """The base edges as one snapshot object (stable identity, so
        slots pinned to it group together), every row sorted."""
        return EdgeSet(self.subj, self.pred, self.obj, self.off,
                       int(self.subj.shape[0]))


def _start_row(g: Glushkov) -> np.ndarray:
    """[S] int8 plane row for a start object: F minus the eps bit."""
    D0 = g.F & ~1
    return np.array([(D0 >> i) & 1 for i in range(g.m + 1)], dtype=np.int8)


def _plane_tables(g: Glushkov, num_labels: int):
    """Bool-plane tables: B[labels + 1, S], PRED[S, S], F[S], with state
    i on column i (column 0 = initial).  The extra label row
    ``num_labels`` is all-zero — the *inert* label: tombstoned base
    edges and padding edges are relabeled to it, so they match nothing
    (the masked-plane half of the live-update path; the sharded edge
    partition uses the same row for its padding edges)."""
    S = g.m + 1
    B = np.zeros((num_labels + 1, S), dtype=np.int8)
    for lab, mask in g.B.items():
        if 0 <= lab < num_labels:
            for i in range(S):
                B[lab, i] = (mask >> i) & 1
    PRED = np.zeros((S, S), dtype=np.int8)
    for j in range(S):
        pm = g.pred_mask[j]
        for i in range(S):
            PRED[j, i] = (pm >> i) & 1
    F = np.array([(g.F >> i) & 1 for i in range(S)], dtype=np.int8)
    F[0] = 0  # state 0 only accepts the empty word; handled separately
    return jnp.asarray(B), jnp.asarray(PRED), jnp.asarray(F)


def _sorted_segment_or(Y, off):
    """OR of ``Y``'s rows over each run ``[off[v], off[v + 1])`` of
    subject-sorted edge rows, with no scatter: an inclusive running
    count along the edge axis (int32: a hub's count overflows narrower
    ints), a zero row in front, one gather at the V + 1 offsets, and
    adjacent differences.  Work linear in E.  Returns bool [V, S]."""
    run = jnp.cumsum(Y.astype(jnp.int32), axis=0)
    run = jnp.concatenate([jnp.zeros((1,) + Y.shape[1:], jnp.int32), run])
    at = run[off]
    return at[1:] > at[:-1]


def _edge_scatter(subj, pred, obj, B, PRED, frontier, num_segments,
                  off=None, n_sorted=0):
    """The shared half of a superstep: Fact-1 edge mask -> bit-matrix
    step -> sorted-segment OR.  Rows ``[0, n_sorted)`` are sorted by
    subject with segment offsets ``off`` and are ORed without a
    scatter; the rows after them (an unsorted insert buffer), or every
    row when ``off`` is None, go through ``segment_max``.  Also the
    sharded supersteps' local body (``repro.core.distributed``), which
    passes no offsets, where ``frontier`` is the gathered full array
    while the scatter targets only the shard's own rows — keeping the
    math in ONE place is what guarantees sharded results stay
    bit-identical to single-device runs.  Returns int8 0/1 planes."""
    X = frontier[obj] * B[pred]                       # [E, S]
    Y = (X.astype(jnp.int32) @ PRED.astype(jnp.int32)) > 0
    n = n_sorted if off is not None else 0
    if n:
        hit = _sorted_segment_or(Y[:n], off)
    else:
        hit = jnp.zeros((num_segments, Y.shape[-1]), dtype=bool)
    if n < Y.shape[0]:
        hit = hit | (jax.ops.segment_max(
            Y[n:].astype(jnp.int8), subj[n:], num_segments=num_segments) > 0)
    return hit.astype(jnp.int8)


def _step_core(subj, pred, obj, B, PRED, frontier, visited, num_nodes,
               off=None, n_sorted=0):
    """One backward product-graph superstep (the docstring's four lines):
    edge scatter, then merge into the monotone visited planes."""
    scat = _edge_scatter(subj, pred, obj, B, PRED, frontier, num_nodes,
                         off, n_sorted)
    new = jnp.logical_and(scat > 0, visited == 0).astype(jnp.int8)
    return new, visited | new


# Every BFS entry point takes the edge rows (subj, pred, obj), and as
# keywords their segment offsets ``off`` and the static length
# ``n_sorted`` of their sorted prefix (see :class:`EdgeSet`); without
# ``off`` every row is scattered.


def _bfs_loop(subj, pred, obj, B, PRED, frontier, visited, num_nodes,
              max_steps, off, n_sorted):
    """Supersteps until the frontier empties or ``max_steps`` trips.
    Returns (frontier, visited, trips)."""
    def step(state):
        f, v, it = state
        new, vis = _step_core(subj, pred, obj, B, PRED, f, v, num_nodes,
                              off, n_sorted)
        return new, vis, it + 1

    def cond(state):
        f, _, it = state
        return jnp.logical_and(jnp.any(f > 0), it < max_steps)

    return jax.lax.while_loop(cond, step,
                              (frontier, visited, jnp.int32(0)))


_BFS_STATIC = ("num_nodes", "max_steps", "n_sorted")
_CHUNK_STATIC = ("num_nodes", "chunk", "n_sorted")


@functools.partial(jax.jit, static_argnames=_BFS_STATIC)
def _bfs(subj, pred, obj, B, PRED, start_planes, num_nodes: int,
         max_steps: int, off=None, n_sorted: int = 0):
    """Single-frontier BFS.  start_planes: [V, S] int8.  Returns visited
    [V, S] (int8) after convergence (or max_steps), and the trips."""
    _, visited, it = _bfs_loop(subj, pred, obj, B, PRED, start_planes,
                               start_planes, num_nodes, max_steps, off,
                               n_sorted)
    return visited, it


@functools.partial(jax.jit, static_argnames=_BFS_STATIC)
def _bfs_batched(subj, pred, obj, B, PRED, start_planes, num_nodes,
                 max_steps, off=None, n_sorted: int = 0):
    """start_planes: [Bsrc, V, S] — multi-source batched BFS (vmapped)."""
    run = jax.vmap(
        lambda sp: _bfs_loop(subj, pred, obj, B, PRED, sp, sp, num_nodes,
                             max_steps, off, n_sorted)[1]
    )
    return run(start_planes)


# -- deadline-steppable variants: a compiled CHUNK of supersteps (its own
# while_loop, capped at `chunk` trips), driven from a host loop so the
# wall clock is checked every `chunk` supersteps — near-compiled
# throughput, bounded deadline granularity ---------------------------------
_DEADLINE_CHUNK = 16


@functools.partial(jax.jit, static_argnames=_CHUNK_STATIC)
def _bfs_chunk(subj, pred, obj, B, PRED, frontier, visited, num_nodes,
               chunk, off=None, n_sorted: int = 0):
    return _bfs_loop(subj, pred, obj, B, PRED, frontier, visited,
                     num_nodes, chunk, off, n_sorted)


@functools.partial(jax.jit, static_argnames=_CHUNK_STATIC)
def _bfs_chunk_batched(subj, pred, obj, B, PRED, frontier, visited,
                       num_nodes, chunk, off=None, n_sorted: int = 0):
    run = jax.vmap(
        lambda f, v: _bfs_loop(subj, pred, obj, B, PRED, f, v, num_nodes,
                               chunk, off, n_sorted)
    )
    f, v, its = run(frontier, visited)
    return f, v, jnp.max(its)


@functools.partial(jax.jit, static_argnames=_CHUNK_STATIC)
def _bfs_chunk_hetero(subj, pred, obj, Bstk, PREDstk, frontier, visited,
                      num_nodes, chunk, off=None, n_sorted: int = 0):
    run = jax.vmap(
        lambda B, PRED, f, v: _bfs_loop(subj, pred, obj, B, PRED, f, v,
                                        num_nodes, chunk, off, n_sorted)
    )
    f, v, its = run(Bstk, PREDstk, frontier, visited)
    return f, v, jnp.max(its)


def _host_stepped(chunk_fn, edges: EdgeSet, tables, start_planes,
                  num_nodes, max_steps, deadline, collector=None):
    """Drive compiled superstep chunks over ``edges`` with the plane
    tables ``tables`` = (B, PRED) from the host, checking
    ``deadline`` (absolute seconds) between chunks — raises the same
    ``TimeoutError`` the ring engine uses.  Returns (visited, steps).
    The fixed chunk size keeps compiled shapes stable; overshooting
    ``max_steps`` by a partial chunk is harmless (the fixpoint is
    monotone, converged chunks are no-ops).

    ``collector`` (ANALYZE, :mod:`repro.obs.explain`) drops the chunk
    size to 1 so every trip IS one superstep, and appends a
    ``{"frontier", "activations"}`` row per superstep — the extra
    device syncs are the price of the timeline and exist only on the
    analyzing path."""
    import time as _time
    frontier = visited = jnp.asarray(start_planes)
    it = 0
    steps = 1 if collector is not None else _DEADLINE_CHUNK
    while it < max_steps and bool(jnp.any(frontier > 0)):
        if deadline is not None and _time.time() > deadline:
            raise TimeoutError("query deadline exceeded")
        if collector is not None:
            fin = int((frontier > 0).sum())   # repro: noqa R002 — ANALYZE-only sync
            vin = int((visited > 0).sum())    # repro: noqa R002 — ANALYZE-only sync
        with otrace.span("dense.bfs_chunk", cat="kernel", steps=steps,
                         sorted_rows=edges.n_sorted,
                         tail_rows=edges.tail_rows):
            frontier, visited, done = chunk_fn(
                edges.subj, edges.pred, edges.obj, *tables, frontier,
                visited, num_nodes, steps, off=edges.off,
                n_sorted=edges.n_sorted)
            if collector is not None:
                # block inside the span so kernel_ms covers the dispatch
                done = int(done)              # repro: noqa R002 — ANALYZE-only sync
        # the chunk-count sync IS the deadline design: the loop test
        # already blocks on this chunk's result, so reading `done` adds
        # no extra device round-trip
        it += int(done)  # repro: noqa R002 — deadline loop syncs per chunk by design
        if collector is not None and done:
            collector.append({
                "frontier": fin,
                "activations": int((visited > 0).sum()) - vin,  # repro: noqa R002 — ANALYZE-only sync
            })
    return visited, it


@functools.partial(jax.jit, static_argnames=_BFS_STATIC)
def _bfs_hetero(subj, pred, obj, Bstk, PREDstk, start_planes, num_nodes,
                max_steps, off=None, n_sorted: int = 0):
    """Heterogeneous-plan batched BFS: row r runs its OWN automaton.
    Bstk: [R, L, S_pad], PREDstk: [R, S_pad, S_pad],
    start_planes: [R, V, S_pad] — one vmap over (tables, sources)."""
    run = jax.vmap(
        lambda B, PRED, sp: _bfs_loop(subj, pred, obj, B, PRED, sp, sp,
                                      num_nodes, max_steps, off,
                                      n_sorted)[1]
    )
    return run(Bstk, PREDstk, start_planes)


@dataclass(eq=False)  # identity hash: plans key the sharded table cache
class _DensePlan:
    """Compiled dense-side plan: automaton + device-resident bool-plane
    tables (B, PRED) — shared across queries via the plan cache."""

    g: Glushkov
    B: jnp.ndarray
    PRED: jnp.ndarray
    _host: Optional[Tuple[np.ndarray, np.ndarray]] = None

    def host_tables(self) -> Tuple[np.ndarray, np.ndarray]:
        """Host copies of (B, PRED) for hetero-stack assembly, fetched
        from device once per plan instead of once per batch row."""
        if self._host is None:
            self._host = (np.asarray(self.B), np.asarray(self.PRED))
        return self._host


class DenseRPQ(dl.LiveUpdateEngine):
    """Dense-engine 2RPQ evaluation with RingRPQ-identical semantics.

    ``planner``/``stats`` mirror :class:`~repro.core.rpq.RingRPQ`: the
    cost-based planner may run ``reverse`` or ``split`` physical plans
    (executed with the same padded/batched BFS primitives), and
    ``planner="naive"`` keeps the pre-planner behavior.

    Sharding: ``mesh=`` (a :class:`jax.sharding.Mesh`) or ``shards=N``
    routes every BFS — single, multi-source, and heterogeneous
    ``eval_many`` buckets, under all planner shapes — through the
    row-partitioned sharded executor
    (:class:`~repro.core.distributed.ShardedDenseExec`); ``data_axes``
    names the mesh axes the node axis is split over and ``model_axis``
    optionally edge-splits each shard for an intra-shard sweep.  Sharded
    results are identical to single-device ``eval``.

    ``deadline_s`` on :meth:`eval` (per query) and :meth:`eval_many`
    (batch-wide, like the ring engine) raises ``TimeoutError`` — the
    BFS is host-stepped while a deadline is active so the clock is
    checked between supersteps.
    """

    def __init__(self, graph: LabeledGraph, source_batch: int = 16,
                 result_cache: Optional[ResultCache] = None,
                 planner: str = "cost",
                 stats: Optional[GraphStats] = None,
                 mesh=None, shards: Optional[int] = None,
                 data_axes=None, model_axis: Optional[str] = None,
                 compact_threshold: Optional[int] =
                 dl.DEFAULT_COMPACT_THRESHOLD):
        if planner not in ("cost", "naive", "forward", "reverse", "split"):
            raise ValueError(f"unknown planner policy {planner!r}")
        self.graph = graph
        self.dg = DenseGraph.from_graph(graph)
        self.source_batch = source_batch
        self.planner = planner
        self.plans = PlanCache()
        self.decisions = PlanCache()
        self.results = result_cache if result_cache is not None else ResultCache()
        self.traces = TraceTracker()  # distinct BFS dispatch signatures
        self.hetero_dispatches = 0   # _bfs_hetero device calls
        self.h2d_bytes = 0           # slot-tick planes and tables uploaded
        self.d2h_bytes = 0           # slot-tick planes downloaded
        self.delta: Optional[dl.DeltaOverlay] = None  # live-update overlay
        self.compact_threshold = compact_threshold
        self.compactions = 0
        self._eff: Optional[EdgeSet] = None  # rows with overlay applied
        self._stats = stats
        self._edge_s: Optional[np.ndarray] = None   # completed edges,
        self._edge_o: Optional[np.ndarray] = None   # label-major order
        self._edge_off: Optional[np.ndarray] = None
        self._edge_eff: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._deadline: Optional[float] = None      # absolute, per eval call
        self._analyze = None        # ANALYZE superstep collector (obs.explain)
        self._superstep_acc = 0     # host-stepped/sharded superstep count
        self.sharded = None
        if mesh is not None or shards is not None:
            from .distributed import ShardedDenseExec, resolve_mesh
            rmesh, raxes = resolve_mesh(mesh, shards, data_axes, model_axis)
            self.sharded = ShardedDenseExec(self.dg, rmesh, raxes, model_axis)

    @property
    def graph_stats(self) -> GraphStats:
        """Selectivity statistics for the planner (lazy; injectable).
        With a live overlay, a fresh harvest reads the static base, so
        every predicate the overlay ever touched is refreshed from the
        effective edges before first use."""
        if self._stats is None:
            self._stats = GraphStats.from_graph(self.graph)
            self._refresh_touched_stats()
        return self._stats

    # -- live updates (surface shared via delta.LiveUpdateEngine) ------------
    def _base_graph(self) -> LabeledGraph:
        return self.graph

    def _overlay_created(self) -> None:
        # base edge keys, aligned with dg's subject-sorted edge order
        # — the tombstone mask is a per-mutation np.isin over these
        self._base_keys = dl.pack_keys(
            np.asarray(self.dg.subj), np.asarray(self.dg.pred),
            np.asarray(self.dg.obj), self.graph.num_nodes,
            self.dg.num_labels)

    def _on_overlay_change(self, mutated_raw) -> None:
        """Rebuild the effective edge arrays (the masked-plane path):
        tombstoned base edges are relabeled to the inert label — their
        B row is all-zero, so they can never fire — and the overlay's
        insert buffer is appended as extra edge rows (padded to a power
        of two so compiled BFS shapes are reused while the buffer
        grows).  Both keep the base rows in place, so the base's segment
        offsets still describe the sorted prefix, and only the buffer is
        scattered.  A mesh-sharded engine re-partitions the same arrays."""
        ov = self.delta
        self._edge_eff = {}
        subj = np.asarray(self.dg.subj, dtype=np.int32)
        pred = np.asarray(self.dg.pred, dtype=np.int32)
        obj = np.asarray(self.dg.obj, dtype=np.int32)
        L = self.dg.num_labels
        if ov.has_tombs:
            pred = np.where(np.isin(self._base_keys, ov.tombstoned_keys()),
                            np.int32(L), pred)
        ds, dp, do = ov.delta_edge_rows()
        cap = 8
        while cap < ds.size:
            cap *= 2
        if ds.size or ov.has_tombs:
            pad_s = np.zeros(cap, dtype=np.int32)
            pad_p = np.full(cap, L, dtype=np.int32)
            pad_o = np.zeros(cap, dtype=np.int32)
            pad_s[:ds.size] = ds
            pad_p[:dp.size] = dp
            pad_o[:do.size] = do
            subj = np.concatenate([subj, pad_s])
            pred = np.concatenate([pred, pad_p])
            obj = np.concatenate([obj, pad_o])
            self._eff = EdgeSet(jnp.asarray(subj), jnp.asarray(pred),
                                jnp.asarray(obj), self.dg.off,
                                int(self.dg.subj.shape[0]))
        else:
            self._eff = None
        if self.sharded is not None:
            from types import SimpleNamespace
            self.sharded.refresh_edges(SimpleNamespace(
                subj=subj, pred=pred, obj=obj,
                num_nodes=self.dg.num_nodes, num_labels=L))

    def _edges(self) -> EdgeSet:
        """The edge rows every BFS runs over — the effective set when an
        overlay is live, else the base."""
        return self._eff if self._eff is not None else self.dg.edges

    def compact(self) -> None:
        """Fold the overlay into a fresh base graph + plane arrays.
        Logical no-op: results, the epoch counter, and surviving cache
        entries are unchanged — only the physical base moves."""
        if self.delta is None or self.delta.size == 0:
            return
        self.graph = self.effective_graph()
        self.dg = DenseGraph.from_graph(self.graph)
        s, p, o = self.graph.completed_triples()
        self.delta.reset_after_compaction(
            dl.pack_keys(s, p, o, self.graph.num_nodes, self.dg.num_labels))
        self._overlay_created()   # re-key the fresh base edge order
        self._eff = None
        self._edge_s = self._edge_o = self._edge_off = None
        self._edge_eff = {}
        if self._stats is not None:
            self._stats = GraphStats.from_graph(self.graph)
        if self.sharded is not None:
            self.sharded.refresh_edges(self.dg)
        self.compactions += 1

    def _resolve_lit(self, lit: rx.Lit) -> int:
        return self.graph.resolve_lit(lit)

    def _automaton(self, ast) -> Glushkov:
        return Glushkov.from_ast(ast, self._resolve_lit)

    def _plan(self, ast) -> _DensePlan:
        """Automaton + plane tables for ``ast``, shared via the plan cache
        (keyed by the canonical AST, so equivalent spellings share)."""

        def build():
            g = self._automaton(ast)
            B, PRED, _F = _plane_tables(g, self.dg.num_labels)
            return _DensePlan(g=g, B=B, PRED=PRED)

        return self.plans.get(normalized_key(ast), build)

    def _decide(self, ast, subject_bound: bool, obj_bound: bool,
                stats: Optional[QueryStats]) -> qp.Plan:
        """Planner decision, memoized per (expression, binding) class.
        The higher unanchored margin reflects that dense naive unanchored
        evaluation is already one batched all-nodes BFS."""
        return qp.decide(ast, subject_bound, obj_bound,
                         policy=self.planner, decisions=self.decisions,
                         stats_provider=lambda: self.graph_stats,
                         resolve=self._resolve_lit, record=stats,
                         unanchored_margin=qp.ANCHORED_MARGIN,
                         footprint=self._footprint(ast))

    def make_stepper(self, steps_per_tick: int = 1) -> "DenseStepper":
        """A continuously-batchable superstep executor over this engine
        — the slot scheduler's entry point (see
        :mod:`repro.core.scheduler`)."""
        return DenseStepper(self, steps_per_tick=steps_per_tick)

    # -- split-plan primitives ---------------------------------------------
    def _pred_edges_base(self, p: int) -> Tuple[np.ndarray, np.ndarray]:
        """(subjects, objects) of the *base* completed edges labeled
        ``p``, label-major order built on first use."""
        if self._edge_s is None:
            pred = np.asarray(self.dg.pred)
            order = np.argsort(pred, kind="stable")
            self._edge_s = np.asarray(self.dg.subj)[order].astype(np.int64)
            self._edge_o = np.asarray(self.dg.obj)[order].astype(np.int64)
            cnt = np.bincount(pred, minlength=self.dg.num_labels)
            self._edge_off = np.zeros(self.dg.num_labels + 1, dtype=np.int64)
            np.cumsum(cnt, out=self._edge_off[1:])
        if not (0 <= p < self.dg.num_labels):
            z = np.zeros(0, dtype=np.int64)
            return z, z
        b, e = int(self._edge_off[p]), int(self._edge_off[p + 1])
        return self._edge_s[b:e], self._edge_o[b:e]

    def _half_union(self, side_ast, seeds, reverse: bool = False) -> set:
        """Union half-traversal of a split plan: one multi-start BFS from
        all seeds (the node axis carries them simultaneously), plus the
        seeds themselves when the half matches the empty word."""
        seeds = [int(x) for x in seeds]
        if not seeds:
            return set()
        if side_ast is None:
            return set(seeds)
        ast = rx.reverse(side_ast) if reverse else side_ast
        hit = self._run_from(self._plan(ast), np.asarray(seeds))
        out = set(int(v) for v in np.nonzero(hit)[0])
        if rx.nullable(side_ast):
            out.update(seeds)
        return out

    def _grouped_half(self, side_ast, endpoints: np.ndarray,
                      reverse: bool = False) -> Dict[int, Tuple[int, ...]]:
        """Per-endpoint half results for the unanchored split join: one
        batched-BFS row per distinct seed endpoint."""
        eps = [int(x) for x in endpoints]
        if side_ast is None:
            return {u: (u,) for u in eps}
        ast = rx.reverse(side_ast) if reverse else side_ast
        hits = self._run_from_batched(self._plan(ast), eps)
        null = rx.nullable(side_ast)
        out = {}
        for i, u in enumerate(eps):
            vals = set(int(v) for v in np.nonzero(hits[i])[0])
            if null:
                vals.add(u)
            out[u] = tuple(vals)
        return out

    def _start_planes(self, g: Glushkov, objs) -> np.ndarray:
        """[V, S] planes with F (minus eps bit) active on the start objects."""
        V = self.graph.num_nodes
        planes = np.zeros((V, g.m + 1), dtype=np.int8)
        planes[np.asarray(objs)] = _start_row(g)
        return planes

    def _run_from(self, plan: _DensePlan, objs) -> np.ndarray:
        """Returns bool[V]: nodes whose initial-state plane activated."""
        V = self.graph.num_nodes
        g = plan.g
        if g.F & ~1 == 0:
            return np.zeros(V, dtype=bool)
        edges = self._edges()
        max_steps = V * (g.m + 1) + 1
        # ANALYZE routes to the host-stepped loop (chunk=1, per-superstep
        # collector) even when sharded — results are identical (the
        # sharded parity property), only the dispatch site moves
        if self.sharded is not None and self._analyze is None:
            B_host, PRED_host = plan.host_tables()
            self.traces.record("sharded_rows", 1, g.m + 1)
            visited, it = self.sharded.run_rows(
                B_host[None], PRED_host[None],
                self._start_planes(g, objs)[None],
                max_steps, deadline=self._deadline,
                table_key=(plan, 1),
            )
            self._superstep_acc += it
            return visited[0, :, 0] > 0
        if self._deadline is not None or self._analyze is not None:
            self.traces.record("bfs_chunk", V, g.m + 1)
            visited, it = _host_stepped(
                _bfs_chunk, edges, (plan.B, plan.PRED),
                self._start_planes(g, objs), V, max_steps, self._deadline,
                collector=self._analyze,
            )
            self._superstep_acc += it
            return np.asarray(visited[:, 0]) > 0
        self.traces.record("bfs", V, g.m + 1, max_steps)
        visited, _ = _bfs(
            edges.subj, edges.pred, edges.obj, plan.B, plan.PRED,
            jnp.asarray(self._start_planes(g, objs)),
            num_nodes=V, max_steps=max_steps, off=edges.off,
            n_sorted=edges.n_sorted,
        )
        return np.asarray(visited[:, 0]) > 0

    def _run_from_batched(self, plan: _DensePlan, starts: Sequence[int],
                          batch_size: Optional[int] = None) -> np.ndarray:
        """Multi-source batched BFS: bool[len(starts), V] hit planes, one
        independent start node per batch row (chunked over source_batch)."""
        V = self.graph.num_nodes
        g = plan.g
        hits = np.zeros((len(starts), V), dtype=bool)
        if g.F & ~1 == 0 or not len(starts):
            return hits
        edges = self._edges()
        Bsz = batch_size or self.source_batch
        S = g.m + 1
        frow = _start_row(g)
        use_sharded = self.sharded is not None and self._analyze is None
        if use_sharded:
            B_host, PRED_host = plan.host_tables()
            Bstk = np.broadcast_to(B_host, (Bsz,) + B_host.shape)
            PREDstk = np.broadcast_to(PRED_host, (Bsz,) + PRED_host.shape)
        for i in range(0, len(starts), Bsz):
            chunk = np.asarray(starts[i : i + Bsz], dtype=np.int64)
            if use_sharded:
                # pad the tail chunk so the compiled sharded step is
                # reused across batches; zero rows converge immediately.
                # table_key: the device tables are identical per (plan,
                # Bsz), so chunks after the first skip the transfer
                planes = np.zeros((Bsz, V, S), dtype=np.int8)
                planes[np.arange(len(chunk)), chunk] = frow
                self.traces.record("sharded_rows", Bsz, S)
                visited, it = self.sharded.run_rows(
                    Bstk, PREDstk, planes, V * S + 1,
                    deadline=self._deadline, table_key=(plan, Bsz),
                )
                self._superstep_acc += it
                hits[i : i + len(chunk)] = visited[: len(chunk), :, 0] > 0
                continue
            planes = np.zeros((len(chunk), V, S), dtype=np.int8)
            planes[np.arange(len(chunk)), chunk] = frow
            if self._deadline is not None or self._analyze is not None:
                self.traces.record("bfs_chunk_batched", len(chunk), V, S)
                visited, it = _host_stepped(
                    _bfs_chunk_batched, edges, (plan.B, plan.PRED),
                    planes, V, V * S + 1, self._deadline,
                    collector=self._analyze,
                )
                self._superstep_acc += it
            else:
                self.traces.record("bfs_batched", len(chunk), V, S)
                visited = _bfs_batched(
                    edges.subj, edges.pred, edges.obj, plan.B, plan.PRED,
                    jnp.asarray(planes), V, V * S + 1, off=edges.off,
                    n_sorted=edges.n_sorted,
                )
            hits[i : i + len(chunk)] = np.asarray(visited[:, :, 0]) > 0
        return hits

    @staticmethod
    def _pad_width(S: int) -> int:
        """Bucket state width: next power of two (min 4), so mixed-size
        automata share compiled BFS shapes instead of retracing per m."""
        w = 4
        while w < S:
            w *= 2
        return w

    def _run_hetero_rows(
        self,
        rows: Sequence[Tuple[_DensePlan, int]],
        batch_size: Optional[int] = None,
    ) -> np.ndarray:
        """Heterogeneous multi-plan batched BFS: row i runs ``rows[i] =
        (plan, start node)`` with its own padded plane tables.  Returns
        bool[len(rows), V] hit planes (initial-state activations).

        Rows bucket by padded state width; each bucket stacks per-row
        B/PRED tables and start planes and dispatches ``_bfs_hetero`` in
        ``source_batch`` chunks, the tail chunk zero-padded so compiled
        shapes are reused across batches."""
        V = self.graph.num_nodes
        hits = np.zeros((len(rows), V), dtype=bool)
        if not rows:
            return hits
        edges = self._edges()
        L = self.dg.num_labels
        Bsz = batch_size or self.source_batch
        buckets: Dict[int, List[int]] = {}
        for i, (plan, _start) in enumerate(rows):
            buckets.setdefault(self._pad_width(plan.g.m + 1), []).append(i)
        for S_pad, members in buckets.items():
            for c0 in range(0, len(members), Bsz):
                chunk = members[c0 : c0 + Bsz]
                R = len(chunk)
                # L+1 label rows: the trailing inert row (see
                # _plane_tables) stays all-zero in every stacked table
                Bstk = np.zeros((Bsz, L + 1, S_pad), dtype=np.int8)
                PREDstk = np.zeros((Bsz, S_pad, S_pad), dtype=np.int8)
                planes = np.zeros((Bsz, V, S_pad), dtype=np.int8)
                for r, i in enumerate(chunk):
                    plan, start = rows[i]
                    S = plan.g.m + 1
                    if plan.g.F & ~1 == 0:
                        continue  # no reachable final state: row stays empty
                    B_host, PRED_host = plan.host_tables()
                    Bstk[r, :, :S] = B_host
                    PREDstk[r, :S, :S] = PRED_host
                    planes[r, start, :S] = _start_row(plan.g)
                if self.sharded is not None and self._analyze is None:
                    self.traces.record("sharded_rows", Bsz, S_pad)
                    visited, it = self.sharded.run_rows(
                        Bstk, PREDstk, planes, V * S_pad + 1,
                        deadline=self._deadline,
                    )
                    self._superstep_acc += it
                elif self._deadline is not None or self._analyze is not None:
                    self.traces.record("bfs_chunk_hetero", Bsz, S_pad)
                    visited, it = _host_stepped(
                        _bfs_chunk_hetero, edges,
                        (jnp.asarray(Bstk), jnp.asarray(PREDstk)),
                        planes, V, V * S_pad + 1, self._deadline,
                        collector=self._analyze,
                    )
                    self._superstep_acc += it
                else:
                    self.traces.record("bfs_hetero", Bsz, S_pad)
                    visited = _bfs_hetero(
                        edges.subj, edges.pred, edges.obj, jnp.asarray(Bstk),
                        jnp.asarray(PREDstk), jnp.asarray(planes),
                        V, V * S_pad + 1, off=edges.off,
                        n_sorted=edges.n_sorted,
                    )
                self.hetero_dispatches += 1
                vis0 = np.asarray(visited[:R, :, 0]) > 0
                for r, i in enumerate(chunk):
                    hits[i] = vis0[r]
        return hits

    # -- split / reverse plan execution ------------------------------------
    def _seed_subjects(self, plan: qp.Plan, obj: int,
                       stats: Optional[QueryStats]) -> np.ndarray:
        """Right half from the bound object, then the surviving seed
        edges' subjects (shared by the (x,E,o) and (s,E,o) split paths)."""
        sp = plan.split
        sarr, oarr = self._pred_edges(plan.split_pred)
        if sarr.size == 0:
            if stats is not None:
                stats.plan_actual_frontier = 0
            return sarr
        U = self._half_union(sp.right, [obj])
        keep = qp.isin_mask(oarr, U)
        if stats is not None:
            stats.plan_actual_frontier = int(keep.sum())
        return np.unique(sarr[keep])

    def _split_from_subj(self, plan: qp.Plan, subject: int,
                         stats: Optional[QueryStats]) -> set:
        """(s, E=A/p/B, y): objects reachable through any seed edge whose
        subject endpoint the left half validates from ``subject``."""
        sp = plan.split
        sarr, oarr = self._pred_edges(plan.split_pred)
        if sarr.size == 0:
            if stats is not None:
                stats.plan_actual_frontier = 0
            return set()
        Vs = self._half_union(sp.left, [subject], reverse=True)
        keep = qp.isin_mask(sarr, Vs)
        if stats is not None:
            stats.plan_actual_frontier = int(keep.sum())
        return self._half_union(sp.right, np.unique(oarr[keep]),
                                reverse=True)

    def _split_unanchored(self, plan: qp.Plan,
                          stats: Optional[QueryStats]) -> Set[Tuple[int, int]]:
        """(x, E=A/p/B, y): per-endpoint batched half-BFS rows joined
        through the seed edges (answer pairs need the SAME edge).  The
        join always completes — ``limit`` truncation is deterministic
        (the sorted prefix), so a partial join could return the wrong
        pairs."""
        sp = plan.split
        sarr, oarr = self._pred_edges(plan.split_pred)
        if stats is not None:
            stats.plan_actual_frontier = int(sarr.size)
        if sarr.size == 0:
            return set()
        lmap = self._grouped_half(sp.left, np.unique(sarr))
        rmap = self._grouped_half(sp.right, np.unique(oarr), reverse=True)
        out: Set[Tuple[int, int]] = set()
        for u, v in zip(sarr.tolist(), oarr.tolist()):
            for a in lmap[u]:
                for b in rmap[v]:
                    out.add((a, b))
        return out

    def eval(
        self,
        expr: str,
        subject: Optional[int] = None,
        obj: Optional[int] = None,
        limit: Optional[int] = None,
        stats: Optional[QueryStats] = None,
        deadline_s: Optional[float] = None,
    ) -> Set[Tuple[int, int]]:
        """Evaluate the 2RPQ (subject, expr, obj); ``None`` = variable.

        ``deadline_s``: per-query timeout — raises ``TimeoutError`` (the
        same signal :meth:`RingRPQ.eval` uses), checked between BFS
        supersteps."""
        import time as _time
        prev_deadline = self._deadline
        if deadline_s:
            self._deadline = _time.time() + deadline_s
        try:
            return self._eval_inner(expr, subject, obj, limit, stats)
        finally:
            self._deadline = prev_deadline

    def explain(self, query, analyze: bool = False,
                deadline_s: Optional[float] = None) -> Dict:
        """Structured plan report for ``query`` (see
        :mod:`repro.obs.explain`).  ``analyze=False`` never executes a
        superstep; ``analyze=True`` runs the query under a private
        tracer and attaches the per-superstep timeline."""
        from ..obs import explain as oexplain
        return oexplain.explain_query(self, query, analyze=analyze,
                                      deadline_s=deadline_s)

    def _eval_inner(self, expr, subject, obj, limit, stats):
        ast = rx.parse(expr)
        V = self.graph.num_nodes
        null = rx.nullable(ast)
        out: Set[Tuple[int, int]] = set()
        acc0 = self._superstep_acc
        tr0 = self.traces.retraces
        plan = self._decide(ast, subject is not None, obj is not None, stats)

        if subject is None and obj is None:
            if null:
                out.update((v, v) for v in range(V))
            if plan.mode == "split":
                out.update(self._split_unanchored(plan, stats))
            elif plan.mode == "reverse":
                # objects-first: phase 1 over ^E finds the objects, then
                # one batched-BFS row per object completes its subjects
                objs = np.nonzero(self._run_from(
                    self._plan(rx.reverse(ast)), np.arange(V)))[0]
                if stats is not None:
                    stats.plan_actual_frontier = len(objs)
                hits = self._run_from_batched(self._plan(ast),
                                              [int(o) for o in objs])
                for bi, o in enumerate(objs):
                    for s in np.nonzero(hits[bi])[0]:
                        out.add((int(s), int(o)))
            else:
                sources = np.nonzero(
                    self._run_from(self._plan(ast), np.arange(V)))[0]
                if stats is not None:
                    stats.plan_actual_frontier = len(sources)
                # batched phase 2: source_batch sources at a time
                p_fwd = self._plan(rx.reverse(ast))
                hits = self._run_from_batched(p_fwd, [int(s) for s in sources])
                for bi, s in enumerate(sources):
                    for o in np.nonzero(hits[bi])[0]:
                        out.add((int(s), int(o)))
        elif subject is None:
            if null:
                out.add((obj, obj))
            if plan.mode == "split":
                seeds = self._seed_subjects(plan, obj, stats)
                out.update((s, obj) for s in
                           self._half_union(plan.split.left, seeds))
            else:
                for s in np.nonzero(self._run_from(self._plan(ast), [obj]))[0]:
                    out.add((int(s), obj))
        elif obj is None:
            if null:
                out.add((subject, subject))
            if plan.mode == "split":
                out.update((subject, o) for o in
                           self._split_from_subj(plan, subject, stats))
            else:
                p_fwd = self._plan(rx.reverse(ast))
                for o in np.nonzero(self._run_from(p_fwd, [subject]))[0]:
                    out.add((subject, int(o)))
        else:
            if null and subject == obj:
                out.add((subject, obj))
            elif plan.mode == "split":
                seeds = self._seed_subjects(plan, obj, stats)
                if subject in self._half_union(plan.split.left, seeds):
                    out.add((subject, obj))
            elif plan.mode == "reverse":
                if self._run_from(self._plan(rx.reverse(ast)),
                                  [subject])[obj]:
                    out.add((subject, obj))
            else:
                if self._run_from(self._plan(ast), [obj])[subject]:
                    out.add((subject, obj))
        if stats is not None:
            stats.results = len(out)
            stats.supersteps += self._superstep_acc - acc0
            stats.retraces += self.traces.retraces - tr0
            stats.epoch = self.epoch
            stats.result_cache_invalidations = self.results.invalidations
            stats.plan_cache_invalidations = self.decisions.invalidations
        return truncate_result(out, limit)

    def eval_many(
        self,
        queries: Sequence[QueryLike],
        batch_size: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ) -> List[Set[Tuple[int, int]]]:
        """Answer a batch of queries; results match per-query :meth:`eval`.

        Every fixed-endpoint query becomes one row of a multi-source
        batched BFS — *including queries with different automata*: a
        single-plan batch reuses the shared-table fast path
        (``_bfs_batched``), a mixed batch stacks per-row padded plane
        tables and runs ``_bfs_hetero``, so a 64-request batch over 16
        expressions costs 16 plan compilations and a handful of device
        dispatches instead of 64 of each.  Finished answers land in the
        cross-request :class:`ResultCache`; replayed requests (and
        duplicates within the batch) skip evaluation entirely.

        ``deadline_s`` is a *batch-wide* budget, exactly like
        :meth:`RingRPQ.eval_many`: the coalesced rows and the delegated
        multi-stage queries share one absolute deadline, and exceeding
        it raises ``TimeoutError`` for the whole batch.
        """
        import time as _time
        qs = [as_query(q) for q in queries]
        results: List[Optional[Set[Tuple[int, int]]]] = [None] * len(qs)
        deadline = (_time.time() + deadline_s) if deadline_s else None
        prev_deadline = self._deadline
        self._deadline = deadline
        try:
            return self._eval_many_inner(qs, results, batch_size, deadline)
        finally:
            self._deadline = prev_deadline

    def _eval_many_inner(self, qs, results, batch_size, deadline):
        import time as _time
        epoch = self.epoch

        # ANALYZE-tagged queries run individually under a private tracer
        # (the per-superstep timeline is per-query by construction) and
        # settle before the probe; they still share the batch deadline.
        if any(q.explain is not None for q in qs):
            from ..obs import explain as oexplain
            for i, q in enumerate(qs):
                if q.explain is None:
                    continue
                remaining = None
                if deadline is not None:
                    remaining = deadline - _time.time()
                    if remaining <= 0:
                        raise TimeoutError("query deadline exceeded")
                report, res = oexplain.analyze_query(
                    self, q, deadline_s=remaining)
                oexplain.deliver(q.explain, report)
                results[i] = res
                # publish like any other settled query: the explain tag
                # is excluded from the cache key, so an untagged repeat
                # of the same query replays from the cache
                self.results.put(result_key(q), res,
                                 footprint=self._footprint(rx.parse(q.expr)),
                                 epoch=self.epoch)

        pending = probe_result_cache(self.results, qs, results)

        rows: List[Tuple[_DensePlan, int]] = []
        row_info: List[Tuple[Tuple, "rx.Node", str]] = []  # (key, ast, mode)
        for key, idxs in pending.items():
            q = qs[idxs[0]]
            ast = rx.parse(q.expr)
            qplan = self._decide(ast, q.subject is not None,
                                 q.obj is not None, None)
            if (q.subject is None and q.obj is None) \
                    or qplan.mode == "split":
                # multi-stage plans can't ride the single-BFS batch; the
                # result stays keyed on the ORIGINAL normalized AST +
                # endpoints, never the rewritten plan's expression.
                # They still draw on the shared batch deadline.
                if deadline is not None and _time.time() > deadline:
                    raise TimeoutError("query deadline exceeded")
                res = self._eval_inner(q.expr, q.subject, q.obj, q.limit,
                                       None)
                publish_result(self.results, key, res, idxs, results,
                               footprint=self._footprint(ast), epoch=epoch)
            elif q.obj is not None and q.subject is not None \
                    and qplan.mode == "reverse":
                # (s,E,o) from the subject side over ^E
                rows.append((self._plan(rx.reverse(ast)), q.subject))
                row_info.append((key, ast, "reverse"))
            elif q.obj is not None:
                # (x,E,o) and (s,E,o) both run backward from o
                rows.append((self._plan(ast), q.obj))
                row_info.append((key, ast, "forward"))
            else:                                          # (s, E, y)
                rows.append((self._plan(rx.reverse(ast)), q.subject))
                row_info.append((key, ast, "forward"))

        if rows:
            distinct = {id(plan) for plan, _ in rows}
            if len(distinct) == 1:
                hits = self._run_from_batched(rows[0][0],
                                              [start for _, start in rows],
                                              batch_size=batch_size)
            else:
                hits = self._run_hetero_rows(rows, batch_size=batch_size)
        for bi, (key, ast, mode) in enumerate(row_info):
            idxs = pending[key]
            q = qs[idxs[0]]
            null = rx.nullable(ast)
            out: Set[Tuple[int, int]] = set()
            if q.subject is None:                          # (x, E, o)
                if null:
                    out.add((q.obj, q.obj))
                out.update((int(s), q.obj) for s in np.nonzero(hits[bi])[0])
            elif q.obj is None:                            # (s, E, y)
                if null:
                    out.add((q.subject, q.subject))
                out.update((q.subject, int(o)) for o in np.nonzero(hits[bi])[0])
            else:                                          # (s, E, o)
                hit = hits[bi][q.obj] if mode == "reverse" \
                    else hits[bi][q.subject]
                if (null and q.subject == q.obj) or hit:
                    out.add((q.subject, q.obj))
            out = truncate_result(out, q.limit)
            publish_result(self.results, key, out, idxs, results,
                           footprint=self._footprint(ast), epoch=epoch)
        return results


class _DenseSlot:
    """One in-flight dense BFS under continuous batching: its own
    frontier/visited planes (host-resident between ticks), pinned to the
    edge-array snapshot of its admission epoch."""

    __slots__ = ("plan", "start", "edges", "S_pad", "frontier", "visited",
                 "active")

    def __init__(self, plan: _DensePlan, start: int, edges: EdgeSet,
                 S_pad: int, num_nodes: int):
        self.plan = plan
        self.start = start
        self.edges = edges
        self.S_pad = S_pad
        S = plan.g.m + 1
        planes = np.zeros((num_nodes, S_pad), dtype=np.int8)
        if plan.g.F & ~1 != 0:
            planes[start, :S] = _start_row(plan.g)
        self.frontier = planes
        self.visited = planes.copy()
        # no reachable non-eps final state: converged before the 1st step
        self.active = bool(planes.any())


class DenseStepper:
    """Externally-driven superstep executor over a dynamic slot set —
    the dense engine's half of the continuous-batching contract (the
    ring engine's is :class:`repro.core.rpq.RingStepper`).

    Each :meth:`step` advances every active slot by up to
    ``steps_per_tick`` supersteps.  Slots are grouped by (edge-array
    snapshot, padded state width) and each group dispatches ONE
    ``_bfs_chunk_hetero`` call with the group's row count padded to a
    power of two (min 4), so continuous admission/retirement reuses a
    bounded set of compiled shapes — the hetero-bucket analogue of the
    prefill-insert pattern.  ``visited[:, 0]`` (the initial-state
    plane) only ever grows, which makes incremental result streaming
    sound.

    Version snapshots: ``add_job`` pins the :class:`EdgeSet` the slot's
    BFS reads.  ``submit_update`` builds the next epoch's
    effective arrays OFF TO THE SIDE (``_on_overlay_change`` constructs
    fresh arrays, never mutating old ones), so in-flight slots keep
    reading their admission epoch — at most two snapshots are live at
    once (draining + current), keeping the group count bounded.
    """

    def __init__(self, eng: DenseRPQ, steps_per_tick: int = 1):
        self.eng = eng
        self.steps_per_tick = max(1, int(steps_per_tick))
        self.slots: List[_DenseSlot] = []
        # per edge snapshot (keyed like step()'s groups, holding the
        # snapshot so the id stays its own): in-degree over live labels
        self._indeg: Dict[int, Tuple[EdgeSet, np.ndarray]] = {}

    # -- admission / retirement --------------------------------------------
    def add_job(self, plan: _DensePlan, start: int,
                edges: Optional[EdgeSet] = None) -> _DenseSlot:
        """Admit one backward BFS from ``start`` (before the next tick).
        ``edges`` pins the edge snapshot; default = the engine's current
        effective rows."""
        eng = self.eng
        edges = edges if edges is not None else eng._edges()
        slot = _DenseSlot(plan, int(start), edges,
                          eng._pad_width(plan.g.m + 1),
                          eng.graph.num_nodes)
        self.slots.append(slot)
        return slot

    def finished(self, slot: _DenseSlot) -> bool:
        return not slot.active

    def remove_job(self, slot: _DenseSlot) -> None:
        slot.active = False
        try:
            self.slots.remove(slot)
        except ValueError:
            pass

    def reported(self, slot: _DenseSlot) -> Set[int]:
        """Nodes whose initial-state plane has activated so far —
        monotone, so callers stream the set difference per tick."""
        return {int(v) for v in np.nonzero(slot.visited[:, 0] > 0)[0]}

    def useful(self, slot: _DenseSlot) -> int:
        """Edge-states the supersteps had to read for ``slot`` so far:
        the sum, over the (node, state) pairs set in its visited planes,
        of the node's in-degree over live labels.  Each pair enters the
        frontier once, and a frontier pair at node v reads the edges
        whose object is v, whatever kernel implements the superstep.  A
        pair found by the last superstep and not yet expanded counts
        too, so for a slot retired on a hit this is an upper bound by
        one frontier.  Costs a pass over the planes (and, once per edge
        snapshot, a download of its label and object arrays)."""
        key = id(slot.edges)
        if key not in self._indeg:
            pred = np.asarray(slot.edges.pred)
            obj = np.asarray(slot.edges.obj)
            if len(self._indeg) >= 4:      # at most two snapshots are live
                self._indeg.clear()
            self._indeg[key] = (slot.edges, np.bincount(
                obj[pred < self.eng.dg.num_labels],
                minlength=self.eng.graph.num_nodes))
        indeg = self._indeg[key][1]
        return int(np.count_nonzero(slot.visited, axis=1) @ indeg)

    # -- one tick -----------------------------------------------------------
    def step(self) -> bool:
        """Advance every active slot by up to ``steps_per_tick``
        supersteps (one compiled chunk per (snapshot, width) group).
        Returns True while any slot still has a live frontier."""
        eng = self.eng
        V = eng.graph.num_nodes
        L = eng.dg.num_labels
        groups: Dict[Tuple, List[_DenseSlot]] = {}
        for slot in self.slots:
            if slot.active:
                key = (id(slot.edges), slot.S_pad)
                groups.setdefault(key, []).append(slot)
        with otrace.span("dense.superstep", cat="engine",
                         slots=len(self.slots), groups=len(groups)):
            for (_ids, S_pad), members in groups.items():
                C = 4
                while C < len(members):
                    C *= 2
                with otrace.span("dense.restack", cat="engine", rows=C,
                                 live=len(members), width=S_pad):
                    Bstk = np.zeros((C, L + 1, S_pad), dtype=np.int8)
                    PREDstk = np.zeros((C, S_pad, S_pad), dtype=np.int8)
                    front = np.zeros((C, V, S_pad), dtype=np.int8)
                    vis = np.zeros((C, V, S_pad), dtype=np.int8)
                    for r, slot in enumerate(members):
                        S = slot.plan.g.m + 1
                        B_host, PRED_host = slot.plan.host_tables()
                        Bstk[r, :, :S] = B_host
                        PREDstk[r, :S, :S] = PRED_host
                        front[r] = slot.frontier
                        vis[r] = slot.visited
                h2d = Bstk.nbytes + PREDstk.nbytes + front.nbytes + vis.nbytes
                # f and v come back in the shapes and dtype they went up in
                d2h = front.nbytes + vis.nbytes
                with otrace.span("dense.upload", cat="transfer", bytes=h2d):
                    planes = (jnp.asarray(Bstk), jnp.asarray(PREDstk),
                              jnp.asarray(front), jnp.asarray(vis))
                edges = members[0].edges
                eng.traces.record("bfs_chunk_hetero", C, S_pad)
                # the device wait is the block inside this span; the
                # download below then copies finished buffers
                with otrace.span("dense.bfs_chunk", cat="kernel", rows=C,
                                 live=len(members), width=S_pad,
                                 swept=C * int(edges.subj.shape[0]) * S_pad
                                 * self.steps_per_tick,
                                 sorted_rows=edges.n_sorted,
                                 tail_rows=edges.tail_rows):
                    out = _bfs_chunk_hetero(
                        edges.subj, edges.pred, edges.obj, *planes, V,
                        self.steps_per_tick, off=edges.off,
                        n_sorted=edges.n_sorted)
                    jax.block_until_ready(out)
                eng.hetero_dispatches += 1
                eng.h2d_bytes += h2d
                eng.d2h_bytes += d2h
                with otrace.span("dense.download", cat="transfer", bytes=d2h):
                    f, v, it = out
                    f = np.asarray(f)
                    v = np.asarray(v)
                    eng._superstep_acc += int(it)
                    for r, slot in enumerate(members):
                        slot.frontier = f[r]
                        slot.visited = v[r]
                        if not f[r].any():
                            slot.active = False
        return any(s.active for s in self.slots)
