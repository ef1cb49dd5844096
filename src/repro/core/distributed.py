"""Mesh-sharded execution substrate for both RPQ engines.

This module is the device-sharding layer the engines dispatch into when
built with ``make_engine(graph, ..., mesh=...)`` or ``shards=N``:

  * :func:`resolve_mesh` — turn the engine knobs (``mesh=``/``shards=``/
    ``data_axes=``) into a concrete :class:`jax.sharding.Mesh` + the data
    axes the wavefront is partitioned over;
  * :class:`ShardedGraph` — edges range-partitioned by the owner of their
    backward-push destination (the subject), padded to equal per-shard
    length so every shard runs the same static shapes;
  * :func:`make_superstep` / :func:`make_superstep_batched` — the
    jittable shard_map supersteps of the dense engine's frontier-
    synchronous product-graph BFS (single plane set, and the batched
    variant whose rows carry their *own* plane tables — the sharded form
    of the heterogeneous ``eval_many`` bucket);
  * :func:`make_task_shard_step` — the ring engine's sharded wavefront
    transition: a superstep's merged task list is range-split over the
    data axes, each shard steps its slice through the bit-parallel
    ``kernels/nfa_step`` locally, and the per-shard result masks merge
    with an all-gather (disjoint ranges, so the gather IS the mask-OR);
  * :class:`ShardedDenseExec` — the dense engine's sharded executor: a
    host-driven superstep loop (deadline-checkable between supersteps)
    over device-resident sharded edges, used by ``_run_from`` /
    ``_run_from_batched`` / ``_run_hetero_rows`` so every planner shape
    (forward / reverse / split) and ``eval_many`` bucket runs sharded.

Sharding design (DESIGN.md §4):
  * graph nodes are range-partitioned over the data axes — shard k owns
    nodes [k*Vl, (k+1)*Vl);
  * edges live with the *owner of their backward-push destination* (the
    subject), so scatter-OR updates are always shard-local;
  * each superstep all-gathers the frontier planes (the only collective:
    V*S bytes) and computes gather -> Fact-1 mask -> bit-matrix step ->
    segment-OR entirely locally.

The NFA-state axis S is tiny and replicated.  ``model_axis`` optionally
splits each shard's *edges* over the model axis for an intra-shard
edge-parallel sweep; the partial scatter-ORs are combined with a local
psum-OR (a psum of 0/1 counts followed by >0) — no extra frontier
traffic, since the frontier stays replicated over the model axis.

Results are bit-identical to the single-device engines: the superstep
computes exactly the same monotone visited-plane fixpoint, only
partitioned; on one device the partition is trivial.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checking off: a
    ``pallas_call`` body declares no ``vma`` on its output shapes, which
    the check requires."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def resolve_mesh(
    mesh: Optional[Mesh] = None,
    shards: Optional[int] = None,
    data_axes: Optional[Sequence[str]] = None,
    model_axis: Optional[str] = None,
) -> Tuple[Optional[Mesh], Tuple[str, ...]]:
    """Resolve the engine sharding knobs into (mesh, data_axes).

    ``mesh=`` wins; ``shards=N`` builds a 1-D ``("data",)`` mesh over the
    first N local devices.  ``data_axes`` defaults to every mesh axis
    except ``model_axis``.  Returns ``(None, ())`` when sharding is off.
    """
    if mesh is None and shards is None:
        return None, ()
    if mesh is None:
        if model_axis is not None:
            raise ValueError(
                "model_axis requires an explicit mesh= containing that "
                "axis; shards=N builds a 1-D ('data',) mesh")
        devs = jax.devices()
        if not 1 <= shards <= len(devs):
            raise ValueError(
                f"shards={shards} but only {len(devs)} devices are visible "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "for a forced host mesh)")
        mesh = Mesh(np.asarray(devs[:shards]), ("data",))
    if model_axis is not None and model_axis not in mesh.axis_names:
        raise ValueError(
            f"model_axis={model_axis!r} is not an axis of the mesh "
            f"(axes: {mesh.axis_names})")
    if data_axes is None:
        data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    return mesh, tuple(data_axes)


@dataclass
class ShardedGraph:
    """Edges partitioned by destination(subject)-owner, padded to equal
    per-shard length.  Padding edges carry the reserved label
    ``num_labels`` whose B row is all-zero — they contribute nothing.
    ``pad_multiple`` rounds the per-shard edge count up so a model-axis
    split divides evenly."""

    subj_local: np.ndarray  # [shards, E_max] int32 (owner-local row ids)
    pred: np.ndarray        # [shards, E_max] int32 (padded: num_labels)
    obj: np.ndarray         # [shards, E_max] int32 (global node ids)
    nodes_per_shard: int
    num_shards: int
    num_nodes_padded: int
    num_labels: int

    @classmethod
    def from_dense(cls, dg, num_shards: int,
                   pad_multiple: int = 1) -> "ShardedGraph":
        V = dg.num_nodes
        Vl = -(-V // num_shards)
        Vp = Vl * num_shards
        subj = np.asarray(dg.subj)
        pred = np.asarray(dg.pred)
        obj = np.asarray(dg.obj)
        owner = subj // Vl
        emax = max(1, int(np.bincount(owner, minlength=num_shards).max()))
        emax = -(-emax // pad_multiple) * pad_multiple
        sl = np.zeros((num_shards, emax), dtype=np.int32)
        pr = np.full((num_shards, emax), dg.num_labels, dtype=np.int32)
        ob = np.zeros((num_shards, emax), dtype=np.int32)
        for k in range(num_shards):
            sel = owner == k
            cnt = int(sel.sum())
            sl[k, :cnt] = subj[sel] - k * Vl
            pr[k, :cnt] = pred[sel]
            ob[k, :cnt] = obj[sel]
        return cls(
            subj_local=sl, pred=pr, obj=ob,
            nodes_per_shard=Vl, num_shards=num_shards,
            num_nodes_padded=Vp, num_labels=dg.num_labels,
        )


def _local_bfs_step(frontier, frontier_l, visited_l, subj_l, pred_l, obj_l,
                    B, PRED, model_axis: Optional[str]):
    """One shard's superstep body on an already-gathered frontier [V, S]:
    the single-device edge scatter (``dense._edge_scatter`` — one source
    of truth for the step math) targeting only the shard's local rows;
    it passes no segment offsets, so every row of the shard takes the
    scatter.  Then an optional psum-OR over the model axis when the
    shard's edges are model-split (0/1 counts, then >0), then the
    visited merge."""
    from .dense import _edge_scatter
    scat = _edge_scatter(subj_l, pred_l, obj_l, B, PRED, frontier,
                         frontier_l.shape[0])
    if model_axis is not None:
        scat = jax.lax.psum(scat.astype(jnp.int32), model_axis)
    new = jnp.logical_and(scat > 0, visited_l == 0).astype(jnp.int8)
    return new, visited_l | new


def make_superstep(mesh: Mesh, data_axes: Tuple[str, ...], S: int,
                   model_axis: Optional[str] = None):
    """Build the jittable sharded superstep (single shared plane set).

    Args (sharded):  frontier/visited [V_pad, S] rows over data_axes;
    edge arrays [shards, E_max] over data_axes (leading dim; the E_max
    dim additionally over ``model_axis`` when given);
    B [L+1, S], PRED [S, S] replicated.
    Returns (new_frontier, new_visited).
    """
    axes = data_axes

    def local_step(frontier_l, visited_l, subj_l, pred_l, obj_l, B, PRED):
        # shard_map gives leading dims of size 1 for the edge arrays
        subj_l, pred_l, obj_l = subj_l[0], pred_l[0], obj_l[0]
        # the only collective: assemble the full frontier
        frontier = frontier_l
        for ax in reversed(axes):
            frontier = jax.lax.all_gather(frontier, ax, tiled=True)
        return _local_bfs_step(frontier, frontier_l, visited_l,
                               subj_l, pred_l, obj_l, B, PRED, model_axis)

    spec_rows = P(axes, None)
    spec_edges = P(axes, model_axis)
    rep = P()
    return _shard_map(
        local_step,
        mesh=mesh,
        in_specs=(spec_rows, spec_rows, spec_edges, spec_edges, spec_edges,
                  rep, rep),
        out_specs=(spec_rows, spec_rows),
    )


def make_superstep_batched(mesh: Mesh, data_axes: Tuple[str, ...],
                           model_axis: Optional[str] = None):
    """Batched sharded superstep: row r of the leading batch axis runs
    its OWN plane tables — the sharded form of the heterogeneous
    ``eval_many`` bucket (and, with identical rows, of the multi-source
    batched BFS).

    Args (sharded): frontier/visited [R, V_pad, S] with the node axis
    over data_axes; edge arrays [shards, E_max] over data_axes (E_max
    additionally over ``model_axis``); Bstk [R, L+1, S] and
    PREDstk [R, S, S] replicated.
    """
    axes = data_axes

    def local_step(frontier_l, visited_l, subj_l, pred_l, obj_l,
                   Bstk, PREDstk):
        subj_l, pred_l, obj_l = subj_l[0], pred_l[0], obj_l[0]
        frontier = frontier_l
        for ax in reversed(axes):
            frontier = jax.lax.all_gather(frontier, ax, axis=1, tiled=True)
        run = jax.vmap(
            lambda f, fl, vl, B, PRED: _local_bfs_step(
                f, fl, vl, subj_l, pred_l, obj_l, B, PRED, model_axis)
        )
        return run(frontier, frontier_l, visited_l, Bstk, PREDstk)

    spec_rows = P(None, axes, None)
    spec_edges = P(axes, model_axis)
    rep = P()
    return _shard_map(
        local_step,
        mesh=mesh,
        in_specs=(spec_rows, spec_rows, spec_edges, spec_edges, spec_edges,
                  rep, rep),
        out_specs=(spec_rows, spec_rows),
    )


def make_bfs(mesh: Mesh, data_axes: Tuple[str, ...], S: int, num_steps: int):
    """Fixed-trip-count BFS (lowering-friendly: the dry-run lowers this);
    real runs drive :func:`make_superstep` from a host loop instead."""
    step = make_superstep(mesh, data_axes, S)

    @jax.jit
    def run(frontier, visited, subj, pred, obj, B, PRED):
        def body(_, state):
            f, v = state
            return step(f, v, subj, pred, obj, B, PRED)

        f, v = jax.lax.fori_loop(0, num_steps, body, (frontier, visited))
        return f, v

    return run


def make_task_shard_step(mesh: Mesh, data_axes: Tuple[str, ...]):
    """Sharded wavefront transition for the ring engine.

    The merged superstep task list X [N, W] (packed uint32 state words,
    already label-masked — Fact 1 happens upstream) is range-split over
    the data axes; each shard runs the bit-parallel ``T'[D & B[p]]``
    transition locally through ``kernels/nfa_step`` and the per-shard
    result masks merge with an all-gather — the only collective.  The
    shard ranges are disjoint, so the gather is exactly the mask-OR
    merge of the design note.  ``bwd`` may be a single plan's packed
    table or a block-diagonal multi-plan bundle table — the kernel does
    not care.
    """
    axes = data_axes

    def local_step(x_l, bwd):
        from ..kernels import ops
        y_l = ops.nfa_step(x_l, bwd)
        for ax in reversed(axes):
            y_l = jax.lax.all_gather(y_l, ax, axis=0, tiled=True)
        return y_l

    return jax.jit(_shard_map(
        local_step, mesh=mesh,
        in_specs=(P(axes, None), P()), out_specs=P(),
    ))


class ShardedDenseExec:
    """The dense engine's sharded executor.

    Holds the device-resident :class:`ShardedGraph` and drives the
    batched sharded superstep from a host loop — any(frontier) is
    checked between supersteps, which is also where per-query/batch
    deadlines are enforced (``TimeoutError``, the same signal the ring
    engine raises).  ``run_rows`` is the single entry point: row r of
    the batch runs its own plane tables, so the same loop serves the
    single-plan, multi-source, and heterogeneous ``eval_many`` shapes.
    """

    def __init__(self, dg, mesh: Mesh,
                 data_axes: Tuple[str, ...] = ("data",),
                 model_axis: Optional[str] = None):
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.model_axis = model_axis
        self.num_shards = int(np.prod([mesh.shape[a] for a in data_axes]))
        self._pad_multiple = int(mesh.shape[model_axis]) if model_axis else 1
        self.num_nodes = dg.num_nodes
        self.num_labels = dg.num_labels
        self.dispatches = 0      # sharded superstep-loop launches
        self.supersteps = 0      # total supersteps across all launches
        self.edge_refreshes = 0  # live-update edge re-partitions
        self._table_cache: dict = {}  # table_key -> (B_dev, PRED_dev)
        self._spec_edges = NamedSharding(mesh, P(self.data_axes, model_axis))
        self._spec_rows = NamedSharding(mesh, P(None, self.data_axes, None))
        self._rep = NamedSharding(mesh, P())
        self._step = jax.jit(make_superstep_batched(
            mesh, self.data_axes, model_axis))
        self.refresh_edges(dg)

    def refresh_edges(self, dg) -> None:
        """(Re)partition the edge arrays over the mesh — called at build
        and after every live-update mutation batch, with ``dg`` any
        object carrying effective ``subj``/``pred``/``obj`` arrays (base
        edges with tombstones relabeled inert, delta rows appended).
        Node count and label alphabet are fixed between rebuilds, so the
        row partition and plane tables are untouched; only the per-shard
        edge arrays (and their padded length, when the overlay grows
        past a power of two) change."""
        self.sg = ShardedGraph.from_dense(dg, self.num_shards,
                                          pad_multiple=self._pad_multiple)
        put = lambda x: jax.device_put(jnp.asarray(x), self._spec_edges)
        self._subj = put(self.sg.subj_local)
        self._pred = put(self.sg.pred)
        self._obj = put(self.sg.obj)
        self.edge_refreshes += 1

    def pad_nodes(self, planes: np.ndarray) -> np.ndarray:
        """[R, V, S] start planes -> [R, V_pad, S] (trailing zero rows)."""
        Vp = self.sg.num_nodes_padded
        if planes.shape[1] == Vp:
            return planes
        out = np.zeros((planes.shape[0], Vp, planes.shape[2]),
                       dtype=planes.dtype)
        out[:, : planes.shape[1]] = planes
        return out

    def _pad_tables(self, Bstk: np.ndarray) -> np.ndarray:
        """[R, L, S] label tables -> [R, L+1, S]: append the all-zero row
        of the reserved inert label, so padding (and tombstoned) edges
        match nothing.  Plan tables built by ``dense._plane_tables``
        already carry the inert row — those pass through unchanged."""
        R, L, S = Bstk.shape
        if L == self.num_labels + 1:
            return Bstk
        out = np.zeros((R, L + 1, S), dtype=Bstk.dtype)
        out[:, :L] = Bstk
        return out

    def run_rows(
        self,
        Bstk: np.ndarray,       # [R, L, S] int8 per-row label tables
        PREDstk: np.ndarray,    # [R, S, S] int8 per-row transition tables
        start_planes: np.ndarray,  # [R, V or V_pad, S] int8
        max_steps: int,
        deadline: Optional[float] = None,
        table_key=None,
    ) -> Tuple[np.ndarray, int]:
        """Run the sharded BFS to convergence (or ``max_steps``).

        Returns (visited [R, V, S] int8, supersteps).  Raises
        ``TimeoutError`` when ``deadline`` (absolute ``time.time()``
        seconds) passes between supersteps.  ``table_key`` (hashable;
        hold a strong reference, e.g. the plan object itself) memoizes
        the device-put tables so repeated runs of the same plan stack
        skip the host-to-device transfer.
        """
        planes = self.pad_nodes(start_planes)
        frontier = jax.device_put(jnp.asarray(planes), self._spec_rows)
        visited = frontier
        cached = self._table_cache.get(table_key) if table_key is not None \
            else None
        if cached is None:
            Bd = jax.device_put(jnp.asarray(self._pad_tables(Bstk)),
                                self._rep)
            Pd = jax.device_put(jnp.asarray(PREDstk), self._rep)
            if table_key is not None:
                self._table_cache[table_key] = (Bd, Pd)
                while len(self._table_cache) > 32:
                    self._table_cache.pop(next(iter(self._table_cache)))
        else:
            Bd, Pd = cached
        self.dispatches += 1
        it = 0
        while it < max_steps and bool(jnp.any(frontier > 0)):
            if deadline is not None and time.time() > deadline:
                raise TimeoutError("query deadline exceeded")
            frontier, visited = self._step(
                frontier, visited, self._subj, self._pred, self._obj, Bd, Pd)
            it += 1
        self.supersteps += it
        return np.asarray(visited)[:, : self.num_nodes], it
