"""The one traffic generator: reads a mix file and draws requests.

A mix (``traffic/<name>.json``) holds only parameters:

* ``rate_qps`` — the frozen open-loop arrival rate of queries.
* ``pool_seed`` — the seed of the request pool and of its layout.  Every
  run of a cell sends the same set of requests (expressions and anchors)
  at the same due times.  Each request is drawn on its own, so the pool
  of ``n`` requests is the first ``n`` of any larger pool: a sweep over
  rates offers one pool.
* ``layout`` — ``{"blocks": b}``: how the pool is laid out in time.  The
  requests, ranked by cost (the closure levels the reference walks for
  them; requests set-up caches count least), are cut into strata of
  ``b`` and each stratum is dealt out one request to each of ``b``
  consecutive blocks of the window; the gaps are dealt out alike by
  length.  So every block holds the same mix of long and short requests,
  and the long ones do not bunch.  The layout is drawn from
  ``pool_seed``: the same in every run.
* ``--seed`` draws which request of a cost takes which of the positions
  the layout gives that cost.  So runs with different seeds send the
  same work at the same times, in another order of like requests, and
  their spread is that of the system, not of the draw.  With
  ``"alike": "template"`` in the layout, requests are alike only when
  they share their template as well as their cost: where requests of
  one cost differ in work the levels do not count (answer sizes, hops
  outside closures), the seed then moves less of that work.
* ``templates`` — query templates: ``expr`` with ``{0}``..``{3}``
  placeholders for predicates drawn from ``predicates``, or with
  predicate names; ``anchor`` is ``obj``, ``subj`` or ``both``; the
  anchor domains name node sets the configuration's generator exports;
  ``weight`` is the template's share.  Each request's template is drawn
  with it, or, where the mix sets ``"shares": "exact"``, the i-th
  request takes the template furthest below its share of the first i
  requests: every prefix of the pool, and so the pool at every rate,
  then holds each template's share to within one request.
* ``predicates`` — ``{"draw": "zipf"}``: placeholder predicates drawn
  with weight 1/rank over the configuration's predicates.
* ``cached`` (optional) — ``{"count": k}``: set-up serves the ``k`` pool
  requests whose predicates carry the fewest edges (the cheapest to
  serve), so their answers sit in the result cache when the window
  sends them, as on a server that has been running.
* ``warmup`` — the ``seed`` of the warm-up bursts.
* ``check`` — how many finished requests the reference checks
  (``sample``); optionally the ``control`` that takes the program's
  place (``ignore_writes``; by default every closure one level short),
  and how many acknowledged inserts are read back (``read_back``).
* ``writes`` (optional) — ``{"per_read": r, "commit_s": c}``: the
  configuration's update stream (``data["stream"]``, operations in
  stream order) is sent beside the reads, ``r`` operations per read, in
  stream order, each due at its stream time mapped onto the window; a
  writer applies those that fell due in each ``c`` seconds as one batch.
  Set-up first applies the stream's head as one batch, so that the
  window's inserts keep the program's insert buffer at one power-of-two
  size (see :func:`write_plan`).

Arrivals are Poisson in shape: the gaps are the quantiles of an
exponential distribution at ``rate_qps``, scaled to end inside the
window.
"""
from __future__ import annotations

import re

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import reference as ref

# stream ids: each use of a seed draws from its own stream
POOL, ORDER, GAPS, WARMUP, CHECK, READ_BACK, STRADDLE = 0, 1, 2, 4, 5, 6, 7
# the overlay's size at which the program compacts it into a new base
# (``repro.core.delta.DEFAULT_COMPACT_THRESHOLD``): set-up and window
# stay under it, so no compaction runs
COMPACT_ROWS = 32768


@dataclass
class Request:
    expr: str
    subject: Optional[int]
    obj: Optional[int]
    template: str
    due: float = 0.0


@dataclass
class Plan:
    """What a window sends, in due order, and the requests whose answers
    set-up leaves in the result cache."""

    requests: List[Request]
    cached: List[Request] = field(default_factory=list)


@dataclass
class WritePlan:
    """The update stream's part in a run: operations ``[0, head)`` are
    applied in set-up as one batch; ``[head, head + len(due))`` are sent
    in the window, operation ``head + i`` due at ``due[i]``, and applied
    ``commit_s`` seconds at a time.  ``cap`` is the insert buffer's
    power-of-two size through the window; ``asked`` the operations the
    mix asked for (more than ``len(due)`` only when the stream is too
    short)."""

    head: int
    due: np.ndarray
    commit_s: float
    cap: int
    asked: int

    @property
    def end(self) -> int:
        return self.head + int(self.due.size)


def stream_edges(stream: Dict[str, Any], lo: int, hi: int):
    """The raw (s, p, o) edges of stream operations ``[lo, hi)``."""
    a, b = int(stream["off"][lo]), int(stream["off"][hi])
    return stream["s"][a:b], stream["p"][a:b], stream["o"][a:b]


def write_plan(mix: Dict[str, Any], data: Dict[str, Any], seconds: float,
               rate: Optional[float] = None) -> Optional[WritePlan]:
    """The run's writes, or ``None`` for a mix without them.

    Each raw insert adds two rows to the program's insert buffer (the
    edge and its inverse; stream edges are distinct and not in the
    base), and the program pads the buffer to the next power of two
    (at least 8).  The head applied in set-up is the shortest one that
    fills more than half of the smallest such size ``cap`` that head and
    window fit, so every batch of the window keeps that size and the
    compiled programs its shape.  Head and window stay under
    ``COMPACT_ROWS``."""
    w = mix.get("writes")
    if not w:
        return None
    rate = float(rate if rate is not None else mix["rate_qps"])
    st = data["stream"]
    n_ops = int(st["off"].size) - 1
    cum = 2 * np.asarray(st["off"], dtype=np.int64)    # rows before op i
    asked = max(1, int(round(rate * float(w["per_read"]) * seconds)))
    best = None
    cap = 8
    while cap // 2 < COMPACT_ROWS:
        head = int(np.searchsorted(cum, cap // 2, side="right"))
        if head > n_ops:
            break
        room = min(cap, COMPACT_ROWS - 1)
        n = int(np.searchsorted(cum, room, side="right")) - 1 - head
        n = max(0, min(asked, n, n_ops - head))
        if best is None or n > best[2]:
            best = (cap, head, n)
        if n == asked:
            break
        cap *= 2
    if best is None or best[2] == 0:
        raise ValueError("the update stream is too short for the writes "
                         "this mix asks for")
    cap, head, n = best
    t = np.asarray(st["t"], dtype=np.float64)
    t_end = t[head + n] if head + n < n_ops else None
    if t_end is not None and t_end > t[head]:
        due = seconds * (t[head:head + n] - t[head]) / (t_end - t[head])
    else:
        due = seconds * np.arange(n) / n
    return WritePlan(head, due, float(w["commit_s"]), cap, asked)


def written_preds(data: Dict[str, Any], wplan: Optional[WritePlan]) -> set:
    """Predicate ids the window's inserts write."""
    if wplan is None:
        return set()
    return set(np.unique(stream_edges(data["stream"], wplan.head,
                                      wplan.end)[1]).tolist())


def expr_preds(expr: str, data: Dict[str, Any]) -> List[int]:
    """Predicate ids an expression names (by id, or by name where the
    configuration names its predicates)."""
    names = data.get("pred_names")
    index = {n: i for i, n in enumerate(names)} if names else {}
    return [index[t] if t in index else int(t)
            for t in re.findall(r"[A-Za-z0-9_:.-]+", expr)
            if t in index or t.isdigit()]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def _pred_weights(num_preds: int) -> np.ndarray:
    w = 1.0 / np.arange(1, num_preds + 1, dtype=np.float64)
    return w / w.sum()


def _draw_anchor(rng, data, domain: str, draw: str) -> int:
    ids, weights = data["domains"][domain]
    if draw == "uniform" or weights is None:
        return int(ids[rng.integers(0, ids.size)])
    return int(ids[np.searchsorted(np.cumsum(weights), rng.random(),
                                   side="right").clip(0, ids.size - 1)])


def draw_requests(mix: Dict[str, Any], data: Dict[str, Any], n: int,
                  rng: np.random.Generator,
                  anchor_draw: Optional[str] = None,
                  template: Optional[str] = None) -> List[Request]:
    """``n`` requests from the mix's templates (only ``template``, when
    given).  ``anchor_draw`` overrides each template's anchor draw."""
    temps = [t for t in mix["templates"]
             if template is None or t["name"] == template]
    w = np.array([t["weight"] for t in temps], dtype=np.float64)
    w /= w.sum()
    exact = template is None and mix.get("shares") == "exact"
    sent = np.zeros(len(temps))
    pw = _pred_weights(data["num_preds"])
    cpw = np.cumsum(pw)
    out = []
    for i in range(n):
        if exact:
            k = int(np.argmax((i + 1) * w - sent))
            sent[k] += 1
        else:
            k = int(rng.choice(len(temps), p=w))
        t = temps[k]
        preds = [str(int(np.searchsorted(cpw, rng.random(), side="right")
                         .clip(0, data["num_preds"] - 1))) for _ in range(4)]
        expr = t["expr"].format(*preds)
        draw = anchor_draw or t.get("draw", "popularity")
        subj = obj = None
        if t["anchor"] in ("subj", "both"):
            subj = _draw_anchor(rng, data, t["subject_domain"], draw)
        if t["anchor"] in ("obj", "both"):
            obj = _draw_anchor(rng, data, t["object_domain"], draw)
        out.append(Request(expr, subj, obj, t["name"]))
    return out


def exp_gaps(n: int, rate: float, seconds: float) -> np.ndarray:
    """``n`` inter-arrival gaps: exponential quantiles at ``rate``, scaled
    to sum to ``seconds * n / (n + 1)``, shortest first."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return q * (seconds * n / (n + 1) / q.sum())


def deal(keys: Sequence[float], blocks: int,
         rng: np.random.Generator) -> List[int]:
    """An order of ``len(keys)`` items in ``blocks`` consecutive blocks:
    the items, largest key first, are cut into strata of ``blocks`` and
    each stratum is dealt out one item to a block; the order inside each
    block is drawn."""
    ranked = sorted(range(len(keys)), key=lambda i: (-keys[i], i))
    out: List[List[int]] = [[] for _ in range(blocks)]
    for s in range(0, len(ranked), blocks):
        for i, b in zip(ranked[s:s + blocks], rng.permutation(blocks)):
            out[int(b)].append(i)
    return [b[int(j)] for b in out for j in rng.permutation(len(b))]


def permute_alike(order: Sequence[int], keys: Sequence[Any],
                  rng: np.random.Generator) -> List[int]:
    """``order`` with the items of each key permuted among the positions
    that key holds."""
    out = list(order)
    for k in sorted(set(keys)):
        pos = [j for j, i in enumerate(order) if keys[i] == k]
        for j, m in zip(pos, rng.permutation(len(pos))):
            out[j] = order[pos[int(m)]]
    return out


def window_plan(mix: Dict[str, Any], data: Dict[str, Any], seconds: float,
                seed: int, graph: ref.Graph,
                rate: Optional[float] = None) -> Plan:
    """The window's requests for ``seed``, and those of them whose
    answers set-up caches.  ``graph`` is the reference's graph of
    ``data``, which prices each request."""
    rate = float(rate if rate is not None else mix["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    pool = draw_requests(mix, data, n, rng_for(mix["pool_seed"], POOL))
    cached_at: List[int] = []
    if mix.get("cached"):
        use = np.bincount(data["p"], minlength=data["num_preds"])
        preds = [expr_preds(r.expr, data) for r in pool]
        cost = [sum(int(use[x]) for x in ps) for ps in preds]
        # a window's insert invalidates the cached answers over its
        # predicate: requests over predicates it writes come last
        written = written_preds(data, write_plan(mix, data, seconds, rate))
        stale = [bool(written.intersection(ps)) for ps in preds]
        pick = sorted(range(n), key=lambda i: (stale[i], cost[i], i))
        cached_at = sorted(pick[:int(mix["cached"]["count"])])
    levels = [-1 if i in cached_at else
              ref.levels(graph, r.expr, r.subject, r.obj)
              for i, r in enumerate(pool)]
    blocks = int(mix["layout"]["blocks"])
    order = deal(levels, blocks, rng_for(mix["pool_seed"], ORDER))
    gaps = exp_gaps(n, rate, seconds)
    gaps = gaps[deal(list(gaps), blocks, rng_for(mix["pool_seed"], GAPS))]
    alike: List[Any] = levels
    if mix["layout"].get("alike") == "template":
        alike = [(lv, r.template) for lv, r in zip(levels, pool)]
    order = permute_alike(order, alike, rng_for(seed, ORDER))
    reqs = [pool[i] for i in order]
    for r, d in zip(reqs, np.cumsum(gaps)):
        r.due = float(d)
    return Plan(reqs, [pool[i] for i in cached_at])


def isolated_nodes(data: Dict[str, Any]) -> np.ndarray:
    """Nodes without an edge: a query anchored there converges after its
    first superstep."""
    V = int(data["num_nodes"])
    deg = np.bincount(data["s"], minlength=V) + \
        np.bincount(data["o"], minlength=V)
    return np.nonzero(deg == 0)[0]


def warmup_bursts(mix: Dict[str, Any], data: Dict[str, Any],
                  max_slots: int) -> List[List[Request]]:
    """Bursts that make the served path dispatch every tick shape the
    window can: per template, one burst that fills every slot and one
    that fills half of them.  Anchored at nodes without edges, each
    burst settles in one tick: the shapes are dispatched, and set-up
    does no other work."""
    wu = mix["warmup"]
    rng = rng_for(wu["seed"], WARMUP)
    ids = isolated_nodes(data)
    if ids.size == 0:
        raise ValueError("warm-up needs a node without edges")
    dead = dict(data, domains={k: (ids, None) for k in data["domains"]})
    bursts = []
    for size in (max_slots, max(1, max_slots // 2)):
        for t in mix["templates"]:
            bursts.append(draw_requests(mix, dead, size, rng,
                                        anchor_draw="uniform",
                                        template=t["name"]))
    return bursts


def check_sample(n_items: int, sizes: Sequence[int], k: int, seed: int,
                 must: Sequence[int] = ()) -> List[int]:
    """Indices of the finished requests the reference checks: the
    largest answers, those in ``must``, and the rest drawn from the
    seed, ``k`` in all (or every one when fewer)."""
    if n_items <= k:
        return list(range(n_items))
    by_size = sorted(range(n_items), key=lambda i: -sizes[i])
    chosen = list(dict.fromkeys(list(must) + by_size[:max(1, k // 4)]))
    rest = [i for i in rng_for(seed, CHECK).permutation(n_items).tolist()
            if i not in set(chosen)]
    chosen += rest[:max(0, k - len(chosen))]
    return sorted(chosen)
