"""The plain reference: regular path queries by set semantics, in numpy.

Independent of the program under test: its own parser, its own graph
index, and no automaton.  A two-way regular expression E denotes a
binary relation over nodes, and an anchored query asks for the image of
one node under it:

    back(p, T)      = {u : (u, p, v) in G, v in T}
    back(^p, T)     = {u : (v, p, u) in G, v in T}
    back(A/B, T)    = back(A, back(B, T))
    back(A|B, T)    = back(A, T) | back(B, T)
    back(A*, T)     = least R with R = T | back(A, R)   (level by level)
    back(A+, T)     = back(A, back(A*, T))
    back(A?, T)     = T | back(A, T)

``forward`` is the same with every literal's direction flipped and
concatenation read left to right.

``truncate_closures`` is the control: each closure stops one level short
of its fixpoint, the approximation a bounded superstep count would make.

``levels`` counts the levels every closure of a query takes to reach its
fixpoint: the traffic generator's measure of a request's cost.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

# -- parser -------------------------------------------------------------------

_NAME = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
            "0123456789_:.-")


@dataclass(frozen=True)
class Lit:
    name: str
    inverse: bool = False


@dataclass(frozen=True)
class Cat:
    left: "Expr"
    right: "Expr"


@dataclass(frozen=True)
class Alt:
    left: "Expr"
    right: "Expr"


@dataclass(frozen=True)
class Star:
    child: "Expr"


@dataclass(frozen=True)
class Plus:
    child: "Expr"


@dataclass(frozen=True)
class Opt:
    child: "Expr"


Expr = Union[Lit, Cat, Alt, Star, Plus, Opt]


def _tokens(s: str) -> List[str]:
    out, i = [], 0
    while i < len(s):
        c = s[i]
        if c.isspace():
            i += 1
        elif c in "()|/*+?^":
            out.append(c)
            i += 1
        elif c in _NAME:
            j = i
            while j < len(s) and s[j] in _NAME:
                j += 1
            out.append(s[i:j])
            i = j
        else:
            raise ValueError(f"bad character {c!r} in {s!r}")
    return out


def parse(s: str) -> Expr:
    """``|`` binds loosest, then ``/``, then the postfix ``* + ?``;
    ``^`` inverts one literal."""
    toks = _tokens(s)
    pos = 0

    def peek():
        return toks[pos] if pos < len(toks) else None

    def take():
        nonlocal pos
        pos += 1
        return toks[pos - 1]

    def alt():
        node = cat()
        while peek() == "|":
            take()
            node = Alt(node, cat())
        return node

    def cat():
        node = post()
        while peek() == "/":
            take()
            node = Cat(node, post())
        return node

    def post():
        node = atom()
        while peek() in ("*", "+", "?"):
            op = take()
            node = {"*": Star, "+": Plus, "?": Opt}[op](node)
        return node

    def atom():
        t = take()
        if t == "(":
            node = alt()
            if take() != ")":
                raise ValueError(f"unbalanced parentheses in {s!r}")
            return node
        if t == "^":
            return Lit(take(), inverse=True)
        if t is None or t in "()|/*+?":
            raise ValueError(f"unexpected {t!r} in {s!r}")
        return Lit(t)

    node = alt()
    if pos != len(toks):
        raise ValueError(f"trailing tokens in {s!r}")
    return node


def nullable(e: Expr) -> bool:
    if isinstance(e, Lit):
        return False
    if isinstance(e, Cat):
        return nullable(e.left) and nullable(e.right)
    if isinstance(e, Alt):
        return nullable(e.left) or nullable(e.right)
    if isinstance(e, (Star, Opt)):
        return True
    return nullable(e.child)


# -- graph ------------------------------------------------------------------


class _Index:
    """Edges grouped by one endpoint: ``keys`` sorted, ``vals`` the other
    endpoint."""

    def __init__(self, keys, vals):
        order = np.argsort(keys, kind="stable")
        self.keys = keys[order]
        self.vals = vals[order]

    def step(self, nodes: np.ndarray) -> np.ndarray:
        lo = np.searchsorted(self.keys, nodes, "left")
        hi = np.searchsorted(self.keys, nodes, "right")
        cnt = hi - lo
        total = int(cnt.sum())
        if total == 0:
            return np.zeros(0, dtype=np.int64)
        starts = np.repeat(lo - np.cumsum(cnt) + cnt, cnt)
        return self.vals[starts + np.arange(total)]


class Graph:
    """Labeled multigraph.  ``pred_names`` maps literal names to
    predicate ids; without it literals are ids."""

    def __init__(self, s, p, o, num_nodes: int, num_preds: int,
                 pred_names: Optional[Sequence[str]] = None):
        s = np.asarray(s, dtype=np.int64)
        p = np.asarray(p, dtype=np.int64)
        o = np.asarray(o, dtype=np.int64)
        self.num_nodes = int(num_nodes)
        self.num_preds = int(num_preds)
        self.names = {n: i for i, n in enumerate(pred_names)} \
            if pred_names is not None else None
        order = np.argsort(p, kind="stable")
        s, p, o = s[order], p[order], o[order]
        off = np.searchsorted(p, np.arange(self.num_preds + 1))
        # by_obj[p]: object -> subjects (backward over p);
        # by_subj[p]: subject -> objects (backward over ^p)
        self.by_obj: Dict[int, _Index] = {}
        self.by_subj: Dict[int, _Index] = {}
        for q in range(self.num_preds):
            b, e = off[q], off[q + 1]
            if b == e:
                continue
            self.by_obj[q] = _Index(o[b:e], s[b:e])
            self.by_subj[q] = _Index(s[b:e], o[b:e])

    def pred_id(self, name: str) -> int:
        if self.names is not None and not name.isdigit():
            return self.names[name]
        return int(name)

    def back_lit(self, lit: Lit, nodes: np.ndarray,
                 forward: bool) -> np.ndarray:
        """Nodes one ``lit`` edge before ``nodes`` (after them when
        ``forward``)."""
        q = self.pred_id(lit.name)
        use_obj = (not lit.inverse) != forward
        idx = (self.by_obj if use_obj else self.by_subj).get(q)
        if idx is None or nodes.size == 0:
            return np.zeros(0, dtype=np.int64)
        return idx.step(nodes)


# -- evaluation -----------------------------------------------------------


class Evaluator:
    """Set-semantics evaluation over :class:`Graph`."""

    def __init__(self, graph: Graph, truncate_closures: bool = False):
        self.g = graph
        self.truncate = truncate_closures
        self.levels = 0         # closure levels walked so far

    def _mask(self, nodes: np.ndarray) -> np.ndarray:
        m = np.zeros(self.g.num_nodes, dtype=bool)
        m[nodes] = True
        return m

    def walk(self, e: Expr, T: np.ndarray, forward: bool) -> np.ndarray:
        """Bool mask of the nodes related to some node of mask ``T`` by
        ``e`` (reading ``e`` backward, or forward when ``forward``)."""
        if isinstance(e, Lit):
            return self._mask(self.g.back_lit(e, np.nonzero(T)[0], forward))
        if isinstance(e, Cat):
            first, second = (e.left, e.right) if forward \
                else (e.right, e.left)
            return self.walk(second, self.walk(first, T, forward), forward)
        if isinstance(e, Alt):
            return self.walk(e.left, T, forward) | \
                self.walk(e.right, T, forward)
        if isinstance(e, Opt):
            return T | self.walk(e.child, T, forward)
        if isinstance(e, Star):
            return self._closure(e.child, T, forward)
        if isinstance(e, Plus):
            return self.walk(e.child, self._closure(e.child, T, forward),
                             forward)
        raise TypeError(e)

    def _closure(self, child: Expr, T: np.ndarray,
                 forward: bool) -> np.ndarray:
        R = T.copy()
        F = T
        last = None
        while F.any():
            new = self.walk(child, F, forward) & ~R
            self.levels += 1
            if not new.any():
                break
            R |= new
            F = new
            last = new
        if self.truncate and last is not None:
            R &= ~last      # the control: the closure's last level dropped
        return R

    def answer(self, expr: str, subject: Optional[int],
               obj: Optional[int]) -> frozenset:
        """All (s, o) pairs of the anchored query (subject, expr, obj)."""
        e = parse(expr)
        V = self.g.num_nodes
        if obj is not None:
            T = np.zeros(V, dtype=bool)
            T[obj] = True
            subs = self.walk(e, T, forward=False)
            if subject is not None:
                return frozenset({(subject, obj)}) if subs[subject] \
                    else frozenset()
            return frozenset((int(x), obj) for x in np.nonzero(subs)[0])
        if subject is None:
            raise ValueError("the reference answers anchored queries only")
        S = np.zeros(V, dtype=bool)
        S[subject] = True
        objs = self.walk(e, S, forward=True)
        return frozenset((subject, int(y)) for y in np.nonzero(objs)[0])


def answer(graph: Graph, expr: str, subject: Optional[int],
           obj: Optional[int], truncate_closures: bool = False) -> frozenset:
    return Evaluator(graph, truncate_closures).answer(
        expr, subject, obj)


def levels(graph: Graph, expr: str, subject: Optional[int],
           obj: Optional[int]) -> int:
    """Closure levels the anchored query walks to its fixpoints."""
    ev = Evaluator(graph)
    ev.answer(expr, subject, obj)
    return ev.levels


def pairs_equal(got, want: frozenset) -> Tuple[bool, int, int]:
    """(equal, pairs missing from ``got``, pairs ``got`` has in excess)."""
    got = set(got)
    missing = len(want - got)
    extra = len(got - want)
    return missing == 0 and extra == 0, missing, extra
