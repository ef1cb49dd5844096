"""The benchmark's plain reference agrees with the dense engine on a tiny
graph: on every plan shape the planner can pick, and through the served
path, answers from the result cache included."""
import numpy as np
import pytest

from tpubench_testutil import tiny_cell  # noqa: F401  (sets the paths)

from repro.core.engines import Query, QueryStats, make_engine
from repro.core.ring import LabeledGraph
from repro.core.scheduler import SlotScheduler
from tpubench import reference as ref

V, P, E = 300, 6, 1800
EXPRS = ["0", "^1", "0/1", "0|2", "0*", "1+", "0/1*", "2*/0", "0/^1/2?",
         "(0|1)*/2", "3/4*/5", "^0/1+", "0/1/2/3"]


def _graph(seed=4):
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, V + 1) ** 0.8
    w /= w.sum()
    s = rng.choice(V, E, p=w)
    o = rng.choice(V, E, p=w)
    p = rng.choice(P, E, p=np.r_[8, 4, 3, 2, 1, 1] / 19.0)
    return s, p, o


def _queries(seed=5):
    rng = np.random.default_rng(seed)
    out = []
    for e in EXPRS:
        for _ in range(3):
            out.append((e, None, int(rng.integers(V))))
            out.append((e, int(rng.integers(V)), None))
        out.append((e, int(rng.integers(V)), int(rng.integers(V))))
        out.append((e, 0, 0))
    return out


@pytest.mark.parametrize("planner", ["cost", "forward", "reverse", "split"])
def test_reference_agrees_with_every_plan_shape(planner):
    s, p, o = _graph()
    eng = make_engine(LabeledGraph.from_arrays(s, p, o, V, P), "dense",
                      planner=planner)
    g = ref.Graph(s, p, o, V, P)
    modes = set()
    for expr, subj, obj in _queries():
        st = QueryStats()
        got = eng.eval(expr, subj, obj, stats=st)
        modes.add(st.plan_mode)
        want = ref.answer(g, expr, subj, obj)
        assert got == set(want), (planner, expr, subj, obj)
    if planner in ("forward", "reverse", "split"):
        assert planner in modes


def test_reference_parses_what_the_program_parses():
    from repro.core import regex as rx
    for e in EXPRS + ["knows|knows/knows", "replyOf*/^containerOf"]:
        assert str(ref.parse(e)).count("Lit") == \
            len(list(rx.parse(e).literals()))
        assert ref.nullable(ref.parse(e)) == rx.nullable(rx.parse(e))


def test_reference_agrees_through_the_served_path():
    s, p, o = _graph(seed=9)
    eng = make_engine(LabeledGraph.from_arrays(s, p, o, V, P), "dense")
    sched = SlotScheduler(eng, max_slots=4)
    g = ref.Graph(s, p, o, V, P)
    queries = _queries(seed=12)
    for _ in range(2):              # the second round comes from the cache
        tickets = [sched.submit(Query(e, a, b)) for e, a, b in queries]
        sched.drain()
        for (e, a, b), t in zip(queries, tickets):
            assert t.result() == set(ref.answer(g, e, a, b)), (e, a, b)
    assert sched.cache_hits >= len(queries)


def test_control_breaks_exactness():
    s, p, o = _graph()
    g = ref.Graph(s, p, o, V, P)
    differ = sum(ref.answer(g, e, a, b) != ref.answer(
        g, e, a, b, truncate_closures=True) for e, a, b in _queries())
    assert differ > 0
