"""The harness under writes (``snb-writes``), on the CPU at tiny sizes:
the update stream and its write plan, the reference at an epoch, whole
runs with the control and three faults planted under the timed path, and
``kg-steady``'s plan, which a mix without writes must leave as it was."""
import hashlib
import json
import time

import numpy as np
import pytest

from tpubench_testutil import CELLS, spec, tiny_cell

from tpubench import faults
from tpubench import harness
from tpubench import reference as ref
from tpubench import traffic as tr

SEED = 2_147_483_677        # over 32 signed bits, as a run's seed may be
CELL = "snb-writes"

# kg-steady's window plans and warm-up bursts at the tests' tiny size,
# as the tree before the harness learnt writes drew them
KG_PLAN_SHA256 = \
    "6b4046eea6d4d6739733d321f042f68367fc98aa400105ec8bf5e3a7641a69a9"
KG_FIRST = [["6*/1*", None, 17, "v */* c", "0.15669163167780362"],
            ["0/0*", None, 1074, "v /* c", "0.7877903446351132"],
            ["2/2*", 13, None, "c /* v", "1.0179096082372263"]]
KG_CACHED = [["15*", None, 1355, "v * c"], ["6*", None, 42, "v * c"]]


def _kg_plans():
    cell = tiny_cell("kg-steady")
    data = cell.generator.build(cell.config)
    graph = harness.reference_graph(data)
    out = []
    for seed, seconds, rate in ((3_000_000_017, 10.0, None),
                                (5, 51.0, None), (7, 10.0, 4.0)):
        plan = tr.window_plan(cell.mix, data, seconds, seed, graph,
                              rate=rate)
        out.append([[r.expr, r.subject, r.obj, r.template, repr(r.due)]
                    for r in plan.requests])
        out.append([[r.expr, r.subject, r.obj, r.template]
                    for r in plan.cached])
    bursts = tr.warmup_bursts(cell.mix, data, 8)
    out.append([[[r.expr, r.subject, r.obj, r.template] for r in b]
                for b in bursts])
    return cell, data, out


def test_kg_steady_plan_is_pinned():
    """Expressions, anchors, due times, cached picks and warm-up bursts
    of the mix without writes are those the tree before writes gave."""
    cell, data, out = _kg_plans()
    assert out[0][:3] == KG_FIRST
    assert out[1] == KG_CACHED
    blob = json.dumps(out, sort_keys=True).encode()
    assert hashlib.sha256(blob).hexdigest() == KG_PLAN_SHA256
    assert tr.write_plan(cell.mix, data, 51.0) is None


# -- the update stream and the write plan ---------------------------------


@pytest.fixture(scope="module")
def snb():
    cell = tiny_cell(CELL)
    return cell, cell.generator.build(cell.config)


def test_stream_is_distinct_ordered_and_new(snb):
    cell, data = snb
    st = data["stream"]
    V, P = data["num_nodes"], data["num_preds"]
    key = lambda s, p, o: (s * P + p) * V + o     # noqa: E731
    base = key(data["s"], data["p"], data["o"])
    new = key(st["s"], st["p"], st["o"])
    assert np.unique(new).size == new.size
    assert not np.isin(new, base).any()
    assert np.all(np.diff(st["t"]) >= 0)
    assert st["off"][0] == 0 and st["off"][-1] == new.size
    assert np.all(np.diff(st["off"]) > 0)
    assert set(np.unique(st["kind"]).tolist()) <= set(range(1, 9))
    # the bulk is about nine tenths of the edges
    share = data["s"].size / (data["s"].size + new.size)
    assert 0.85 < share < 0.95


@pytest.mark.parametrize("rate", [0.5, 6.0, 40.0])
def test_write_plan_keeps_one_insert_buffer_size(snb, rate):
    cell, data = snb
    seconds = 4.0
    w = tr.write_plan(cell.mix, data, seconds, rate)
    off = data["stream"]["off"]
    head_rows = 2 * int(off[w.head])
    all_rows = 2 * int(off[w.end])
    # the program pads the buffer to max(8, next power of two)
    assert w.cap // 2 < head_rows <= all_rows <= w.cap
    assert all_rows < tr.COMPACT_ROWS
    assert w.due.size <= w.asked
    assert w.asked == round(rate * cell.mix["writes"]["per_read"] * seconds)
    assert np.all(np.diff(w.due) >= 0)
    assert w.due[0] == 0 and w.due[-1] < seconds


def test_cached_picks_avoid_written_predicates(snb):
    cell, data = snb
    mix = dict(cell.mix, cached={"count": 2})
    plan = tr.window_plan(mix, data, 2.0, 5, harness.reference_graph(data),
                          rate=6.0)
    written = tr.written_preds(data, tr.write_plan(mix, data, 2.0, 6.0))
    assert written and len(plan.cached) == 2
    for r in plan.cached:
        assert not written.intersection(tr.expr_preds(r.expr, data))


@pytest.mark.parametrize("name", [c for c in CELLS if spec.resolve(c).mix[
    "layout"].get("alike") == "template"])
def test_mix_is_deterministic_in_the_seed(name):
    """A mix whose like requests share their template: another seed sends
    the same requests at the same due times, each template keeping its
    positions, and moves anchors among reads of one template and cost."""
    cell = tiny_cell(name)
    data = cell.generator.build(cell.config)
    graph = harness.reference_graph(data)
    seed = 3_000_000_017

    def plan(s):
        return tr.window_plan(cell.mix, data, 10.0, s, graph)

    a, b, c = plan(seed), plan(seed), plan(seed + 1)
    assert [(r.expr, r.subject, r.obj, r.due) for r in a.requests] == \
        [(r.expr, r.subject, r.obj, r.due) for r in b.requests]
    key = sorted((r.expr, r.subject or -1, r.obj or -1) for r in a.requests)
    assert key == sorted((r.expr, r.subject or -1, r.obj or -1)
                         for r in c.requests)
    assert [r.due for r in a.requests] == [r.due for r in c.requests]
    assert [r.template for r in a.requests] == \
        [r.template for r in c.requests]
    sent = [[(r.expr, r.subject, r.obj) for r in p.requests]
            for p in [a, c] + [plan(seed + j) for j in range(2, 5)]]
    assert any(x != sent[0] for x in sent[1:])
    for ra, rc in zip(a.requests, c.requests):
        assert ref.levels(graph, ra.expr, ra.subject, ra.obj) == \
            ref.levels(graph, rc.expr, rc.subject, rc.obj)
    assert all(0 <= r.due < 10.0 for r in a.requests)


def test_exact_shares_hold_in_every_prefix_of_the_pool(snb):
    """With ``"shares": "exact"`` every prefix of the pool, and so the
    pool at every rate, holds each template's share of the weights to
    within one request."""
    cell, data = snb
    assert cell.mix["shares"] == "exact"
    names = [t["name"] for t in cell.mix["templates"]]
    w = np.array([t["weight"] for t in cell.mix["templates"]])
    w = w / w.sum()
    pool = tr.draw_requests(cell.mix, data, 200,
                            tr.rng_for(cell.mix["pool_seed"], tr.POOL))
    for n in range(1, 201):
        got = np.array([sum(r.template == t for r in pool[:n])
                        for t in names])
        assert np.all(np.abs(got - n * w) < 1), n


# -- the reference at an epoch ----------------------------------------------


def test_reference_at_an_epoch_is_a_rebuild():
    """Per-edge epochs give, at every epoch, the answers of a graph built
    from scratch over the base and the batches up to it."""
    rng = np.random.default_rng(41)
    V, P, E = 120, 3, 300
    s, p, o = (rng.integers(0, V, E), rng.integers(0, P, E),
               rng.integers(0, V, E))
    n_base = 180
    epochs = np.r_[np.zeros(n_base, np.int64),
                   np.repeat(np.arange(1, 5), 30)]
    epochs[-10:] = ref.NEVER                    # never applied
    g = ref.Graph(s, p, o, V, P, epochs=epochs)
    exprs = ["0", "^1", "0/1", "0*", "1+/2", "(0|2)*/^1", "2?/0+"]
    for e in range(5):
        keep = epochs <= e
        rebuilt = ref.Graph(s[keep], p[keep], o[keep], V, P)
        for expr in exprs:
            for a in range(0, V, 17):
                for subj, obj in ((None, a), (a, None), (a, (a * 7) % V)):
                    assert ref.answer(g, expr, subj, obj, epoch=e) == \
                        ref.answer(rebuilt, expr, subj, obj), (e, expr, a)
    # a query that names no epoch reads ``at``, by default every edge
    whole = ref.Graph(s, p, o, V, P)
    assert ref.answer(g, "0*", None, 3) == ref.answer(whole, "0*", None, 3)
    g.at = 0
    base = ref.Graph(s[:n_base], p[:n_base], o[:n_base], V, P)
    assert ref.answer(g, "0*", None, 3) == ref.answer(base, "0*", None, 3)


# -- whole runs -------------------------------------------------------------


def _run(*, seconds=2.0, rate=6.0, control=False, fault=None, trace=False,
         commit_s=None, template=None, drain_s=20.0):
    cell = tiny_cell(CELL)
    if commit_s is not None:
        cell.mix["writes"]["commit_s"] = commit_s
    if template is not None:
        cell.mix["templates"] = [t for t in cell.mix["templates"]
                                 if t["name"] == template]
    return harness.run(CELL, SEED, seconds, trace, time.monotonic(),
                       require_tpu=False, cell=cell, control=control,
                       rate=rate, drain_s=drain_s, before_window=fault)


def test_sound_writes_run_is_correct(capsys):
    res = _run()
    assert res["correct"], res["checks"]
    checks = res["checks"]
    assert list(checks) == ["wrong_answers", "unanswered", "stale_reads",
                            "lost_writes"]
    assert all(c["value"] == 0 for c in checks.values())
    assert checks["lost_writes"]["read_back"] > 0
    assert {"p50_ms", "write_p90_ms", "setup_s"} == set(res["metrics"])
    err = capsys.readouterr().err
    assert "batches, insert buffer" in err
    assert err.strip().splitlines()[-1] == "check lost_writes 0 limit 0"


def test_control_ignoring_the_writes_is_not_correct():
    res = _run(control=True, rate=12.0, seconds=4.0, commit_s=0.25)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault,caught_by", [
    (faults.ack_without_apply, "lost_writes"),
    (faults.read_at_newest, "wrong_answers"),
    (faults.admission_lags, "stale_reads"),
], ids=["ack-without-apply", "read-at-newest-epoch", "admission-lags"])
def test_write_fault_makes_run_incorrect(fault, caught_by, monkeypatch):
    # short commits and many reads in flight, so reads straddle batches;
    # reads of friends' messages, whose answers a new post or comment by
    # a friend changes
    res = _run(rate=40.0, seconds=2.0, commit_s=0.05,
               template="IC2/IC9 friends' messages",
               fault=lambda: fault(monkeypatch.setattr))
    assert not res["correct"], res["checks"]
    assert res["checks"][caught_by]["value"] > 0, res["checks"]


def test_traced_writes_run_reports_the_write_metrics(capsys):
    """The write metrics are read, and every dispatch of the window
    scatters the same insert buffer: set-up's head fixed its size."""
    from tpubench import spanargs, xplane
    res = _run(trace=True, commit_s=0.25)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["write_apply_ms"]["value"] > 0
    assert "compiles in the window 0 []" in capsys.readouterr().err
    assert m["chunk_tail_rows"]["value"] >= 8
    assert 0 < m["useful_sweep_fraction"]["value"] <= 1
    assert "write_p90_ms" not in m
    path = spanargs.trace_path(harness.Context(tiny_cell(CELL), None))
    spans = spanargs.load(path, *xplane.load(path).window())
    rows = spanargs.arg_values(spans, "dense.bfs_chunk", "tail_rows")
    assert rows and len(set(rows)) == 1
    assert rows[0] == m["chunk_tail_rows"]["value"]
    assert len(spanargs.named(spans, "updates.apply")) >= 2
