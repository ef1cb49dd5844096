"""The benchmark's inputs: generators and mixes are deterministic in the
seed, every cell resolves to its files by name, and ``BENCHMARK.json``
keeps to the form the harness and the check rely on."""
import json
import re

import numpy as np
import pytest

from tpubench_testutil import CELLS, ROOT, bench, spec, tiny_cell

from tpubench import harness
from tpubench import reference as ref
from tpubench import traffic as tr

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    full = bench()
    cell = spec.resolve(name, bench=full)
    assert cell.config["name"] == next(
        w["config"] for w in full["workloads"] if w["name"] == name)
    assert callable(cell.generator.build)
    assert cell.mix["rate_qps"] > 0
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(m.reader.read)
        assert next(x for x in full["per_layer"]
                    if x["name"] == m.name)["moves"] in e2e


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.resolve("no-such-cell")


def test_benchmark_json_form():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()
    for c in BENCH["configs"]:
        assert name.match(c["name"]) and len(c["source"]) <= 200
        assert len(c["why"]) <= 200 and (ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for w in BENCH["workloads"]:
        assert name.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert name.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf, f"layer {layer!r} missing from PERF.md"
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_generator_is_deterministic(name):
    cell = tiny_cell(name)
    a = cell.generator.build(cell.config)
    b = cell.generator.build(cell.config)
    for k in ("s", "p", "o"):
        np.testing.assert_array_equal(a[k], b[k])
    assert a["num_nodes"] == b["num_nodes"]
    assert a["s"].max() < a["num_nodes"] and a["o"].max() < a["num_nodes"]
    assert a["p"].max() < a["num_preds"]


def _plan(cell, data, seed, rate=None):
    return tr.window_plan(cell.mix, data, 10.0, seed,
                          harness.reference_graph(data), rate=rate)


@pytest.mark.parametrize("name", CELLS)
def test_mix_is_deterministic_in_the_seed(name):
    cell = tiny_cell(name)
    data = cell.generator.build(cell.config)
    seed = 3_000_000_017
    a, b = _plan(cell, data, seed), _plan(cell, data, seed)
    assert [(r.expr, r.subject, r.obj, r.due) for r in a.requests] == \
        [(r.expr, r.subject, r.obj, r.due) for r in b.requests]
    # another seed sends the same requests at the same due times, in
    # another order of requests that cost alike
    c = _plan(cell, data, seed + 1)
    key = sorted((r.expr, r.subject or -1, r.obj or -1) for r in a.requests)
    assert key == sorted((r.expr, r.subject or -1, r.obj or -1)
                         for r in c.requests)
    assert [r.due for r in a.requests] == [r.due for r in c.requests]
    assert [r.expr for r in a.requests] != [r.expr for r in c.requests]
    graph = harness.reference_graph(data)
    cost = {(r.expr, r.subject, r.obj): ref.levels(graph, r.expr, r.subject,
                                                   r.obj)
            for r in a.requests}
    cached = {(r.expr, r.subject, r.obj) for r in a.cached}
    for ra, rc in zip(a.requests, c.requests):
        ka, kc = (ra.expr, ra.subject, ra.obj), (rc.expr, rc.subject, rc.obj)
        assert (ka in cached) == (kc in cached)
        assert ka in cached or cost[ka] == cost[kc]
    assert all(0 <= r.due < 10.0 for r in a.requests)


def test_deal_spreads_each_stratum_over_the_blocks():
    keys = [20, 19, 18, 17, 3, 3, 2, 2, 1, 1, 1, 0]
    order = tr.deal(keys, 4, np.random.default_rng(7))
    assert sorted(order) == list(range(len(keys)))
    blocks = [order[0:3], order[3:6], order[6:9], order[9:]]
    # the four largest keys land one to a block, and so do the next four
    for stratum in ({0, 1, 2, 3}, {4, 5, 6, 7}):
        assert [len(stratum & set(b)) for b in blocks] == [1, 1, 1, 1]
    assert order == tr.deal(keys, 4, np.random.default_rng(7))


def test_permute_alike_moves_items_among_positions_of_their_key():
    keys = [5, 5, 5, 1, 1, 0]
    order = [3, 0, 4, 1, 5, 2]
    out = tr.permute_alike(order, keys, np.random.default_rng(3))
    assert sorted(out) == sorted(order)
    assert [keys[i] for i in out] == [keys[i] for i in order]


def test_reference_counts_closure_levels():
    # a chain 0 <- 1 <- 2 <- 3 over predicate 0
    g = ref.Graph([1, 2, 3], [0, 0, 0], [0, 1, 2], 4, 1)
    # three levels reach 1, 2 and 3; the fourth finds nothing new
    assert ref.levels(g, "0*", None, 0) == 4
    assert ref.levels(g, "0", None, 0) == 0


@pytest.mark.parametrize("name", CELLS)
def test_sweep_rates_offer_one_pool(name):
    """The pool at a lower rate is the first part of the pool at a higher
    one, so a sweep over rates offers one pool."""
    cell = tiny_cell(name)
    data = cell.generator.build(cell.config)
    rng = tr.rng_for(cell.mix["pool_seed"], tr.POOL)
    big = tr.draw_requests(cell.mix, data, 40, rng)
    for n in (5, 17, 40):
        small = tr.draw_requests(cell.mix, data, n,
                                 tr.rng_for(cell.mix["pool_seed"], tr.POOL))
        assert [(r.expr, r.subject, r.obj) for r in small] == \
            [(r.expr, r.subject, r.obj) for r in big[:n]]


@pytest.mark.parametrize("name", CELLS)
def test_cached_requests_are_sent_in_the_window(name):
    cell = tiny_cell(name)
    cell.mix["cached"] = {"count": 3}
    data = cell.generator.build(cell.config)
    plan = _plan(cell, data, 5, rate=2.0)
    assert len(plan.cached) == 3
    sent = [(r.expr, r.subject, r.obj) for r in plan.requests]
    for r in plan.cached:
        assert (r.expr, r.subject, r.obj) in sent


def test_check_sample_keeps_the_largest_and_the_required():
    sizes = list(range(100))
    pick = tr.check_sample(100, sizes, 20, seed=5, must=[3])
    assert len(pick) == 20 and 3 in pick and 99 in pick
    assert pick == tr.check_sample(100, sizes, 20, seed=5, must=[3])
    assert tr.check_sample(5, sizes[:5], 20, seed=5) == list(range(5))
