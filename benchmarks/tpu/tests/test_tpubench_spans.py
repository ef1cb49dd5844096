"""The readers of the per-layer metrics that read span arguments
(``tpubench/spanargs.py``): on hand-made spans, on a trace recorded here
through the program's tracer and its profiler bridge, and on the
committed chip trace, whose program passed no span arguments and had
none of the spans they read."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from tpubench_testutil import bench  # (importing it sets the paths)

from tpubench import harness, spanargs, spec, xplane

RECORDED = Path(__file__).resolve().parent / "data" / "kg-steady.xplane.pb"
NEW = ["restack_ms", "transfer_ms", "tick_io_mb", "harvest_ms", "flush_ms",
       "queue_wait_p90_ms", "useful_sweep_fraction"]
MS = 1_000_000                                  # ns


def _readers():
    cell = spec.resolve("kg-steady", bench=bench())
    return cell, {m.name: m.reader for m in cell.per_layer}


def _sp(name, start_ms, ms, **args):
    return spanargs.Span(int(start_ms * MS), int((start_ms + ms) * MS),
                         name, args)


def _hand_made():
    """Two ticks: three dispatches, two harvests, three flushes, five
    admissions and three retirements (one without sweep work)."""
    return [
        _sp("scheduler.tick", 0, 100),
        _sp("dense.restack", 1, 10, rows=8, live=5, width=8),
        _sp("dense.upload", 11, 5, bytes=100e6),
        _sp("dense.bfs_chunk", 16, 20, swept=1000),
        _sp("dense.download", 36, 3, bytes=80e6),
        _sp("dense.restack", 40, 20, rows=4, live=2, width=4),
        _sp("dense.upload", 60, 7, bytes=50e6),
        _sp("dense.bfs_chunk", 67, 10, swept=3000),
        _sp("dense.download", 77, 5, bytes=40e6),
        _sp("scheduler.harvest", 83, 2, slots=5),
        _sp("scheduler.retire", 84, 0.5, rid=3, results=9, useful=400),
        _sp("server.flush", 101, 1, tickets=4),
        _sp("server.flush", 150, 3, tickets=4),
        _sp("scheduler.tick", 200, 60),
        _sp("scheduler.admit", 201, 1, rid=7, queue_wait_ms=5.0),
        _sp("scheduler.admit", 202, 1, rid=8, queue_wait_ms=1.0),
        _sp("scheduler.admit", 203, 1, rid=9, queue_wait_ms=3.0),
        _sp("scheduler.admit", 204, 1, rid=10, queue_wait_ms=2.0),
        _sp("scheduler.admit", 205, 1, rid=11, queue_wait_ms=4.0),
        _sp("scheduler.retire", 206, 0.5, rid=11, results=1),
        _sp("dense.restack", 210, 30, rows=8, live=6, width=8),
        _sp("dense.upload", 240, 6, bytes=100e6),
        _sp("dense.bfs_chunk", 246, 8, swept=4000),
        _sp("dense.download", 254, 4, bytes=80e6),
        _sp("scheduler.harvest", 258, 4, slots=6),
        _sp("scheduler.retire", 259, 0.5, rid=4, results=2, useful=200),
        _sp("server.flush", 261, 8, tickets=6),
    ]


HAND = {
    "restack_ms": (10 + 20 + 30) / 3,
    "transfer_ms": (5 + 7 + 6) / 3 + (3 + 5 + 4) / 3,
    "tick_io_mb": (100 + 50 + 100 + 80 + 40 + 80) / 2,
    "harvest_ms": (2 + 4) / 2,
    "flush_ms": (1 + 3 + 8) / 2,
    "queue_wait_p90_ms": 1.0 + 0.9 * 4,      # linear, sorted 1..5
    "useful_sweep_fraction": (400 + 200) / (1000 + 3000 + 4000),
}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_hand_made_spans(name):
    _, readers = _readers()
    assert readers[name].value(_hand_made()) == pytest.approx(HAND[name])
    # spans without the arguments (or none at all) give nothing to read
    assert readers[name].value([]) is None
    bare = [spanargs.Span(sp.start, sp.end, sp.name, {})
            for sp in _hand_made()
            if not sp.name.startswith(("dense.", "scheduler.harvest",
                                       "server."))]
    assert readers[name].value(bare) is None


def test_span_arguments_round_trip_through_the_profiler(tmp_path,
                                                        monkeypatch):
    """Spans opened through the program's tracer with its bridge on reach
    the ``.xplane.pb`` with their numeric arguments; ``of`` keeps those
    inside the window, and the readers read them."""
    import jax
    from repro.obs import trace as otrace
    tr = otrace.Tracer()
    tr.enable(jax_annotations=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with otrace.use(tr):
            with otrace.span("scheduler.admit", rid=0, queue_wait_ms=99.0):
                pass                                  # before the window
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                with otrace.span("scheduler.tick"):
                    with otrace.span("dense.upload", bytes=3_000_000):
                        pass
                    with otrace.span("dense.bfs_chunk", rows=4, swept=500):
                        pass
                    with otrace.span("dense.download", bytes=1_000_000):
                        pass
                    with otrace.span("scheduler.admit", rid=1,
                                     queue_wait_ms=12.5, expr="a/b*"):
                        pass
                    with otrace.span("scheduler.retire", rid=1, useful=20,
                                     expr="a/b*"):
                        pass
    finally:
        jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    trace = xplane.load(path)
    monkeypatch.setattr(spanargs, "trace_path", lambda ctx: path)
    cell, readers = _readers()
    ctx = harness.Context(cell, harness.Outcome(
        0.0, harness.Window(0.0, 1.0, []), 0, None, trace=trace,
        trace_window=trace.window()))
    spans = spanargs.of(ctx)
    assert spanargs.of(ctx) is spans                  # loaded once
    admits = spanargs.named(spans, "scheduler.admit")
    assert [sp.args for sp in admits] == [{"rid": 1, "queue_wait_ms": 12.5}]
    assert spanargs.named(spans, "scheduler.retire")[0].args == \
        {"rid": 1, "useful": 20}
    assert readers["tick_io_mb"].read(ctx) == pytest.approx(4.0)
    assert readers["queue_wait_p90_ms"].read(ctx) == pytest.approx(12.5)
    assert readers["useful_sweep_fraction"].read(ctx) == pytest.approx(0.04)
    assert readers["transfer_ms"].read(ctx) > 0
    assert readers["restack_ms"].read(ctx) is None
    # without a trace the readers find nothing
    bare = harness.Context(cell, harness.Outcome(
        0.0, harness.Window(0.0, 1.0, []), 0, None))
    assert all(readers[n].read(bare) is None for n in NEW)


# what the six readers of PR 12 read on the committed trace, with the
# window below for the two that read the load generator's records
RECORDED_VALUES = {
    "gen_late_p90_ms": 220.0,
    "window_compiles": 1,
    "admit_ms": 1.06082,
    "tick_host_ms": 29.84861499999991,
    "chunk_device_ms": 862.834285,
    "device_idle_share": 0.5686462043930076,
}


@pytest.fixture(scope="module")
def recorded_trace():
    return xplane.load(str(RECORDED))


@pytest.mark.parametrize("name", list(RECORDED_VALUES) + NEW)
def test_readers_on_the_committed_chip_trace(name, recorded_trace,
                                             monkeypatch):
    monkeypatch.setattr(spanargs, "trace_path", lambda ctx: str(RECORDED))
    records = [SimpleNamespace(submitted=100.0 + late + due,
                               req=SimpleNamespace(due=due))
               for late, due in ((0.25, 1.0), (0.1, 2.0), (0.0, 3.0))]
    win = harness.Window(100.0, 51.0, records, compiles=["jit_x"])
    cell, readers = _readers()
    ctx = harness.Context(cell, harness.Outcome(
        0.0, win, 0, None, trace=recorded_trace,
        trace_window=recorded_trace.window()))
    got = readers[name].read(ctx)
    if name in NEW:
        assert got is None
    else:
        assert got == pytest.approx(RECORDED_VALUES[name], rel=1e-9)


def test_traced_run_reports_the_span_metrics():
    """A whole traced run of the harness on the CPU at tiny size: the
    program's span arguments reach all seven readers through the run's
    own trace."""
    import time

    from tpubench_testutil import tiny_cell
    res = harness.run("kg-steady", 2_147_483_659, 2.0, True,
                      time.monotonic(), require_tpu=False,
                      cell=tiny_cell("kg-steady"), rate=6.0, drain_s=20.0)
    assert res["correct"], res["checks"]
    for name in NEW:
        assert res["metrics"][name]["value"] >= 0, name
    assert 0 < res["metrics"]["useful_sweep_fraction"]["value"] <= 1
