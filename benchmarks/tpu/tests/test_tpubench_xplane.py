"""The trace reduction: busy union, idle gaps named by the host span open
during them, device time per operation — on hand-made intervals, and on
a small trace recorded on a TPU v5e by a ``--trace 1`` run of
``kg-steady`` (``data/kg-steady.xplane.pb``)."""
from pathlib import Path

import pytest

from tpubench_testutil import BENCH  # noqa: F401  (sets the paths)

from tpubench import peaks, xplane

RECORDED = Path(__file__).resolve().parent / "data" / "kg-steady.xplane.pb"


def _trace():
    ops = [(10, 20, "fusion"), (15, 30, "scatter"), (50, 60, "fusion"),
           (95, 130, "fusion")]
    host = [(0, 100, xplane.WINDOW_SPAN), (5, 45, "scheduler.tick"),
            (32, 44, "scheduler.admit"), (46, 90, "scheduler.tick"),
            (61, 89, "updates.apply")]
    return xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops,
                                            [(10, 30, "jit__bfs_chunk_hetero")])],
                        host)


def test_union_and_gaps():
    t = _trace()
    ops = t.devices[0].ops
    assert xplane.union(ops, 0, 100) == pytest.approx(35e-9)
    assert xplane.gaps(ops, 0, 100) == [(0, 10), (30, 50), (60, 95)]
    assert xplane.busy_in(t, 0, 100) == pytest.approx(35e-9)
    assert xplane.merged(ops, 12, 55) == [(12, 30), (50, 55)]


def test_gaps_are_named_by_the_innermost_host_span():
    t = _trace()
    assert xplane.innermost(t.host, 40) == "scheduler.admit"
    assert xplane.innermost(t.host, 70) == "updates.apply"
    assert xplane.innermost(t.host, 2) == xplane.OUTSIDE
    ops, idle = xplane.breakdown(t, *t.window())
    assert ops[0][0] == "fusion" and ops[0][1] == pytest.approx(25e-9)
    assert [n for n, _ in idle] == ["updates.apply", "scheduler.admit",
                                    "scheduler.tick"]
    assert t.window() == (0, 100)
    assert [s for s, _, _ in t.spans("scheduler.tick", 0, 100)] == [5, 46]


def test_peak_table_refuses_unknown_devices():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


def test_recorded_chip_trace():
    t = xplane.load(str(RECORDED))
    assert len(t.devices) == 1 and t.devices[0].name.startswith("/device:TPU")
    lo, hi = t.window()
    assert hi > lo
    busy = xplane.busy_in(t, lo, hi)
    assert 0 < busy < (hi - lo) / 1e9
    ticks = t.spans("scheduler.tick", lo, hi)
    assert ticks, "the program's spans reach the profiler's host plane"
    chunks = [m for m in t.devices[0].modules if "bfs_chunk_hetero" in m[2]]
    assert chunks
    # every chunk dispatch starts inside some tick: the host spans and the
    # device events share one clock
    assert all(any(s <= c[0] <= e for s, e, _ in
                   t.spans("scheduler.tick", lo - 10**9, hi + 10**9))
               for c in chunks if lo <= c[0] <= hi)
    ops, idle = xplane.breakdown(t, lo, hi)
    assert ops and idle and len(ops) <= 10
    # ops nest (a while loop holds its body's fusions), so each op's own
    # time, not their sum, is bounded by the window
    assert all(0 < s <= (hi - lo) / 1e9 for _, s in ops)
    assert any("while" in n for n, _ in ops)
