"""Tests for the observability layer (``repro.obs``).

Histogram quantiles vs exact sample percentiles (the bounded-relative-
error property), span nesting + Chrome trace-event schema validity, the
disabled-tracer no-op property (NULL_SPAN identity, zero events), the
jax profiler bridge (numeric span arguments only), the
metrics registry (get-or-create, kind mismatch, snapshot/diff), and
Prometheus text-exposition parseability."""
import json
import math
import random
import re

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, strategies as st

from repro.obs import metrics as om
from repro.obs import trace as ot


# ---------------------------------------------------------------------
# histogram: log-bucketed quantiles vs exact percentiles
# ---------------------------------------------------------------------

def _exact_pct(samples, q):
    s = sorted(samples)
    return s[max(0, math.ceil(q * len(s)) - 1)]


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000))
def test_histogram_quantiles_track_exact_percentiles(seed):
    """The estimate must sit within a factor sqrt(growth) of the exact
    sample percentile — the histogram's designed error bound — for
    latency-like samples spanning several orders of magnitude."""
    rnd = random.Random(seed)
    h = om.Histogram("lat")
    n = rnd.randrange(5, 400)
    # lognormal-ish spread: 10us .. 10s
    samples = [10 ** rnd.uniform(-5, 1) for _ in range(n)]
    for x in samples:
        h.observe(x)
    bound = math.sqrt(h.growth) * (1 + 1e-9)
    for q in (0.5, 0.9, 0.99):
        exact = _exact_pct(samples, q)
        est = h.quantile(q)
        assert exact / bound <= est <= exact * bound, (q, exact, est)
    assert h.count == n
    assert h.min == min(samples) and h.max == max(samples)
    assert h.sum == pytest.approx(sum(samples))


def test_histogram_edge_cases():
    h = om.Histogram("h")
    assert h.quantile(0.5) == 0.0            # empty
    h.observe(0.0)                           # at/below min_value: bucket 0
    h.observe(-1.0)
    assert h.quantile(0.99) <= h.min_value
    h2 = om.Histogram("h2")
    h2.observe(3.25)                         # single sample: clamps exact
    assert h2.quantile(0.5) == pytest.approx(3.25)
    assert h2.quantile(0.99) == pytest.approx(3.25)
    with pytest.raises(ValueError):
        om.Histogram("bad", growth=1.0)


def test_histogram_memory_is_bounded_by_buckets_not_samples():
    h = om.Histogram("h")
    rnd = random.Random(3)
    for _ in range(10_000):
        h.observe(10 ** rnd.uniform(-6, 1))
    # 7 decades at ~19%/bucket: well under 150 buckets for 10k samples
    assert len(h._buckets) < 150
    assert h.count == 10_000


# ---------------------------------------------------------------------
# tracer: disabled no-op, nesting, Chrome schema
# ---------------------------------------------------------------------

def test_disabled_tracer_is_a_shared_noop():
    tr = ot.Tracer()
    assert tr.span("x") is ot.NULL_SPAN       # no allocation per call
    with tr.span("x") as sp:
        sp.set(a=1)
    with tr.span("y", rows=8):
        pass
    assert tr.events == [] and tr.dropped == 0
    # module-level path: off by default in a fresh tracer swap
    with ot.use(ot.Tracer()):
        assert ot.span("x") is ot.NULL_SPAN


def test_bypass_short_circuits_even_when_enabled():
    with ot.bypass() as tr:
        tr.enable()                           # bypass ignores enabled
        assert tr.span("x") is ot.NULL_SPAN
        assert ot.span("x") is ot.NULL_SPAN
        assert tr.events == []


def test_span_nesting_and_chrome_trace_schema(tmp_path):
    # deterministic injectable clock: each read advances 1ms
    t = [0.0]

    def clock():
        t[0] += 1e-3
        return t[0]

    tr = ot.Tracer(clock=clock)
    tr.enable()
    with ot.use(tr):
        with ot.span("outer", cat="test", depth=0):
            with ot.span("inner", cat="test") as sp:
                sp.set(depth=1)
            with ot.span("sibling", cat="test", note="hi"):
                pass
    doc = tr.chrome_trace()
    json.dumps(doc)                           # must be JSON-able
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["inner", "sibling", "outer"]
    by_name = {e["name"]: e for e in evs}
    for e in evs:
        assert set(e) >= {"name", "cat", "ph", "ts", "pid", "tid", "args"}
        assert e["ts"] >= 0
        assert e["ph"] == "X"
    # time containment (what viewers nest by): both children inside
    # outer, one after the other
    outer, inner, sib = by_name["outer"], by_name["inner"], by_name["sibling"]
    for child in (inner, sib):
        assert outer["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= outer["ts"] + outer["dur"]
    assert inner["ts"] + inner["dur"] <= sib["ts"]
    assert inner["args"] == {"depth": 1}
    assert sib["args"] == {"note": "hi"}
    # export round-trip
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        assert json.load(f)["traceEvents"] == evs


def test_tracer_drops_beyond_max_events():
    tr = ot.Tracer(max_events=3)
    tr.enable()
    for i in range(5):
        with tr.span(f"e{i}"):
            pass
    assert [e["name"] for e in tr.events] == ["e0", "e1", "e2"]
    assert tr.dropped == 2
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 2
    tr.clear()
    assert tr.events == [] and tr.dropped == 0


def test_bridge_carries_numeric_span_arguments_to_the_profiler(tmp_path):
    """With the jax bridge on, a span lands in the profiler's ``.xplane.pb``
    under its bare name, with its numeric arguments as event stats; a
    string argument (here one holding the profiler's own separators)
    stays in the tracer's Chrome events only."""
    import glob
    import jax
    from jax.profiler import ProfileData
    tr = ot.Tracer()
    tr.enable(jax_annotations=True)
    assert tr.jax_annotations
    jax.profiler.start_trace(str(tmp_path))
    try:
        with ot.use(tr):
            with ot.span("test.bridge", rows=8, bytes=123, expr="a,b=c#"):
                pass
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [ev for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if "test.bridge" in ev.name]
    assert [ev.name for ev in found] == ["test.bridge"]
    stats = dict(found[0].stats)
    assert stats == {"rows": 8, "bytes": 123}
    assert tr.events[0]["args"] == {"rows": 8, "bytes": 123, "expr": "a,b=c#"}


# ---------------------------------------------------------------------
# metrics registry + Prometheus exposition
# ---------------------------------------------------------------------

def test_registry_get_or_create_and_kind_mismatch():
    reg = om.MetricsRegistry()
    c = reg.counter("reqs", "requests")
    assert reg.counter("reqs") is c
    c.inc()
    c.inc(4)
    g = reg.gauge("depth")
    g.set(7)
    with pytest.raises(TypeError):
        reg.gauge("reqs")
    with pytest.raises(TypeError):
        reg.histogram("depth")
    snap = reg.snapshot()
    assert snap["reqs"] == 5 and snap["depth"] == 7


def test_snapshot_diff():
    reg = om.MetricsRegistry()
    reg.counter("c").inc(10)
    h = reg.histogram("h")
    h.observe(1.0)
    s0 = reg.snapshot()
    reg.counter("c").inc(5)
    h.observe(2.0)
    h.observe(4.0)
    d = om.diff_snapshots(reg.snapshot(), s0)
    assert d["c"] == 5
    assert d["h"]["count"] == 2 and d["h"]["sum"] == pytest.approx(6.0)


# every exposition line must be a comment or `name[{quantile="q"}] value`
_PROM_LINE = re.compile(
    r'^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+'
    r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{quantile="[0-9.]+"\})? -?[0-9][0-9a-z.+-]*)$')


def test_prometheus_exposition_parses():
    reg = om.MetricsRegistry()
    reg.counter("rpq_submitted_total", "total submissions").inc(3)
    reg.gauge("rpq_in_flight", "slots busy").set(2)
    h = reg.histogram("rpq_e2e_seconds", "end to end")
    for v in (0.001, 0.002, 0.004):
        h.observe(v)
    reg.counter("weird-name.with chars").inc()
    text = reg.to_prometheus()
    assert text.endswith("\n")
    for line in text.splitlines():
        assert _PROM_LINE.match(line), line
    assert "rpq_e2e_seconds_count 3" in text
    assert 'rpq_e2e_seconds{quantile="0.5"}' in text
    assert "weird_name_with_chars 1" in text   # sanitised name
