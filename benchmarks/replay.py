"""Workload replay: re-execute a flight-recorder capture against both
engines and assert result-count parity.

    PYTHONPATH=src python -m benchmarks.replay [--smoke] [--json PATH]
        [--workload PATH] [--workload-out PATH] [--analyze-out PATH]

The other half of the flight recorder (``repro.obs.recorder``): any
JSONL workload the :class:`repro.core.scheduler.SlotScheduler` dumped —
from ``examples/serve_rpq.py --record``, the ``/flight`` endpoint, or
this module's own self-capture — is schema-validated, its graph rebuilt
from the header's fixture spec, and every ``status == "ok"`` record
re-executed **open-loop** (batched through ``eval_many``, no arrival
pacing: replay measures engine throughput on a real trace, not the
original schedule) on BOTH engines.  Each replayed query's result count
is checked against the recorded one — the recorder writes the pre-limit
count, so the expectation is ``min(results, limit)`` when a limit was
set.

With no ``--workload``, the suite captures its own: a slot-scheduler
burst over the serving benchmark's workload mix on a scale-free
fixture, dumped with a ``graph`` fixture spec and round-tripped through
``recorder.load`` — so the capture format itself is exercised every
run.  Self-captures replay at the same epoch, so parity below 1.0 is a
bug and fails the suite loudly; external captures (which may have seen
interleaved updates) only report the fraction.

Rows:

    replay/records                      records replayed (informational)
    replay/<engine>/us_per_query        mean replay cost per ok-record
    replay/<engine>/parity_fraction     fraction with exact count parity

``--analyze-out PATH`` additionally writes one schema-validated ANALYZE
report (the heaviest replayed expression, dense engine) — the CI
serving job uploads it as an observability artifact.
``--smoke`` / BENCH_SMOKE=1 shrinks the self-capture fixture for CI.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

if __package__ in (None, ""):                       # direct-script run
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))

import numpy as np

_FULL = dict(V=600, E=4_800, n=32)
_SMOKE = dict(V=300, E=2_400, n=16)


def _capture(path):
    """Self-capture: serve a burst through the slot scheduler on a
    scale-free fixture and dump the recorder ring — with the graph's
    fixture spec in the header so :func:`_rebuild_graph` can replay it
    from the file alone."""
    from benchmarks.serving import _run_slot, _workload
    from repro.core.engines import make_engine
    from repro.core.fixtures import scale_free_graph

    cfg = _SMOKE if os.environ.get("BENCH_SMOKE") == "1" else _FULL
    spec = {"fixture": "scale_free_graph",
            "args": [cfg["V"], 8, cfg["E"]], "seed": 23}
    g = scale_free_graph(*spec["args"], seed=spec["seed"])
    queries = _workload(g, cfg["n"], np.random.default_rng(7))
    eng = make_engine(g, "dense")
    _, _, sched = _run_slot(eng, queries, [0.0] * len(queries))
    return sched.recorder.dump(path, graph=spec)


def _rebuild_graph(header):
    from repro.core import fixtures
    spec = header.get("graph")
    if not spec:
        raise ValueError("workload header has no graph fixture spec; "
                         "replay needs one to rebuild the graph")
    return getattr(fixtures, spec["fixture"])(*spec["args"],
                                              seed=spec.get("seed"))


def _replayable(records):
    """The ok-records as Query objects + their expected result counts
    (the recorder stores the pre-limit count; ``eval_many`` truncates)."""
    from repro.core.engines import Query
    qs, expected = [], []
    for r in records:
        if r["status"] != "ok":
            continue
        qs.append(Query(r["expr"], subject=r["subject"], obj=r["obj"],
                        limit=r["limit"]))
        expected.append(r["results"] if r["limit"] is None
                        else min(r["results"], r["limit"]))
    return qs, expected


def _replay_engine(g, kind, qs, expected):
    """Replay the trace on a fresh engine -> (us_per_query, parity)."""
    from repro.core.engines import make_engine
    eng = make_engine(g, kind)
    eng.eval_many(qs)                   # compiles out of the timed pass
    eng.results.clear()
    t0 = time.perf_counter()
    outs = eng.eval_many(qs)
    elapsed = time.perf_counter() - t0
    match = sum(1 for out, want in zip(outs, expected)
                if len(out) == want)
    return (elapsed / max(1, len(qs)) * 1e6,
            match / max(1, len(qs)))


def _write_analyze(path, g, qs):
    """One schema-validated ANALYZE report over the heaviest replayed
    expression (longest automaton), dense engine — the CI artifact."""
    from repro.core.engines import Query, make_engine
    from repro.obs import explain as oexplain

    q = max(qs, key=lambda q: len(q.expr))
    eng = make_engine(g, "dense")
    report = eng.explain(Query(q.expr, subject=q.subject, obj=q.obj,
                               limit=q.limit), analyze=True)
    oexplain.validate_report(report)
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"wrote {path}", file=sys.stderr)


def run(workload=None, workload_out=None, analyze_out=None,
        max_records=None):
    from repro.obs import recorder as orecorder

    external = workload is not None
    if not external:
        workload = workload_out or os.path.join(
            tempfile.mkdtemp(prefix="rpq-replay-"), "workload.jsonl")
        _capture(workload)
        print(f"captured {workload}", file=sys.stderr)
    header, records = orecorder.load(workload)
    qs, expected = _replayable(records)
    if not qs:
        raise ValueError(f"no ok-records to replay in {workload}")
    if max_records is not None and len(qs) > max_records:
        # no silent caps: a truncated replay must say so
        print(f"replaying first {max_records} of {len(qs)} ok-records "
              f"(--max-records)", file=sys.stderr)
        qs, expected = qs[:max_records], expected[:max_records]
    g = _rebuild_graph(header)
    rows = [("replay/records", float(len(qs)))]
    for kind in ("ring", "dense"):
        us, parity = _replay_engine(g, kind, qs, expected)
        rows.append((f"replay/{kind}/us_per_query", us))
        rows.append((f"replay/{kind}/parity_fraction", parity))
        if not external and parity < 1.0:
            raise RuntimeError(
                f"replay parity broke on {kind}: {parity:.3f} < 1.0 on a "
                f"same-epoch self-capture ({workload})")
    if analyze_out:
        _write_analyze(analyze_out, g, qs)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny self-capture fixture (sets BENCH_SMOKE=1)")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write rows as a JSON document (the shape "
                         "benchmarks/run.py emits, for benchmarks/compare.py)")
    ap.add_argument("--workload", type=str, default=None, metavar="PATH",
                    help="replay an existing capture instead of "
                         "self-capturing (parity reported, not asserted)")
    ap.add_argument("--workload-out", type=str, default=None, metavar="PATH",
                    help="write the self-capture JSONL here (default: a "
                         "temp dir)")
    ap.add_argument("--analyze-out", type=str, default=None, metavar="PATH",
                    help="also write one schema-validated ANALYZE report "
                         "(heaviest replayed expression, dense engine)")
    ap.add_argument("--max-records", type=int, default=None, metavar="N",
                    help="replay at most N ok-records (bounds the cost of "
                         "replaying a large production capture; the "
                         "truncation is logged, never silent)")
    args = ap.parse_args()
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    doc = {"smoke": bool(args.smoke), "suites": {}, "rows": {}}
    print("name,us_per_call,derived")
    t0 = time.time()
    try:
        rows = run(workload=args.workload, workload_out=args.workload_out,
                   analyze_out=args.analyze_out,
                   max_records=args.max_records)
    except Exception as e:   # mirror benchmarks.run: emit the doc, exit 1
        print(f"replay/ERROR,,{type(e).__name__}:{e}")
        doc["suites"]["replay"] = {"error": f"{type(e).__name__}:{e}"}
        rows = []
    for key, val in rows:
        doc["rows"][key] = float(val)
        print(f"{key},,{val}")
    if rows:
        doc["suites"]["replay"] = {"seconds": round(time.time() - t0, 2)}
        print(f"replay/_suite_seconds,,{time.time() - t0:.1f}", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}", file=sys.stderr)
    if "error" in doc["suites"].get("replay", {}):
        sys.exit(1)


if __name__ == "__main__":
    main()
