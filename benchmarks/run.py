"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only space,query_time,...]
                                            [--smoke] [--json PATH]

Prints ``name,us_per_call,derived`` CSV (derived = the value when the row
is not a latency).  Roofline terms come from the dry-run artifacts
(see launch/roofline.py), re-emitted here for one-stop reporting.

``--smoke`` sets ``BENCH_SMOKE=1`` before the suites import, shrinking
fixtures for CI smoke runs; ``--json PATH`` additionally writes all rows
(plus per-suite wall time and errors) as a JSON document — the CI
workflow uploads it as the ``BENCH_smoke.json`` artifact so the perf
trajectory accumulates across commits.  A suite that raises prints an
``<suite>/ERROR`` row, the remaining suites still run and the JSON
document is still written, and the command exits 1.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _rows_roofline():
    from pathlib import Path
    art = Path("artifacts/dryrun")
    if not art.exists():
        return [("roofline/skipped_no_artifacts", 1)]
    from repro.launch.roofline import load_rows
    rows = []
    for r in load_rows(str(art)):
        if r["mesh"] != "16x16":
            continue
        tag = f"roofline/{r['arch']}/{r['shape']}"
        rows.append((f"{tag}/t_compute_us", r["t_compute_s"] * 1e6))
        rows.append((f"{tag}/t_memory_us", r["t_memory_s"] * 1e6))
        rows.append((f"{tag}/t_collective_us", r["t_collective_s"] * 1e6))
        rows.append((f"{tag}/model_over_hlo", r["model_over_hlo"]))
        rows.append((f"{tag}/roofline_fraction", r["roofline_fraction"]))
    return rows


def expand_row(key, val):
    """A suite row's value is usually a number; it may also be a
    ``QueryStats`` (one merged work record for the whole run — see
    ``QueryStats.merge``), which expands into one sub-row per numeric
    field via ``as_dict()`` so every stats field rides the same JSON
    document without hand-formatting."""
    if hasattr(val, "as_dict"):
        return [(f"{key}/{k}", v) for k, v in val.as_dict().items()
                if isinstance(v, (int, float))]
    return [(key, val)]


SUITES = {
    "space": lambda: __import__("benchmarks.space", fromlist=["run"]).run(),
    "query_time": lambda: __import__("benchmarks.query_time",
                                     fromlist=["run"]).run(),
    "fig8": lambda: __import__("benchmarks.patterns_fig8",
                               fromlist=["run"]).run(),
    "complexity": lambda: __import__("benchmarks.complexity",
                                     fromlist=["run"]).run(),
    "kernels": lambda: __import__("benchmarks.kernel_bench",
                                  fromlist=["run"]).run(),
    "batch_queries": lambda: __import__("benchmarks.batch_queries",
                                        fromlist=["run"]).run(),
    "sharded": lambda: __import__("benchmarks.sharded",
                                  fromlist=["run"]).run(),
    "updates": lambda: __import__("benchmarks.updates",
                                  fromlist=["run"]).run(),
    "serving": lambda: __import__("benchmarks.serving",
                                  fromlist=["run"]).run(),
    "replay": lambda: __import__("benchmarks.replay",
                                 fromlist=["run"]).run(),
    "roofline": _rows_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixtures (sets BENCH_SMOKE=1 for the suites)")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write rows as a JSON document")
    args = ap.parse_args()
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    # the suites import jax lazily; place its compile cache first
    from repro.launch.env import use_compile_cache
    use_compile_cache()
    picks = args.only.split(",") if args.only else list(SUITES)
    doc = {"smoke": bool(args.smoke), "suites": {}, "rows": {}}
    print("name,us_per_call,derived")
    for name in picks:
        t0 = time.time()
        try:
            rows = SUITES[name]()
        except Exception as e:  # a failed suite must not hide the others
            print(f"{name}/ERROR,,{type(e).__name__}:{e}")
            doc["suites"][name] = {"error": f"{type(e).__name__}:{e}"}
            continue
        for raw_key, raw_val in rows:
            for key, val in expand_row(raw_key, raw_val):
                doc["rows"][key] = float(val)
                if key.endswith("_us"):
                    print(f"{key},{val:.2f},")
                else:
                    print(f"{key},,{val}")
        dt = time.time() - t0
        doc["suites"][name] = {"seconds": round(dt, 2)}
        print(f"{name}/_suite_seconds,,{dt:.1f}", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}", file=sys.stderr)
    failed = [n for n, rec in doc["suites"].items() if "error" in rec]
    if failed:
        sys.exit(f"suites failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
