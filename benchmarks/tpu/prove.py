"""Runs the benchmark's own runs do not make, each in one process:

* the knee sweep — one window per offered rate, all from the mix's one
  request pool (the pool at a lower rate is the first part of the pool
  at a higher one), reporting latency, how far the queue grew (median
  latency of the window's last third over its first third) and how
  long the queue took to drain after the close;
* the readings that set the limits of ``correct`` — one window per seed
  at the mix's frozen rate, with the program's answers and, with
  ``--control``, the control's (the reference with every closure one
  level short of its fixpoint) compared against the reference on the
  same sampled requests.

    python3 benchmarks/tpu/prove.py --workload kg-steady --seconds 51 \\
        --rates 0.3,0.5,0.8
    python3 benchmarks/tpu/prove.py --workload kg-steady --seconds 51 \\
        --seeds 11,12,13 --control

Every window gets a served system of its own, built and warmed up as a
run's set-up is: a window on a system that has served the pool before
would find its answers in the result cache.  Prints one JSON line per
window.
"""
import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

SWEEP_SEED = 1      # the sweep's windows differ by rate, not by order


def _floats(s):
    return [float(x) for x in s.split(",") if x]


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def summary(cell, out):
    import numpy as np
    from tpubench import harness
    win = out.win
    order = sorted((r for r in win.records if r.answer is not None),
                   key=lambda r: r.req.due)
    third = max(1, len(order) // 3)

    def med(rs):
        return float(np.median([(r.done - win.t0 - r.req.due) * 1e3
                                for r in rs])) if rs else None

    done = [r.done for r in win.records if r.done is not None]
    close = win.t0 + win.seconds
    e2e = harness.end_to_end(cell, out)
    return {
        "requests": len(win.records), "answered": len(order),
        **{k: v["value"] for k, v in e2e.items() if k != "setup_s"},
        "first_third_median_ms": med(order[:third]),
        "last_third_median_ms": med(order[-third:]),
        "drain_s": (max(done) - close) if done else None,
        # due before the close and not answered by then: more than the
        # slots hold means a queue had built up
        "backlog_at_close": sum(1 for r in win.records
                                if win.t0 + r.req.due < close
                                and (r.done is None or r.done > close)),
        "cache_hits": sum(r.cache_hit for r in win.records),
        "plans": sorted({r.plan for r in win.records if r.plan}),
        "window_compiles": len(win.compiles),
    }


async def prove(args):
    from tpubench import harness, spec
    from tpubench import traffic as tr
    cell = spec.resolve(args.workload)
    devs = harness.check_devices(cell.chips)
    print(f"device: {devs[0].device_kind} x{len(devs)}", file=sys.stderr)
    data = cell.generator.build(cell.config)
    graph = harness.reference_graph(data)
    items = [("rate", r) for r in _floats(args.rates)] \
        + [("seed", s) for s in _ints(args.seeds)]
    for kind, v in items:
        seed = int(v) if kind == "seed" else SWEEP_SEED
        rate = float(v) if kind == "rate" else None
        plan = tr.window_plan(cell.mix, data, args.seconds, seed, graph,
                              rate=rate)
        sess = harness.Session(cell, data, time.monotonic())
        await sess.open(plan.cached)
        win, _ = await sess.window(plan, args.seconds)
        settled = all(r.error is None for r in win.records)
        out = harness.Outcome(sess.setup_s, win, 0, graph)
        row = {"workload": cell.name, kind: v, "setup_s": sess.setup_s,
               **summary(cell, out)}
        t0 = time.monotonic()
        row["checks"] = harness.check(cell, data, out, seed)
        if args.control:
            row["control_checks"] = harness.check(cell, data, out, seed,
                                                  control=True)
        row["check_s"] = time.monotonic() - t0
        print(json.dumps(row), flush=True)
        if not settled:
            return 1        # a request never settled: the pump cannot stop
        await sess.close(True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    from tpubench import harness
    harness.setup_env()
    return asyncio.run(prove(args))


if __name__ == "__main__":
    sys.exit(main())
