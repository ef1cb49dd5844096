"""Sharded wavefront scaling: the same eval_many workload on 1/2/4/8
forced host devices.

Each device count runs in its OWN subprocess (XLA_FLAGS must be set
before jax imports) that builds the dense engine with ``shards=d`` and
times a mixed-expression ``eval_many`` batch — the heterogeneous bucket
the sharded row partition was built for.  Rows:

    sharded/dense/devices{d}/us_per_query   batch latency per query
    sharded/dense/devices{d}/supersteps     sharded supersteps executed
    sharded/dense/scaling_vs_1dev/x{d}      t(1 device) / t(d devices)

This is a CPU rehearsal of the sharded path by design: every child sets
``JAX_PLATFORMS=cpu`` and forces its devices on the host, so it never
competes for an accelerator the parent may hold.  The forced devices
share the same cores, so the scaling column measures partitioning
overhead, not speedup.  On a TPU the sharded path is driven by
``python chip_smoke.py --four-chips``.  ``--smoke`` (or BENCH_SMOKE=1)
shrinks the fixture.

    PYTHONPATH=src python -m benchmarks.sharded [--smoke]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

DEVICE_COUNTS = (1, 2, 4, 8)

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, time
import numpy as np
from repro.core.engines import Query, make_engine
from repro.core.fixtures import scale_free_graph

g = scale_free_graph({V}, {P}, {E}, seed=7)
eng = make_engine(g, "dense", shards={devices})
rng = np.random.default_rng(0)
exprs = ["0/1*", "(0|3)+", "^1/0*", "2"]
queries = [Query(e, obj=int(o)) for e in exprs
           for o in rng.integers(0, g.num_nodes, {per_expr})]
eng.eval_many(queries)          # warm-up: compile the sharded supersteps
eng.results.clear()
s0 = eng.sharded.supersteps
t0 = time.time()
eng.eval_many(queries)
dt = time.time() - t0
print(json.dumps({{"seconds": dt, "queries": len(queries),
                   "supersteps": eng.sharded.supersteps - s0}}))
"""


def _run_child(devices: int, V: int, P: int, E: int, per_expr: int) -> dict:
    code = _CHILD.format(devices=devices, V=V, P=P, E=E, per_expr=per_expr)
    env = {**os.environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    if r.returncode != 0:
        raise RuntimeError(
            f"sharded child (devices={devices}) failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run():
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    V, P, E = (400, 6, 3_000) if smoke else (4_000, 16, 30_000)
    per_expr = 4 if smoke else 16
    rows = []
    t1 = None
    for d in DEVICE_COUNTS:
        rec = _run_child(d, V, P, E, per_expr)
        per_query = rec["seconds"] / rec["queries"]
        rows.append((f"sharded/dense/devices{d}/us_per_query",
                     per_query * 1e6))
        rows.append((f"sharded/dense/devices{d}/supersteps",
                     rec["supersteps"]))
        if d == 1:
            t1 = rec["seconds"]
        else:
            rows.append((f"sharded/dense/scaling_vs_1dev/x{d}",
                         t1 / rec["seconds"]))
    return rows


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        os.environ["BENCH_SMOKE"] = "1"
    for key, val in run():
        print(f"{key},{val}")
