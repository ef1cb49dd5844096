"""Shared by the benchmark's tests: the cells cut to sizes a CPU test run
holds, and the import paths of the harness and the program."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from tpubench import spec  # noqa: E402


def bench():
    """BENCHMARK.json as committed."""
    return spec.load_benchmark()


CELLS = [w["name"] for w in bench()["workloads"]]


def tiny_cell(name):
    """The cell ``name`` with its graph cut to a few thousand nodes."""
    cell = spec.resolve(name, bench=bench())
    cell.config["graph"].update(nodes=4096, triples=16384, predicates=16)
    return cell
