"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's entry names its JSON file, which names the
generator module beside it; the mix is ``traffic/<traffic>.json``; each
per-layer metric is the reader ``metrics/<name>.py``.  Nothing here
lists configurations, mixes or metrics: adding one is adding its files
and its entry.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
BENCHMARK_JSON = ROOT / "BENCHMARK.json"


def load_benchmark(path: Path = BENCHMARK_JSON) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Metric:
    name: str
    unit: str
    reader: ModuleType


@dataclass
class Cell:
    """One workload resolved to its files."""

    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's contents
    config_dir: Path
    generator: ModuleType           # the configuration's data generator
    mix: Dict[str, Any]             # the traffic file's contents
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell_name: str, bench: Optional[Dict[str, Any]] = None,
            root: Path = ROOT) -> Cell:
    """The cell called ``cell_name`` with its configuration, generator,
    mix and metric readers loaded.  Raises ``KeyError`` for an unknown
    cell and ``FileNotFoundError`` for a missing file."""
    bench = bench if bench is not None else load_benchmark(
        root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in work:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[cell_name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf_path = root / conf_entry["file"]
    with open(conf_path) as f:
        config = json.load(f)
    generator = load_module(conf_path.parent / config["generator"],
                            f"tpubench_config_{w['config'].replace('-', '_')}")
    mix_path = root / bench["paths"][0] / "traffic" / f"{w['traffic']}.json"
    with open(mix_path) as f:
        mix = json.load(f)
    cell = Cell(name=cell_name, chips=int(w["chips"]), config=config,
                config_dir=conf_path.parent, generator=generator, mix=mix)
    cell.end_to_end = [m for m in bench["end_to_end"] if _applies(m, cell_name)]
    metrics_dir = root / bench["paths"][0] / "metrics"
    for m in bench["per_layer"]:
        if _applies(m, cell_name):
            reader = load_module(metrics_dir / f"{m['name']}.py",
                                 f"tpubench_metric_{m['name']}")
            cell.per_layer.append(Metric(m["name"], m["unit"], reader))
    return cell
