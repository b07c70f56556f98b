"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names every cell; everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its own
under ``bench/`` that is found by that name:

    bench/configs/<config>.json     sizes, pipeline spec, limits, reference
    bench/traffic/<traffic>.json    arrivals, rate, op mix, popularity,
                                    batch policy (``traffic.check``)
    bench/metrics/<metric>.py       ``read(ctx) -> float | None``

so a later change adds a cell or a metric by adding files and entries, and
edits none.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]


class SpecError(Exception):
    """A cell, configuration, traffic mix or metric that cannot be found."""


@dataclass
class Metric:
    name: str
    unit: str
    workloads: Optional[List[str]] = None

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    bench_dir: Path


def _metric(d: Dict) -> Metric:
    return Metric(name=d["name"], unit=d["unit"],
                  workloads=d.get("workloads"))


def load_cell(workload: str, root: Path, bench_dir: Path = BENCH) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with its files."""
    doc = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in doc["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in doc["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names an unknown config "
                        f"{w['config']!r}")
    cfg_path = root / configs[w["config"]]["file"]
    traffic_path = bench_dir / "traffic" / f"{w['traffic']}.json"
    for p in (cfg_path, traffic_path):
        if not p.is_file():
            raise SpecError(f"missing file {p} for workload {workload!r}")
    from benchlib import traffic as traffic_lib
    traffic = json.loads(traffic_path.read_text())
    traffic_lib.check(traffic)
    e2e = [m for m in map(_metric, doc["end_to_end"])
           if m.applies_to(workload)]
    layer = [m for m in map(_metric, doc["per_layer"])
             if m.applies_to(workload)]
    return Cell(name=workload, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=json.loads(cfg_path.read_text()), traffic=traffic,
                end_to_end=e2e, per_layer=layer, bench_dir=bench_dir)


def metric_reader(name: str, bench_dir: Path = BENCH
                  ) -> Callable[[object], Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
