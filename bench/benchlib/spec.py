"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names every cell; everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its own
under ``bench/`` that is found by that name:

    bench/configs/<config>.json     sizes, pipeline spec, limits, reference
    bench/traffic/<traffic>.json    arrivals, rate, op mix, popularity,
                                    batch policy (``traffic.check``)
    bench/metrics/<metric>.py       ``read(ctx) -> float | None``
    bench/benchlib/<module>.py      a generator's plain reference and its
                                    cost count, named by the configuration

A configuration with a ``model`` block names the module of its plain
reference under ``model_reference`` (``make_weights(m, seed)``,
``logits_at(m, w, seqs, at, fp8=False)``; default ``ref_model``, the dense
GQA transformer) and that of its cost count under ``model_costs``
(``from_config(m)``, whose object has ``token_flops``,
``decode_step_bytes``, ``kv_bytes_per_position`` and ``head_params``;
default ``costs``).  So a later change adds a cell, a metric or a generator
of another architecture by adding files and entries, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]


# what a configuration's ``model_reference`` and ``model_costs`` default to,
# and what each named module has to expose
MODEL_MODULES = {
    "model_reference": ("ref_model", ("make_weights", "logits_at")),
    "model_costs": ("costs", ("from_config",))}
MODULE_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


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
    # the generator's plain reference and cost count; None without a model
    reference: Optional[ModuleType] = None
    costs: Optional[ModuleType] = None


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
    cfg = json.loads(cfg_path.read_text())
    cell = Cell(name=workload, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=cfg, traffic=traffic, end_to_end=e2e,
                per_layer=layer, bench_dir=bench_dir)
    if "model" in cfg:
        cell.reference = model_module(cfg, "model_reference", bench_dir)
        cell.costs = model_module(cfg, "model_costs", bench_dir)
    return cell


def _load(path: Path, name: str) -> ModuleType:
    """The file at ``path`` as a module, registered under ``name`` (as a
    dataclass in it needs)."""
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def model_module(cfg: Dict, key: str, bench_dir: Path = BENCH) -> ModuleType:
    """The module ``bench/benchlib/<name>.py`` that the configuration names
    under ``key`` (``model_reference`` or ``model_costs``), or the
    default, checked for what it has to expose."""
    default, needs = MODEL_MODULES[key]
    name = cfg.get(key, default)
    if not isinstance(name, str) or not MODULE_NAME.match(name):
        raise SpecError(f"{key} {name!r} is not a module name")
    path = bench_dir / "benchlib" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no module {path} for {key} {name!r}")
    mod = _load(path, f"bench_{key}_{name}")
    missing = [f for f in needs if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"{key} {name!r} has no {', '.join(missing)}")
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH
                  ) -> Callable[[object], Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for per-layer metric {name!r}")
    return _load(path, "bench_metric_" + name).read
