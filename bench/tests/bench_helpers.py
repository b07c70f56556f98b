"""Shared helpers of the benchmark's own tests: the benchmark's directory and
the program's sources on the path, and a checkout at a tiny size.

Each cell of ``BENCHMARK.json`` has one tiny test-only twin of the same
shape, a file ``fixtures/twins/<twin>.json``: ``of`` names the real cell,
``config`` the twin's configuration (``fixtures/configs/<config>.json``; its
traffic is ``fixtures/traffic/<twin>.json``), ``end_to_end`` and
``per_layer`` the metrics a CPU run of it reads, and ``not_read_on_cpu``
why each other per-layer metric of the real cell reads nothing there.  The
checkout's ``BENCHMARK.json`` is the real one with each cell replaced by its
twin, which takes the real cell's metrics, so a test run reads every metric
it can with the real metric readers.  A cell added later brings its twin as
files.
"""
import json
import os
import shutil
import sys
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
FIXTURES = Path(__file__).resolve().parent / "fixtures"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _fixtures(repo: Path) -> Path:
    return repo / "bench" / "tests" / "fixtures"


def twins(repo: Path = REPO) -> List[Dict]:
    """Every twin file of ``repo``, with its ``name``, in the order of the
    cells of its ``BENCHMARK.json`` (a twin of no cell last)."""
    cells = [w["name"] for w in json.loads(
        (repo / "BENCHMARK.json").read_text())["workloads"]]
    out = [dict(json.loads(p.read_text()), name=p.stem)
           for p in sorted((_fixtures(repo) / "twins").glob("*.json"))]
    return sorted(out, key=lambda t: (cells.index(t["of"]) if t["of"] in cells
                                      else len(cells)))


def tiny_benchmark(repo: Path = REPO) -> dict:
    """``repo``'s ``BENCHMARK.json`` with its cells replaced by their twins."""
    doc = json.loads((repo / "BENCHMARK.json").read_text())
    real = {w["name"]: w for w in doc["workloads"]}
    configs = {c["name"]: c for c in doc["configs"]}
    tw = twins(repo)
    twin_of = {t["of"]: t["name"] for t in tw}
    doc["workloads"], doc["configs"] = [], []
    for t in tw:
        r = real[t["of"]]
        doc["workloads"].append(dict(r, name=t["name"], config=t["config"],
                                     traffic=t["name"]))
        doc["configs"].append(dict(configs[r["config"]], name=t["config"],
                                   file=f"bench/configs/{t['config']}.json"))
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [twin_of[w] for w in m["workloads"]
                              if w in twin_of]
    return doc


def make_checkout(dst: Path, repo: Path = REPO) -> Path:
    """A checkout of ``repo`` whose cells are the tiny test-only twins."""
    (dst / "bench" / "benchlib").mkdir(parents=True)
    (dst / "BENCHMARK.json").write_text(json.dumps(tiny_benchmark(repo),
                                                   indent=2))
    shutil.copytree(_fixtures(repo) / "configs", dst / "bench" / "configs")
    shutil.copytree(_fixtures(repo) / "traffic", dst / "bench" / "traffic")
    for f in (repo / "bench" / "benchlib").glob("*.py"):
        os.symlink(f.resolve(), dst / "bench" / "benchlib" / f.name)
    os.symlink((repo / "bench" / "metrics").resolve(),
               dst / "bench" / "metrics")
    os.symlink(REPO / "src", dst / "src")
    return dst
