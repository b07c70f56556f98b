"""Shared helpers of the benchmark's own tests: the benchmark's directory and
the program's sources on the path, and a checkout at a tiny size.

The checkout's ``BENCHMARK.json`` is the real one with each cell replaced
by a tiny test-only twin of the same shape (``TWINS``; their configurations
and traffic are under ``fixtures/``): the twin takes the real cell's
metrics, so a test run reads every metric the real cell reports, with the
real metric readers.
"""
import json
import os
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
FIXTURES = Path(__file__).resolve().parent / "fixtures"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny twin -> the real cell whose shape and metrics it takes
TWINS = {"tiny-rag.poisson": "phi4mini-rag.poisson",
         "tiny-flat.poisson": "msmarco-flat.overload"}


def tiny_benchmark() -> dict:
    """The real ``BENCHMARK.json`` with its cells replaced by their twins."""
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    real = {w["name"]: w for w in doc["workloads"]}
    configs = {c["name"]: c for c in doc["configs"]}
    twin_of = {r: t for t, r in TWINS.items()}
    doc["workloads"], doc["configs"] = [], []
    for tiny, r in TWINS.items():
        name = tiny.split(".")[0]
        doc["workloads"].append(dict(real[r], name=tiny, config=name,
                                     traffic=tiny))
        doc["configs"].append(dict(configs[real[r]["config"]], name=name,
                                   file=f"bench/configs/{name}.json"))
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [twin_of[w] for w in m["workloads"]
                              if w in twin_of]
    return doc


def make_checkout(dst: Path) -> Path:
    """A checkout whose cells are the tiny test-only twins."""
    (dst / "bench").mkdir(parents=True)
    (dst / "BENCHMARK.json").write_text(json.dumps(tiny_benchmark(),
                                                   indent=2))
    shutil.copytree(FIXTURES / "configs", dst / "bench" / "configs")
    shutil.copytree(FIXTURES / "traffic", dst / "bench" / "traffic")
    os.symlink(BENCH / "metrics", dst / "bench" / "metrics")
    os.symlink(REPO / "src", dst / "src")
    return dst
