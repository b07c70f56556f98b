"""The one command, rehearsed on the CPU at a tiny size for each cell's
shape, and its refusals."""
import json
import shutil
import subprocess
import sys

import pytest

from bench_helpers import BENCH, REPO, twins

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TWIN_FILES = twins()


def _last(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("workload,e2e", [
    (t["name"], set(t["end_to_end"])) for t in TWIN_FILES])
def test_end_to_end_run_prints_the_cells_metrics(cpu_run, capsys, workload,
                                                  e2e):
    assert cpu_run(workload, "--trace", "0") == 0
    res = _last(capsys)
    assert RESULT_KEYS <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == e2e
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["compiles_in_window"] == 0


@pytest.mark.parametrize("workload,layer", [
    (t["name"], set(t["per_layer"])) for t in TWIN_FILES])
def test_traced_run_prints_the_per_layer_metrics(cpu_run, capsys, workload,
                                                 layer):
    # what the CPU cannot read (no device plane, no peak) each twin file
    # names under not_read_on_cpu, with the reason
    assert cpu_run(workload, "--trace", "1") == 0
    res = _last(capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == layer
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_every_cell_has_one_twin():
    cells = [w["name"] for w in json.loads(
        (REPO / "BENCHMARK.json").read_text())["workloads"]]
    assert sorted(t["of"] for t in TWIN_FILES) == sorted(cells)


@pytest.mark.parametrize("t", TWIN_FILES, ids=lambda t: t["name"])
def test_a_twin_accounts_for_each_metric_of_its_cell(t):
    doc = json.loads((REPO / "BENCHMARK.json").read_text())

    def of_cell(metrics):
        return {m["name"] for m in metrics
                if t["of"] in m.get("workloads", [t["of"]])}
    assert set(t["end_to_end"]) == of_cell(doc["end_to_end"])
    read, not_read = set(t["per_layer"]), set(t["not_read_on_cpu"])
    assert not read & not_read
    assert read | not_read == of_cell(doc["per_layer"])
    assert all(t["not_read_on_cpu"].values())


def test_a_run_without_a_tpu_prints_no_result(capsys):
    import run
    assert run.main(["--workload", "phi4mini-rag.poisson", "--seed", "1",
                     "--seconds", "1"]) == 1
    out, err = capsys.readouterr()
    assert "no TPU found" in err and "correct" not in out


def test_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "phi4mini-rag.poisson", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
