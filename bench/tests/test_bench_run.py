"""The one command, rehearsed on the CPU at a tiny size for each cell's
shape, and its refusals."""
import json
import shutil
import subprocess
import sys

import pytest

from bench_helpers import BENCH, REPO

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _last(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("workload,e2e", [
    ("tiny-rag.poisson", {"answer_p90_ms", "answered_qps", "recall_at_10",
                          "setup_s"}),
    ("tiny-flat.poisson", {"answered_qps", "recall_at_10", "setup_s"}),
])
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
    ("tiny-rag.poisson", {"client.late_p90_ms", "harness.queue_wait_p50_ms",
                          "harness.batch_mean", "stages.host_ms",
                          "retrieve.batch_ms", "generate.tpot_p50_ms",
                          "model.mfu", "device.idle_pct"}),
    ("tiny-flat.poisson", {"client.answer_p90_ms.flat",
                           "harness.batch_mean.flat", "stages.host_ms.flat",
                           "retrieve.batch_ms.flat", "retrieve.bw_mfu",
                           "device.idle_pct.flat"}),
])
def test_traced_run_prints_the_per_layer_metrics(cpu_run, capsys, workload,
                                                 layer):
    # the CPU has no device plane and reports no peak, so the kernel and
    # model-step rooflines and the peak memory find nothing to read here
    assert cpu_run(workload, "--trace", "1") == 0
    res = _last(capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == layer
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


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
