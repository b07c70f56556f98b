"""A generator of another family goes in as files alone.

``fixtures/moe_cell/`` holds what a later change would add to the
repository for a cell whose generator is of the program's ``moe`` family:
its plain reference and cost count under ``bench/benchlib/`` (named by the
configuration's ``model_reference`` and ``model_costs``), its tiny twin's
configuration, traffic and twin file under ``bench/tests/fixtures/``, and
the entries it adds to ``BENCHMARK.json``.  Laid over a copy of this
repository, nothing else edited, the twin runs on the CPU and is held to
its own reference."""
import json
import shutil

from bench_helpers import BENCH, FIXTURES, REPO, make_checkout, twins

CELL = FIXTURES / "moe_cell"
TWIN = "tiny-moe.poisson"


def _later_change(tmp_path):
    """A copy of the repository with the moe cell's files and entries
    added, and a checkout of it."""
    repo = tmp_path / "repo"
    shutil.copytree(BENCH, repo / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "moe_cell"))
    shutil.copytree(CELL / "bench", repo / "bench", dirs_exist_ok=True)
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    add = json.loads((CELL / "BENCHMARK.add.json").read_text())
    doc["configs"] += add["configs"]
    doc["workloads"] += add["workloads"]
    cell = add["workloads"][0]["name"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in add["metric_workloads"]:
            m["workloads"].append(cell)
    (repo / "BENCHMARK.json").write_text(json.dumps(doc, indent=2))
    return repo, make_checkout(tmp_path / "co", repo)


def _last(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_moe_twin_is_correct_against_its_own_reference(
        cpu_run, capsys, tmp_path):
    repo, co = _later_change(tmp_path)
    (twin,) = [t for t in twins(repo) if t["name"] == TWIN]
    assert cpu_run(TWIN, "--trace", "0", root=co) == 0
    res = _last(capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == set(twin["end_to_end"])
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["compiles_in_window"] == 0


def test_the_moe_twin_reads_its_per_layer_metrics_by_its_own_count(
        cpu_run, capsys, tmp_path):
    repo, co = _later_change(tmp_path)
    (twin,) = [t for t in twins(repo) if t["name"] == TWIN]
    assert cpu_run(TWIN, "--trace", "1", root=co) == 0
    res = _last(capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == set(twin["per_layer"])
    assert res["metrics"]["model.mfu"]["value"] > 0


def test_the_moe_twin_against_a_top_1_reference_is_not_correct(
        cpu_run, capsys, tmp_path):
    _, co = _later_change(tmp_path)
    lib = co / "bench" / "benchlib"
    src = (CELL / "bench" / "benchlib" / "moe_ref.py").read_text()
    assert src.count('int(m["moe"]["top_k"])') == 1
    (lib / "moe_ref_top1.py").write_text(
        src.replace('int(m["moe"]["top_k"])', "1"))
    path = co / "bench" / "configs" / "tiny-moe.json"
    cfg = json.loads(path.read_text())
    path.write_text(json.dumps(dict(cfg, model_reference="moe_ref_top1")))
    assert cpu_run(TWIN, root=co) == 0
    res = _last(capsys)
    assert res["correct"] is False
    gap = res["checks"]["gen.logit_gap"]
    assert gap["value"] > gap["limit"]
