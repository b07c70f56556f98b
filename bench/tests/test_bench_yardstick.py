"""The yardstick: discovery by name, the device table, the FLOP and byte
counts against hand counts, the references against the program at a small
size, and ``BENCHMARK.json`` against the rules it is held to."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import BENCH, REPO, make_checkout
from benchlib import costs, device, ref_model, ref_retrieve, spec

PHI4 = json.loads((BENCH / "configs" / "phi4mini-rag.json").read_text())


# -- discovery ---------------------------------------------------------------


def test_a_cell_added_as_files_alone_is_found(tmp_path):
    root = make_checkout(tmp_path / "co")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append(dict(doc["configs"][1], name="new-cfg",
                               file="bench/configs/new-cfg.json"))
    doc["workloads"].append(dict(doc["workloads"][1], name="new-cfg.burst",
                                 config="new-cfg", traffic="new-cfg.burst"))
    doc["per_layer"].append({"name": "new.metric", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "client", "moves": "answer_p90_ms",
                             "workloads": ["new-cfg.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    cfg = json.loads((root / "bench/configs/tiny-flat.json").read_text())
    (root / "bench/configs/new-cfg.json").write_text(json.dumps(
        dict(cfg, name="new-cfg")))
    tr = json.loads((root / "bench/traffic/tiny-flat.poisson.json")
                    .read_text())
    tr["arrival"]["rate_qps"] = 9.5
    (root / "bench/traffic/new-cfg.burst.json").write_text(json.dumps(tr))
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    (metrics / "new.metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.x\n")
    bench_dir = tmp_path / "benchdir"
    bench_dir.mkdir()
    (bench_dir / "traffic").symlink_to(root / "bench" / "traffic")
    (bench_dir / "metrics").symlink_to(metrics)
    cell = spec.load_cell("new-cfg.burst", root, bench_dir)
    assert cell.config["name"] == "new-cfg"
    assert cell.traffic["arrival"]["rate_qps"] == 9.5
    assert [m.name for m in cell.per_layer] == ["new.metric"]

    class Ctx:
        x = 1.5
    assert spec.metric_reader("new.metric", bench_dir)(Ctx) == 3.0


def test_an_unknown_workload_is_refused(tmp_path):
    root = make_checkout(tmp_path / "co")
    with pytest.raises(spec.SpecError):
        spec.load_cell("nope.poisson", root, root / "bench")


@pytest.mark.parametrize("key,value", [
    (("ops",), {"query": 0.9, "update": 0.1}),
    (("ops",), {"search": 1.0}),
    (("questions", "popularity"), "zipf"),
    (("arrival", "process"), "bursty"),
])
def test_a_mix_the_generator_does_not_implement_is_refused(tmp_path, key,
                                                           value):
    root = make_checkout(tmp_path / "co")
    path = root / "bench/traffic/tiny-flat.poisson.json"
    tr = json.loads(path.read_text())
    parent = tr
    for k in key[:-1]:
        parent = parent[k]
    parent[key[-1]] = value
    path.write_text(json.dumps(tr))
    with pytest.raises(spec.SpecError, match="not implemented"):
        spec.load_cell("tiny-flat.poisson", root, root / "bench")


def test_the_configuration_states_the_programs_model():
    from benchlib.cell import check_model
    check_model(PHI4)
    unstated = json.loads(json.dumps(PHI4))
    del unstated["assumed"]["tie_word_embeddings"]
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        check_model(unstated)
    wrong = json.loads(json.dumps(PHI4))
    wrong["model"]["d_ff"] = 8000
    with pytest.raises(ValueError, match="d_ff"):
        check_model(wrong)


# -- device ------------------------------------------------------------------


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(device.DeviceError):
        device.peaks_for("TPU v99")
    assert device.peaks_for("TPU v5 lite").hbm_bytes_s == 819e9


def test_the_gate_refuses_the_cpu_and_too_few_chips():
    with pytest.raises(device.DeviceError, match="no TPU"):
        device.gate(1, "tpu")
    with pytest.raises(device.DeviceError, match="chip"):
        device.gate(len(jax.devices()) + 1, "cpu")


# -- FLOP and byte counts against hand counts ----------------------------------


def test_phi4_mini_decode_step_against_a_hand_count():
    m = costs.Dense.from_config(PHI4["model"])
    # per layer: wq, wo 3072x3072; wk, wv 3072x1024; gate, up, down 3072x8192
    assert m.layer_params == 100_663_296
    # 32 layers + head 3072x200064 + 65 norms of 3072, bf16; 4 embedding
    # rows; keys and values 2 x 32 x 8 x 128 x 2 bytes per position, read
    # for positions below each write and written at it
    pos = [1024, 1030, 1040, 1050]
    assert m.decode_step_bytes(pos) == 7_672_043_520 + 24_576 + \
        (4144 + 4) * 131_072
    # a decode token at position 1024 attends to 1,025 keys
    assert m.token_flops(1025, True) == 6_442_450_944 + 403_046_400 + \
        1_229_193_216
    assert m.token_flops(1, False) == 6_442_450_944 + 393_216


def test_flat_launch_against_a_hand_count():
    assert costs.flat_launch_bytes(1 << 20, 768, 32, 16) == \
        3_221_225_472 + 98_304 + 4_096


# -- references against the program at a small size ----------------------------

SMALL = {"n_layers": 2, "d_model": 96, "n_heads": 3, "n_kv_heads": 1,
         "head_dim": 32, "d_ff": 192, "vocab_size": 512, "rope_theta": 1e4,
         "norm_eps": 1e-5, "dtype": "bfloat16"}


def _program_cfg(dtype):
    from repro.models.config import ModelConfig
    return ModelConfig(name="t", family="dense", n_layers=2, d_model=96,
                       n_heads=3, n_kv_heads=1, d_ff=192, vocab_size=512,
                       rope_theta=1e4, dtype=dtype, remat="none")


def test_reference_weights_are_the_programs_bit_for_bit():
    from repro.models import transformer
    prog = transformer.init(jax.random.PRNGKey(5), _program_cfg("bfloat16"))
    ref = ref_model.make_weights(SMALL, 5)
    flat = {"embed": prog["embed"], "final_norm": prog["final_norm"],
            "lm_head": prog["lm_head"], **prog["layers"]["attn"],
            **prog["layers"]["mlp"],
            "attn_norm": prog["layers"]["attn_norm"],
            "mlp_norm": prog["layers"]["mlp_norm"]}
    assert set(flat) == set(ref)
    for k in ref:
        assert ref[k].dtype == flat[k].dtype, k
        assert (np.asarray(ref[k]) == np.asarray(flat[k])).all(), k


def test_reference_forward_matches_the_programs_float32_forward():
    from repro.models import transformer
    cfg = _program_cfg("float32")
    m = dict(SMALL, dtype="float32")
    w = ref_model.make_weights(m, 9)
    params = {"embed": w["embed"], "final_norm": w["final_norm"],
              "lm_head": w["lm_head"],
              "layers": {"attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                         "mlp": {k: w[k] for k in ("w_gate", "w_up",
                                                    "w_down")},
                         "attn_norm": w["attn_norm"],
                         "mlp_norm": w["mlp_norm"]}}
    toks = np.random.default_rng(0).integers(4, 512, size=(1, 40))
    with jax.default_matmul_precision("highest"):
        want, _ = transformer.forward(params, cfg,
                                      {"tokens": jnp.asarray(toks)})
    got = ref_model.logits_at(m, w, [toks[0]], [list(range(40))])
    np.testing.assert_allclose(got, np.asarray(want)[0], atol=2e-4)


def test_hash_embedding_copy_is_the_programs():
    from repro.core.embedder import HashEmbedder
    texts = ["what is the capital of entity7?", "", "w1 w2 w3 the of"]
    prog = HashEmbedder(dim=32, seed=11).embed(texts)
    ref = ref_retrieve.HashEmbed(32, 32768, 11)(texts)
    assert (prog == ref).all()


def test_quantile_gaps_are_the_same_set_in_another_order():
    from benchlib import traffic
    a = traffic.arrival_times(4.0, 160, seed=1)
    b = traffic.arrival_times(4.0, 160, seed=2 ** 31 + 5)
    assert not np.array_equal(a, b)
    ga, gb = np.sort(np.diff(a)), np.sort(np.diff(b))
    assert len(ga) == len(gb) and abs(ga.sum() - gb.sum()) < 1.0
    tr = {"arrival": {"process": "poisson", "rate_qps": 4.0},
          "ops": {"query": 1.0}, "questions": {"popularity": "uniform"}}
    assert len(traffic.due_times(tr, 40.0, 3)) == 160


# -- BENCHMARK.json against its rules -----------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_keeps_its_rules():
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["bench"] and 1 <= doc["run_seconds"] <= 51
    cfgs = {c["name"]: c for c in doc["configs"]}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file() and NAME.match(c["name"])
        assert json.loads((REPO / c["file"]).read_text())["reduced"] \
            == c["reduced"]
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    layers = {}
    for w in doc["workloads"]:
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and NAME.match(w["name"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in doc["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m["workloads"]) <= {w["name"] for w in doc["workloads"]}
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in re.split(r"[._]",
                                                                m["name"]):
            assert m["unit"] == "%"
    for w in doc["workloads"]:
        assert any(w["name"] in m["workloads"] for m in doc["per_layer"])
