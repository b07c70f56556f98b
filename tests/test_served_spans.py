"""The served path's own spans and counters (``repro.obs.span``, ``count``,
``observe``): the index copy's bytes, the spans on the profiler's host plane
nested in the benchmark's names, the engine's program names, each request's
first token, the process registry's bound, the ``--trace-out`` export, and
the benchmark's readers of them."""
import glob
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.interfaces import Chunk
from repro.core.registry import build
from repro.core.spec import GenSpec, PipelineSpec, StageSpec
from repro.core.vectordb import make_db
from repro.obs.metrics import MetricsRegistry
from repro.serving.arrival import ArrivalConfig
from repro.serving.batcher import BatchPolicy
from repro.serving.harness import ServingConfig, ServingHarness
from repro.workload.corpus import CorpusConfig, SyntheticCorpus
from repro.workload.generator import WorkloadConfig

ROOT = Path(__file__).resolve().parents[1]
DIM, ROWS, CAP = 32, 256, 512


def _db(index_type, **kw):
    db = make_db(index_type, dim=DIM, capacity=CAP, nlist=8, nprobe=2,
                 use_hybrid=False, **kw)
    v = np.random.default_rng(0).standard_normal((ROWS, DIM))
    v = (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(np.float32)
    db.insert(v, [Chunk(-1, i, "") for i in range(ROWS)])
    db.build_index()
    return db, v


def _grown(name, before):
    return obs.REGISTRY.counter_value(name) - before


@pytest.mark.parametrize("index_type", ["flat", "ivf"])
def test_a_search_counts_the_bytes_it_sends(index_type):
    db, v = _db(index_type)
    q = v[:3]
    send = q.nbytes + db.vectors.nbytes + db.live.nbytes
    if index_type == "ivf":
        send += (db.centroids.nbytes + db.buckets.nbytes
                 + db.bucket_live.nbytes)
    b0 = obs.REGISTRY.counter_value("db.h2d_bytes")
    c0 = obs.REGISTRY.counter_value("db.search_calls")
    db.search(q, 5)
    assert _grown("db.h2d_bytes", b0) == send
    assert _grown("db.search_calls", c0) == 1
    assert db.stats()["h2d_bytes"] == send


def _resident_counts():
    return [obs.REGISTRY.counter_value(name) for name in
            ("db.h2d_bytes", "db.resident_hits", "db.resident_pushes")]


def _grown_counts(before):
    return [a - b for a, b in zip(_resident_counts(), before)]


@pytest.mark.parametrize("index_type", ["flat", "ivf"])
def test_a_second_search_sends_only_its_queries_and_masks(index_type):
    db, v = _db(index_type)
    q = v[:3]
    db.search(q, 5)
    before = _resident_counts()
    db.search(q, 5)
    assert _grown_counts(before) == [q.nbytes + db.live.nbytes, 1, 0]


@pytest.mark.parametrize("index_type", ["flat", "ivf"])
def test_a_search_after_an_insert_sends_the_new_rows(index_type):
    db, v = _db(index_type)
    q = v[:3]
    db.search(q, 5)
    new = v[:7] * 0.5
    db.insert(new, [Chunk(-1, 1000, "") for _ in range(7)])
    before = _resident_counts()
    db.search(q, 5)
    assert _grown_counts(before) == [
        q.nbytes + db.live.nbytes + new.nbytes, 0, 1]
    np.testing.assert_array_equal(np.asarray(db._mirror["vectors"][1]),
                                  db.vectors)


@pytest.mark.parametrize("change", ["insert", "remove"])
def test_a_sharded_search_sees_a_change_after_its_last(change):
    from repro.sharded import ShardedDBConfig, ShardedVectorDB
    db = ShardedVectorDB(ShardedDBConfig(
        n_shards=2, index_type="flat", dim=DIM, capacity=CAP,
        use_hybrid=False))
    v = np.random.default_rng(0).standard_normal((ROWS, DIM))
    v = (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(np.float32)
    db.insert(v, [Chunk(-1, i, "") for i in range(ROWS)])
    db.build_index()
    q = v[:4] * 0.9 + 0.1 * v[4:8]
    first = np.stack([r.chunk_ids for r in db.search(q, 3)])
    if change == "insert":
        db.insert(q / np.linalg.norm(q, axis=1, keepdims=True),
                  [Chunk(-1, 5000 + i, "") for i in range(4)])
        top = np.stack([r.chunk_ids for r in db.search(q, 3)])
        assert top[:, 0].tolist() == [db.doc_slots[5000 + i][0]
                                      for i in range(4)]
    else:
        for i in range(4):
            db.remove(i)
        top = np.stack([r.chunk_ids for r in db.search(q, 3)])
        assert not np.isin(first[:, 0], top).any()


def test_arrays_already_on_the_device_count_nothing():
    db, v = _db("flat")
    host = db._search_arrays(v[:3], 5)
    snap = db._snapshot()
    for key in ("vectors", "live", "indexed"):
        snap[key] = jnp.asarray(snap[key])
    b0 = obs.REGISTRY.counter_value("db.h2d_bytes")
    dev = db._search_arrays(jnp.asarray(v[:3]), 5, snap)
    assert _grown("db.h2d_bytes", b0) == 0
    np.testing.assert_array_equal(dev[1], host[1])


def test_the_process_registry_keeps_a_bounded_ring():
    assert obs.REGISTRY.ring == 1 << 16
    reg = MetricsRegistry(ring=8)
    for i in range(20):
        reg.counter_add("c", 1.0, t=float(i))
        reg.observe("o", float(i), t=float(i))
    assert [p.value for p in reg.series("c")] == [float(i) for i in
                                                   range(13, 21)]
    assert reg.counter_value("c") == 20.0
    assert reg.observations("o") == [(float(i), float(i))
                                     for i in range(12, 20)]
    assert reg.histogram_summary("o")["n"] == 8.0


# -- the served path with the smoke engine -------------------------------------


@pytest.fixture(scope="module")
def engine_pipe():
    spec = PipelineSpec(
        vectordb=StageSpec("fused", {"index_type": "ivf", "nlist": 4,
                                     "nprobe": 2, "capacity": 1024}),
        llm=StageSpec("model", {"arch": "llama3_8b", "smoke": True,
                                "max_prompt": 48, "max_new": 3}),
        retrieve_k=4, rerank_k=2,
        gen=GenSpec(enabled=True, slots=2, chunk_tokens=16))
    pipe = build(spec)
    corpus = SyntheticCorpus(CorpusConfig(n_docs=16, seed=0))
    pipe.index_documents(corpus.all_documents())
    for b in (1, 2):
        pipe.query(["warm up the shapes"] * b)
    pipe.traces.clear()
    return pipe, corpus


def _host_events(log_dir):
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


SPANS = {"stage.db_search": ["stage.retrieval.snapshot", "stage.retrieval.h2d",
                             "stage.retrieval.launch", "stage.retrieval.wait",
                             "stage.retrieval.merge"],
         "engine.decode": ["engine.decode.upload", "engine.decode.launch",
                           "engine.decode.sync", "engine.decode.bookkeep"],
         "engine.prefill_chunk": ["engine.prefill.launch",
                                  "engine.prefill.first_token"]}


def test_served_spans_land_on_the_host_plane_inside_the_benchmarks(
        engine_pipe, tmp_path, monkeypatch):
    pipe, _ = engine_pipe
    eng = pipe.llm.engine
    Annotation = jax.profiler.TraceAnnotation

    def wrap(obj, attr, name):
        real = getattr(obj, attr)

        def wrapped(*a):
            with Annotation(name):
                return real(*a)
        monkeypatch.setattr(obj, attr, wrapped)

    wrap(pipe.db, "search", "stage.db_search")
    wrap(eng, "_decode_work", "engine.decode")
    wrap(eng, "_prefill_chunks", "engine.prefill_chunk")
    b0 = obs.REGISTRY.counter_value("db.h2d_bytes")
    jax.profiler.start_trace(str(tmp_path))
    try:
        pipe.query(["what is the capital of entity seven"])
    finally:
        jax.profiler.stop_trace()
    pipe.traces.clear()
    events = _host_events(tmp_path)
    for outer, inner in SPANS.items():
        spans = [(a, b) for n, a, b, _ in events if n == outer]
        assert spans, outer
        for name in inner:
            mine = [(a, b) for n, a, b, _ in events if n == name]
            assert mine, name
            assert all(any(oa <= a and b <= ob for oa, ob in spans)
                       for a, b in mine), name
    assert any(n == "engine.admit" for n, *_ in events)
    (h2d,) = [s for n, _, _, s in events if n == "stage.retrieval.h2d"]
    assert h2d["bytes"] == _grown("db.h2d_bytes", b0) > 0


def test_the_engines_programs_have_stable_names(engine_pipe):
    eng = engine_pipe[0].llm.engine
    core = eng.core
    decode = core._decode.lower(
        core.params, batch={"tokens": jnp.zeros((eng.slots, 1), jnp.int32)},
        cache=eng.cache)
    chunk = core._chunk.lower(
        core.params, jnp.zeros((1, eng.chunk_tokens), jnp.int32),
        eng.cache["k"], eng.cache["v"], jnp.int32(0), jnp.int32(0))
    assert "@jit_gen_decode_step" in decode.as_text()
    assert "@jit_gen_prefill_chunk" in chunk.as_text()


def test_each_answer_carries_its_first_token(engine_pipe):
    pipe, corpus = engine_pipe
    n = 6
    scfg = ServingConfig(
        arrival=ArrivalConfig(mode="open", process="poisson",
                              target_qps=50.0, n_requests=n, seed=1),
        policy=BatchPolicy(max_batch=2, max_wait_s=0.005), slo_ms=1e4)
    h = ServingHarness(pipe, corpus, WorkloadConfig(
        query_frac=1.0, update_frac=0.0, n_requests=n, seed=1), scfg)
    seen = len(obs.REGISTRY.observations("serve.ttft_s"))
    res = h.run()
    pipe.traces.clear()
    recs = [r for r in res.records if r.ok]
    assert len(recs) == n
    for r in recs:
        assert r.start_s < r.first_token_s <= r.end_s
    ttft = [v for _, v in obs.REGISTRY.observations("serve.ttft_s")[seen:]]
    assert sorted(ttft) == pytest.approx(
        sorted(r.first_token_s - r.arrival_s for r in recs))


def test_an_attached_tracer_gets_the_engines_spans(tmp_path, monkeypatch):
    from repro.launch import serve
    monkeypatch.chdir(ROOT)
    out = tmp_path / "t.json"
    assert serve.main(["--config", "examples/specs/gen_engine.json",
                       "--mode", "sync", "--requests", "3", "--docs", "8",
                       "--update-frac", "0", "--trace-out", str(out)]) == 0
    events = json.loads(out.read_text())["traceEvents"]
    spans = {e["name"] for e in events if e["ph"] == "X"}
    assert {"engine.prefill.launch", "engine.prefill.first_token",
            "engine.decode.launch", "engine.decode.sync",
            "stage.retrieval.h2d"} <= spans
    assert not any(e["name"].startswith("gen.") for e in events)


# -- the benchmark's readers of them ------------------------------------------


def _bench_trace():
    """``benchlib.trace``, with the benchmark's directory on the path."""
    bench = str(ROOT / "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from benchlib import trace
    return trace


def _reader(name):
    _bench_trace()
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Ctx:
    def __init__(self, trace=None, records=()):
        self.trace = trace
        self.trace_win = _bench_trace().window(trace) if trace else None
        self.records = list(records)


def test_decode_idle_reads_the_idle_gaps_under_the_engines_spans():
    trace_lib = _bench_trace()
    host, ops = [["bench.trace", 0.0, 100.0]], []
    for i in range(4):                  # four 20 ns decode steps
        t = 10.0 + 20.0 * i
        host += [["engine.decode", t, 20.0],
                 ["engine.decode.upload", t, 2.0],
                 ["engine.decode.launch", t + 2.0, 1.0],
                 ["engine.decode.sync", t + 3.0, 15.0],
                 ["engine.decode.bookkeep", t + 18.0, 2.0]]
        ops.append(["op", t + 5.0, 11.0])
    tr = {"devices": {"/device:TPU:0": {trace_lib.OPS: ops}}, "host": host}
    ctx = _Ctx(tr)
    # the gaps before steps 1-3 (9 ns each) fall in an upload span; those
    # before step 0 and after step 3 lie outside the engine's spans
    want = sum(s for lab, s in trace_lib.idle_gaps(tr, ctx.trace_win, 100)
               if lab.startswith("engine.decode."))
    got = _reader("generate.decode_idle_ms")(ctx)
    assert got == pytest.approx(want * 1e3 / 4) == pytest.approx(27e-6 / 4)
    tr["devices"] = {}
    assert _reader("generate.decode_idle_ms")(_Ctx(tr)) is None


def test_the_readers_of_the_process_registry_keep_to_the_window():
    b = obs.REGISTRY.counter_value("db.h2d_bytes")
    c = obs.REGISTRY.counter_value("db.search_calls")
    t0 = obs.REGISTRY.clock.now()
    obs.count("db.h2d_bytes", 3e6)
    obs.count("db.search_calls", 2)
    obs.observe("serve.ttft_s", 0.25)
    t1 = obs.REGISTRY.clock.now()
    obs.count("db.h2d_bytes", 5e6)     # after the window: not read
    obs.count("db.search_calls")
    records = [{"due": t0, "end": t1}]
    chip = {"devices": {"/device:TPU:0": {}}, "host": []}
    assert _reader("retrieve.h2d_mb")(_Ctx(chip, records)) == \
        pytest.approx(1.5)
    assert _reader("generate.ttft_p90_ms")(_Ctx(chip, records)) == \
        pytest.approx(250.0)
    # a run traced on the CPU has no device plane: nothing crosses to one
    cpu = {"devices": {}, "host": []}
    for name in ("retrieve.h2d_mb", "generate.ttft_p90_ms"):
        assert _reader(name)(_Ctx(cpu, records)) is None
    assert obs.REGISTRY.counter_value("db.h2d_bytes") - b == 8e6
    assert obs.REGISTRY.counter_value("db.search_calls") - c == 3


def test_h2d_ms_reads_the_copy_spans_per_search():
    host = [["bench.trace", 0.0, 100.0],
            ["stage.db_search", 10.0, 40.0],
            ["stage.retrieval.h2d", 12.0, 3.0],
            ["stage.db_search", 60.0, 30.0],
            ["stage.retrieval.h2d", 62.0, 5.0]]
    tr = {"devices": {"/device:TPU:0": {}}, "host": host}
    # 8 ns of copies over two searches: 4e-6 ms a search
    assert _reader("retrieve.h2d_ms")(_Ctx(tr)) == pytest.approx(4e-6)
    tr["devices"] = {}
    assert _reader("retrieve.h2d_ms")(_Ctx(tr)) is None
