"""ShardedVectorDB: single-shard parity, multi-shard recall, routing,
mutation correctness under the update_storm mix, and the k-vs-shard-rows
padding guard (repro.sharded)."""
import numpy as np
import pytest

from repro.core.interfaces import Chunk
from repro.core.registry import build, create
from repro.core.vectordb import DBConfig, JaxVectorDB
from repro.scenarios import get_scenario
from repro.sharded import (ShardedDBConfig, ShardedVectorDB, doc_shard,
                           make_sharded_db)
from repro.workload.corpus import CorpusConfig, SyntheticCorpus
from repro.workload.generator import WorkloadGenerator

DIM = 64


def _corpus(n=512, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    return vecs


def _chunks(n):
    return [Chunk(chunk_id=-1, doc_id=i // 4, text=f"c{i}")
            for i in range(n)]


def _queries(vecs, nq=12, seed=1):
    rng = np.random.default_rng(seed)
    q = vecs[:nq] + 0.02 * rng.standard_normal((nq, DIM)).astype(np.float32)
    return q.astype(np.float32)


def _fill(db, vecs, build_index=True):
    db.insert(vecs, _chunks(len(vecs)))
    if build_index:
        db.build_index()
    return db


# -- single-shard parity ------------------------------------------------------


@pytest.mark.parametrize("index_type,quant", [("flat", "none"),
                                              ("flat", "sq8"),
                                              ("ivf", "none")])
def test_one_shard_output_identical_to_jax_db(index_type, quant):
    vecs = _corpus()
    kw = dict(dim=DIM, capacity=1024, nlist=16, nprobe=8, flat_capacity=64)
    single = _fill(JaxVectorDB(DBConfig(index_type=index_type, quant=quant,
                                        **kw)), vecs)
    one = _fill(ShardedVectorDB(ShardedDBConfig(n_shards=1,
                                                index_type=index_type,
                                                quant=quant, **kw)), vecs)
    q = _queries(vecs)
    for a, b in zip(single.search(q, 8), one.search(q, 8)):
        assert (a.chunk_ids == b.chunk_ids).all()
        assert np.allclose(a.scores, b.scores)


def test_one_shard_parity_survives_mutations():
    vecs = _corpus(256)
    kw = dict(dim=DIM, capacity=1024, nlist=8, nprobe=4, flat_capacity=32)
    single = _fill(JaxVectorDB(DBConfig(index_type="ivf", **kw)), vecs)
    one = _fill(ShardedVectorDB(ShardedDBConfig(n_shards=1, index_type="ivf",
                                                **kw)), vecs)
    extra = _corpus(24, seed=7)
    for db in (single, one):
        db.remove(3)
        db.insert(extra, [Chunk(chunk_id=-1, doc_id=100 + i, text=f"x{i}")
                          for i in range(24)])
        db.update(5, extra[:4],
                  [Chunk(chunk_id=-1, doc_id=5, text=f"u{i}")
                   for i in range(4)])
    q = _queries(vecs)
    for a, b in zip(single.search(q, 8), one.search(q, 8)):
        assert (a.chunk_ids == b.chunk_ids).all()
        assert np.allclose(a.scores, b.scores)


# -- multi-shard recall -------------------------------------------------------


def test_multi_shard_flat_is_exact():
    """Flat sharded search must return exactly the global top-k set."""
    vecs = _corpus()
    q = _queries(vecs)
    top_ref = np.argsort(-(q @ vecs.T), axis=1)[:, :8]
    for s in (2, 4, 8):
        db = _fill(ShardedVectorDB(ShardedDBConfig(
            n_shards=s, index_type="flat", dim=DIM, capacity=1024)), vecs)
        for i, r in enumerate(db.search(q, 8)):
            got = {db.get_chunk(c).text for c in r.chunk_ids if c >= 0}
            assert got == {f"c{j}" for j in top_ref[i]}, (s, i)


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_multi_shard_ivf_recall_parity(n_shards):
    vecs = _corpus()
    q = _queries(vecs)
    top_ref = np.argsort(-(q @ vecs.T), axis=1)[:, :8]
    kw = dict(dim=DIM, capacity=1024, nlist=16, nprobe=8, flat_capacity=64)

    def recall(db):
        hits = 0
        for i, r in enumerate(db.search(q, 8)):
            got = {db.get_chunk(c).text for c in r.chunk_ids if c >= 0}
            hits += len(got & {f"c{j}" for j in top_ref[i]})
        return hits / (len(q) * 8)

    single = _fill(JaxVectorDB(DBConfig(index_type="ivf", **kw)), vecs)
    sharded = _fill(ShardedVectorDB(ShardedDBConfig(
        n_shards=n_shards, index_type="ivf", **kw)), vecs)
    assert recall(sharded) >= recall(single) - 0.05


# -- routing + ids ------------------------------------------------------------


def test_doc_routing_is_deterministic_and_spread():
    assign = [doc_shard(d, 4) for d in range(256)]
    assert assign == [doc_shard(d, 4) for d in range(256)]
    counts = np.bincount(assign, minlength=4)
    assert counts.min() > 0.5 * counts.mean()   # no starved shard


def test_chunk_ids_are_global_and_stable():
    vecs = _corpus(64)
    db = _fill(ShardedVectorDB(ShardedDBConfig(
        n_shards=4, index_type="flat", dim=DIM, capacity=256)), vecs,
        build_index=False)
    for doc_id, gids in db.doc_slots.items():
        sid = doc_shard(doc_id, 4)
        for g in gids:
            assert g // db.shard_capacity == sid      # on the routed shard
            c = db.get_chunk(g)
            assert c is not None and c.chunk_id == g  # payload re-keyed
            assert c.doc_id == doc_id


def test_k_larger_than_shard_rows_pads():
    """Tiny shards must pad with (-1, NEG), never error or fabricate ids."""
    vecs = _corpus(12)
    db = _fill(ShardedVectorDB(ShardedDBConfig(
        n_shards=4, index_type="flat", dim=DIM, capacity=64,
        balance_slack=1.0)), vecs, build_index=False)
    # per-shard capacity is 16 < k=24: shards must pad, the merge must mask
    res = db.search(_queries(vecs, nq=3), 24)
    for r in res:
        valid = r.chunk_ids[r.chunk_ids >= 0]
        assert len(set(valid.tolist())) == len(valid)
        assert all(db.get_chunk(c) is not None for c in valid)


# -- mutations under the update_storm mix ------------------------------------


def test_update_storm_mutations_route_and_tombstone():
    spec = get_scenario("update_storm").scaled(0.5)
    corpus = SyntheticCorpus(CorpusConfig(n_docs=spec.n_docs,
                                          seed=spec.seed))
    reqs = list(WorkloadGenerator(spec.workload_config(), corpus).requests())
    pspec = spec.pipeline_spec().merged(
        {"vectordb": {"component": "sharded",
                      "options": {"n_shards": 4, "dim": 384}}})
    pipe = build(pspec)
    pipe.index_documents(corpus.all_documents())
    db = pipe.db
    assert isinstance(db, ShardedVectorDB)
    removed = set()
    for r in reqs:
        if r.op == "insert":
            pipe.index_documents([(r.doc_id, r.text)], build=False)
            removed.discard(r.doc_id)
        elif r.op == "update":
            pipe.update_document(r.doc_id, r.text, version=r.version or 1)
            removed.discard(r.doc_id)
        elif r.op == "removal":
            pipe.remove_document(r.doc_id)
            removed.add(r.doc_id)
    # every surviving doc's chunks live on its hash-routed shard
    for doc_id, gids in db.doc_slots.items():
        sid = doc_shard(doc_id, 4)
        assert all(g // db.shard_capacity == sid for g in gids)
        assert all(db.get_chunk(g).doc_id == doc_id for g in gids)
    # tombstoned docs never surface in merged search results
    queries = [r.question for r in reqs if r.op == "query"][:16]
    qv = pipe.embedder.embed(queries)
    for res in db.search(qv, 8):
        for cid in res.chunk_ids:
            if cid >= 0:
                chunk = db.get_chunk(cid)
                assert chunk is not None
                assert chunk.doc_id not in removed
    stats = db.stats()
    assert stats["n_shards"] == 4.0
    assert stats["live"] == sum(s["live"] for s in db.shard_stats())


def test_sharded_vs_single_identical_after_mutation_stream():
    """Same op stream into flat sharded and flat single DBs: search results
    must name the same (doc, text) payloads with the same scores."""
    vecs = _corpus(128)
    kw = dict(index_type="flat", dim=DIM, capacity=512)
    single = _fill(JaxVectorDB(DBConfig(**kw)), vecs)
    shard = _fill(ShardedVectorDB(ShardedDBConfig(n_shards=4, **kw)), vecs)
    rng = np.random.default_rng(3)
    for step in range(30):
        doc = int(rng.integers(0, 32))
        op = step % 3
        if op == 0:
            for db in (single, shard):
                db.remove(doc)
        else:
            nv = rng.standard_normal((2, DIM)).astype(np.float32)

            def chs():
                return [Chunk(chunk_id=-1, doc_id=doc, text=f"m{step}_{j}")
                        for j in range(2)]

            for db in (single, shard):
                if op == 1:
                    db.update(doc, nv, chs())
                else:
                    db.insert(nv, chs())
    q = _queries(vecs)
    for a, b in zip(single.search(q, 8), shard.search(q, 8)):
        pa = [(single.get_chunk(c).doc_id, single.get_chunk(c).text)
              for c in a.chunk_ids if c >= 0]
        pb = [(shard.get_chunk(c).doc_id, shard.get_chunk(c).text)
              for c in b.chunk_ids if c >= 0]
        assert sorted(pa) == sorted(pb)
        assert np.allclose(np.sort(a.scores), np.sort(b.scores))


# -- knob atomicity -----------------------------------------------------------


def test_set_nprobe_reaches_every_shard():
    db = ShardedVectorDB(ShardedDBConfig(n_shards=4, index_type="ivf",
                                         dim=DIM, nlist=16, nprobe=8))
    db.set_nprobe(2)
    assert db.cfg.nprobe == 2
    assert all(sh.cfg.nprobe == 2 for sh in db.shards)


def test_set_nprobe_never_observed_mixed_across_shards():
    """Concurrent ladder walks vs searches: every consistent cross-shard
    snapshot must carry one nprobe level, never a mix."""
    import threading
    vecs = _corpus(256)
    db = _fill(ShardedVectorDB(ShardedDBConfig(
        n_shards=4, index_type="ivf", dim=DIM, capacity=1024, nlist=16,
        nprobe=8, flat_capacity=64)), vecs)
    stop = threading.Event()
    bad = []

    def walker():
        lvl = [8, 4, 2, 1]
        i = 0
        while not stop.is_set():
            db.set_nprobe(lvl[i % 4])
            i += 1

    def snapper():
        while not stop.is_set():
            with db._mu:
                seen = {sh._snapshot()["nprobe"] for sh in db.shards}
            if len(seen) != 1:
                bad.append(seen)

    ts = [threading.Thread(target=walker), threading.Thread(target=snapper),
          threading.Thread(target=snapper)]
    for t in ts:
        t.start()
    import time
    time.sleep(0.4)
    stop.set()
    for t in ts:
        t.join()
    assert not bad, bad


# -- registry / spec integration ---------------------------------------------


def test_registered_backend_builds_from_spec():
    db = create("vectordb", "sharded", n_shards=2, index_type="flat",
                dim=DIM, capacity=256)
    assert isinstance(db, ShardedVectorDB) and db.cfg.n_shards == 2
    assert make_sharded_db(n_shards=1).cfg.n_shards == 1


def test_shard_scale_scenario_spec_selects_sharded_backend():
    spec = get_scenario("shard_scale")
    pspec = spec.pipeline_spec()
    assert pspec.vectordb.component == "sharded"
    assert pspec.vectordb.options["n_shards"] == 4


@pytest.mark.parametrize("kw", [
    dict(n_shards=2),                                # 2 shards, 1 device
    dict(index_type="ivf"),                          # mesh scans flat only
    dict(quant="sq8"),
    dict(corpus_axes=("pod",)),                      # no corpus axis on it
])
def test_explicit_mesh_that_does_not_fit_raises(kw):
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    cfg = dict(n_shards=1, index_type="flat", quant="none", dim=DIM,
               capacity=256)
    ShardedVectorDB(ShardedDBConfig(**cfg), mesh=mesh)   # fits: no error
    with pytest.raises(ValueError, match="one shard per device"):
        ShardedVectorDB(ShardedDBConfig(**{**cfg, **kw}), mesh=mesh)


def test_factory_makes_no_mesh_when_shards_and_devices_differ():
    db = make_sharded_db(n_shards=2, index_type="flat", dim=DIM,
                         capacity=256)
    assert db.mesh is None
