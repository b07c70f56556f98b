"""The index arrays a search reads stay on the device between searches
(``JaxVectorDB._refresh``): searches against the device copies answer bit
for bit as searches against the host arrays, through inserts, removals,
updates and rebuilds, on every index, quantizer and kernel rung."""
import numpy as np
import pytest

from repro import obs
from repro.core.interfaces import Chunk
from repro.core.vectordb import make_db

DIM, K = 16, 5

# (index, quantizer, rung): every rung each index and quantizer has
CASES = [("flat", "none", "off"), ("flat", "none", "op"),
         ("flat", "none", "fused"), ("flat", "sq8", "off"),
         ("flat", "sq8", "op"), ("flat", "sq8", "fused"),
         ("ivf", "none", "off"), ("ivf", "none", "fused"),
         ("ivf", "sq8", "off"), ("ivf", "sq8", "fused"),
         ("ivf", "pq", "off"), ("ivf", "pq", "fused")]


def _vecs(n, seed):
    v = np.random.default_rng(seed).standard_normal((n, DIM))
    return (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(np.float32)


def _chunks(n, doc0):
    return [Chunk(-1, doc0 + i // 4, "") for i in range(n)]


def _db(index_type, quant, rung, hybrid):
    return make_db(index_type, quant, dim=DIM, capacity=512, nlist=4,
                   nprobe=2, pq_m=4, kmeans_iters=2, flat_capacity=64,
                   use_hybrid=hybrid, use_kernel=rung)


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


def _check(db, q):
    """A search against the device copies, the same snapshot searched
    against the host arrays, and the served ``search``: all three alike."""
    snap = db._snapshot()
    got = db._search_arrays(q, K, snap)
    _same(got, db._search_arrays(q, K, dict(snap, dev={})))
    served = db.search(q, K)
    _same(got, (np.stack([r.scores for r in served]),
                np.stack([r.chunk_ids for r in served])))
    return got


@pytest.mark.parametrize("hybrid", [True, False], ids=["hybrid", "plain"])
@pytest.mark.parametrize("index_type,quant,rung", CASES)
def test_device_copies_answer_as_the_host_arrays(index_type, quant, rung,
                                                 hybrid, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
    db = _db(index_type, quant, rung, hybrid)
    base = _vecs(128, 0)
    q = base[:6] + 0.05 * _vecs(6, 1)
    _check(db, q)                                    # empty, cold start
    db.insert(base, _chunks(128, 0))
    _check(db, q)                                    # unbuilt: brute force
    db.build_index()
    _check(db, q)
    db.insert(_vecs(8, 2), _chunks(8, 100))          # fresh rows
    _check(db, q)
    db.remove(1)
    _check(db, q)
    db.update(2, _vecs(4, 3), _chunks(4, 2))
    _check(db, q)
    # an insert between the snapshot and the launch: the search answers
    # as before it, though the inserted rows are each query's best match
    before = _check(db, q)
    snap = db._snapshot()
    db.insert(q / np.linalg.norm(q, axis=1, keepdims=True),
              _chunks(6, 200))
    _same(db._search_arrays(q, K, snap), before)
    after = _check(db, q)
    if hybrid:   # the fresh rows are searched at once
        new = 140 + np.arange(6)[:, None]
        assert (np.asarray(after[1]) == new).any(axis=1).all()
    db.build_index()
    _check(db, q)


def test_an_index_without_fresh_rows_keeps_no_device_vectors(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
    db = _db("ivf", "none", "fused", True)
    db.insert(_vecs(128, 0), _chunks(128, 0))
    db.build_index()
    q = _vecs(3, 1)
    db.search(q, K)
    assert "vectors" not in db._mirror
    assert {"centroids", "packed.vecs", "packed.slot"} <= set(db._mirror)
    db.insert(_vecs(4, 2), _chunks(4, 100))          # the fresh buffer
    db.search(q, K)
    assert db._mirror["vectors"][2] == db.n_slots


def test_a_rebuild_replaces_the_device_copies_it_replaced():
    db = _db("ivf", "none", "off", False)
    db.insert(_vecs(128, 0), _chunks(128, 0))
    db.build_index()
    q = _vecs(3, 1)
    db.search(q, K)
    vecs = db._mirror["vectors"][1]
    db.build_index()
    db.search(q, K)
    for key in ("centroids", "buckets", "bucket_live"):
        assert db._mirror[key][0] is getattr(db, key)
    assert db._mirror["vectors"][1] is vecs          # rows unchanged


def test_the_refresh_span_lies_inside_the_snapshot_span():
    db = _db("flat", "none", "off", False)
    db.insert(_vecs(32, 0), _chunks(32, 0))
    db.tracer = obs.Tracer()
    db.search(_vecs(2, 1), K)
    spans = {s.name: s for s in db.tracer.spans()}
    snap, refresh = (spans["stage.retrieval.snapshot"],
                     spans["stage.retrieval.refresh"])
    assert snap.t0 <= refresh.t0 <= refresh.t1 <= snap.t1

