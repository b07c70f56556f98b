"""Fused retrieve backend: DB-level parity vs the reference ladder,
sharded composition, registry/spec round-trip, packed-mirror rebuilds,
and the roofline byte model (repro.kernels.fused_retrieve et al.).

The exhaustive 6-config x 2-mode x pre/post-mutation sweep rides tier-1
via ``benchmarks.fused_retrieve --check``; the tests here pin the same
contracts on small corpora plus the integration seams the benchmark
doesn't touch (sharded, registry, spec, counters).
"""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import registry
from repro.core.interfaces import Chunk
from repro.core.spec import PipelineSpec
from repro.core.vectordb import (DBConfig, JaxVectorDB, kernel_ladder,
                                 make_fused_db)
from repro.kernels.ref import topk_mismatch
from repro.roofline.retrieve import RetrieveShape, hbm_bytes, roofline
from repro.sharded import ShardedDBConfig, ShardedVectorDB

DIM = 16
N = 192


def _corpus(n=N, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    return vecs


def _chunks(n, doc0=0):
    return [Chunk(chunk_id=-1, doc_id=doc0 + i // 4, text=f"c{i}")
            for i in range(n)]


def _db(index_type, quant, use_kernel, n=N):
    db = JaxVectorDB(DBConfig(
        index_type=index_type, quant=quant, dim=DIM, capacity=n + 96,
        nlist=4, nprobe=2, flat_capacity=48, pq_m=4,
        use_kernel=use_kernel))
    db.insert(_corpus(n), _chunks(n))
    db.build_index()
    return db


def _queries(nq=8, seed=1):
    rng = np.random.default_rng(seed)
    q = _corpus()[:nq] + 0.02 * rng.standard_normal(
        (nq, DIM)).astype(np.float32)
    return q.astype(np.float32)


# -- fused vs reference ladder, pre and post mutation -----------------------


@pytest.mark.parametrize("index_type,quant", [
    ("flat", "sq8"), ("ivf", "none"), ("ivf", "pq")])
@pytest.mark.parametrize("env_mode", ["interpret", "xla"])
def test_fused_matches_reference_db(index_type, quant, env_mode, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", env_mode)
    ref, fus = _db(index_type, quant, False), _db(index_type, quant, "fused")
    q = jnp.asarray(_queries())
    for phase in ("built", "mutated"):
        if phase == "mutated":
            fresh = _corpus(10, seed=3)
            for db in (ref, fus):
                db.remove(2)
                db.remove(31)
                db.insert(fresh.copy(), _chunks(10, doc0=900))
        sa, ia = ref._search_arrays(q, 5)
        sb, ib = fus._search_arrays(q, 5)
        # the cross-mode contract (repro.kernels.ref.SCORE_RTOL): equal
        # ids, scores equal up to f32 reduction order
        assert (np.asarray(ia) == np.asarray(ib)).all(), phase
        assert topk_mismatch(sa, ia, sb, ib) is None, phase


_S = np.array([[0.9, 0.5, 0.5, -3.0e38]], np.float32)
_I = np.array([[4, 7, 2, -1]], np.int32)


@pytest.mark.parametrize("s_b,i_b,violation", [
    (_S + 1e-6, _I, None),                       # reduction-order drift
    (_S + 1e-3, _I, "scores differ"),
    (_S, _I[:, [0, 2, 1, 3]], "ids differ"),     # a tie may not swap
    (_S, np.array([[4, 7, 9, -1]]), "ids differ"),
    (_S, np.array([[4, 7, 2, 3]]), "ids differ"),
])
def test_topk_contract(s_b, i_b, violation):
    bad = topk_mismatch(_S, _I, s_b, i_b)
    assert (bad is None) if violation is None else (violation in bad)


def test_topk_contract_rejects_a_duplicate_id():
    i = np.array([[4, 7, 7, -1]])
    assert "twice" in topk_mismatch(_S, i, _S, i)


def test_packed_mirror_refreshed_by_rebuild(monkeypatch):
    """Inserts past the hybrid-buffer threshold trigger a rebuild; the
    bucket-contiguous packed mirror must track it (stale mirrors would
    surface as silently-missing fresh rows)."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
    ref, fus = _db("ivf", "sq8", False), _db("ivf", "sq8", "fused")
    assert fus.packed is not None
    slot0 = fus.packed["slot"].copy()
    fresh = _corpus(64, seed=9)          # > flat_capacity: forces rebuilds
    for db in (ref, fus):
        db.insert(fresh.copy(), _chunks(64, doc0=500))
    assert fus.counters["rebuilds"] > 1
    assert not np.array_equal(fus.packed["slot"], slot0)
    q = jnp.asarray(_queries())
    sa, ia = ref._search_arrays(q, 5)
    sb, ib = fus._search_arrays(q, 5)
    assert (np.asarray(ia) == np.asarray(ib)).all()
    assert topk_mismatch(sa, ia, sb, ib) is None


# -- sharded composition ----------------------------------------------------


def test_sharded_fused_matches_sharded_unfused(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
    vecs = _corpus()
    kw = dict(n_shards=2, index_type="ivf", quant="sq8", dim=DIM,
              capacity=N + 64, nlist=4, nprobe=2, flat_capacity=48)
    dbs = []
    for uk in (False, "fused"):
        db = ShardedVectorDB(ShardedDBConfig(use_kernel=uk, **kw))
        db.insert(vecs.copy(), _chunks(N))
        db.build_index()
        dbs.append(db)
    for a, b in zip(dbs[0].search(_queries(), 6), dbs[1].search(_queries(), 6)):
        assert (a.chunk_ids == b.chunk_ids).all()
        assert topk_mismatch(a.scores[None], a.chunk_ids[None],
                             b.scores[None], b.chunk_ids[None]) is None


# -- registry / spec seams --------------------------------------------------


def test_kernel_ladder_normalization():
    assert kernel_ladder(False) == "off"
    assert kernel_ladder(None) == "off"
    assert kernel_ladder(True) == "op"
    for rung in ("off", "op", "fused"):
        assert kernel_ladder(rung) == rung
    with pytest.raises(ValueError):
        kernel_ladder("turbo")


def test_fused_registry_backend():
    db = registry.create("vectordb", "fused", index_type="flat", dim=DIM,
                         capacity=64, nlist=4, flat_capacity=16)
    assert db._kernel == "fused"
    with pytest.raises(ValueError):
        make_fused_db(use_kernel=True)      # conflicting rung must not pass


def test_fused_spec_roundtrip_and_counter(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "xla")
    spec = PipelineSpec.from_file("examples/specs/fused_retrieve.json")
    stage = spec.stage("vectordb")
    assert stage.component == "fused"
    assert PipelineSpec.from_dict(spec.to_dict()).to_dict() == spec.to_dict()
    # survives a file round-trip too (what launch.serve consumes)
    p = tmp_path / "spec.json"
    p.write_text(json.dumps(spec.to_dict()))
    assert PipelineSpec.from_file(str(p)).to_dict() == spec.to_dict()
    opts = dict(stage.options, dim=DIM, capacity=N + 64, flat_capacity=48)
    db = registry.create("vectordb", stage.component, **opts)
    assert db._kernel == "fused"
    db.insert(_corpus(), _chunks(N))
    db.build_index()
    db.search(_queries(4), 5)
    assert db.counters["fused_searches"] == 4
    off = _db("ivf", "sq8", False)
    off.search(_queries(4), 5)
    assert off.counters["fused_searches"] == 0


# -- roofline byte model ----------------------------------------------------

LADDER = [("flat", "none"), ("flat", "sq8"), ("flat", "pq"),
          ("ivf", "none"), ("ivf", "sq8"), ("ivf", "pq")]


@pytest.mark.parametrize("index_type,quant", LADDER)
def test_roofline_fused_strictly_fewer_bytes(index_type, quant):
    kw = dict(nq=32, n=1 << 16, d=128, k=16)
    if index_type == "ivf":
        kw.update(nlist=64, nprobe=8)
    if quant == "pq":
        kw.update(pq_m=8)
    s = RetrieveShape(index_type=index_type, quant=quant, **kw)
    fused, unfused = hbm_bytes(s, fused=True), hbm_bytes(s, fused=False)
    # the bound (corpus payload) is common; fused adds only candidates
    assert fused["bound"] == unfused["bound"]
    assert fused["bound"] <= fused["total"] < unfused["total"]
    r = roofline(s)
    assert r["fused_bound_fraction"] > r["unfused_bound_fraction"]
    assert r["fused_memory_s"] < r["unfused_memory_s"]
