"""The comparison that decides ``correct`` fails what it must: the controls
(the reference in a lower precision put in the program's place) and a run
whose timed path is broken underneath, at the tiny size on the CPU."""
import json

import numpy as np

import bench_helpers  # noqa: F401  (paths)
from repro.core.vectordb import JaxVectorDB
from repro.serving.genengine import GenEngine


def _last(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_generation_control_fails_the_logit_gap(cpu_run, capsys):
    assert cpu_run("tiny-rag.poisson", "--control") == 0
    res = _last(capsys)
    assert res["correct"] is True
    limit = res["checks"]["gen.logit_gap"]["limit"]
    assert res["checks"]["gen.logit_gap"]["value"] <= limit
    assert res["controls"]["gen.logit_gap"] > limit


def test_retrieval_control_fails_the_score_gap(cpu_run, capsys):
    assert cpu_run("tiny-flat.poisson", "--control") == 0
    res = _last(capsys)
    assert res["correct"] is True
    limit = res["checks"]["retrieve.score_gap"]["limit"]
    assert res["controls"]["retrieve.score_gap"] > limit


def test_a_token_altered_where_it_is_produced_is_not_correct(
        cpu_run, capsys, monkeypatch):
    real = GenEngine._decode_work

    def altered(self):
        active = self._decode_slots()
        did = real(self)
        for s in active:
            req = self._slot_req[s] or None
            if req is not None and len(req.out) == 2:
                req.out[-1] = (req.out[-1] + 1) % self.cfg.vocab_size
        return did

    monkeypatch.setattr(GenEngine, "_decode_work", altered)
    assert cpu_run("tiny-rag.poisson") == 0
    res = _last(capsys)
    assert res["correct"] is False
    gap = res["checks"]["gen.logit_gap"]
    assert gap["value"] > gap["limit"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        cpu_run, capsys, monkeypatch):
    real = JaxVectorDB.search

    def altered(self, vectors, k):
        out = real(self, vectors, k)
        r = out[0]
        r.chunk_ids = np.roll(np.asarray(r.chunk_ids), 1)
        return out

    monkeypatch.setattr(JaxVectorDB, "search", altered)
    assert cpu_run("tiny-flat.poisson") == 0
    res = _last(capsys)
    assert res["correct"] is False
    assert (res["checks"]["retrieve.score_gap"]["value"]
            > res["checks"]["retrieve.score_gap"]["limit"])


def test_the_blockwise_reference_finds_the_exact_top_k(monkeypatch):
    """The retrieval reference makes the corpus one block of rows at a time
    and merges each block's candidates: over several blocks it must find
    what one exact pass over all rows finds, and fail an altered answer."""
    import functools

    from benchlib import cell, checks, ref_retrieve
    blocks = functools.partial(cell.vector_blocks, block=512)
    monkeypatch.setattr(checks, "vector_blocks", blocks)
    cfg = {"pipeline": {"retrieve_k": 16,
                        "embedder": {"options": {"dim": 32}},
                        "vectordb": {"options": {"index_type": "flat"}}},
           "corpus": {"kind": "clustered_vectors", "rows": 2000, "dim": 32,
                      "clusters": 16, "seed": 0}}
    x = np.concatenate([np.asarray(b) for _, b in blocks(2000, 32, 16, 0)])
    qs = [f"w{i} w{i + 7} w{3 * i}" for i in range(24)]
    s = ref_retrieve.HashEmbed(32, 32768, 0)(qs).astype(np.float64) \
        @ x.astype(np.float64).T
    ids = np.argsort(-s, axis=1, kind="stable")[:, :16]
    scores = np.take_along_axis(s, ids, axis=1).astype(np.float32)
    row_of = {i: i for i in range(2000)}
    served = [(qs[:10], list(ids[:10]), list(scores[:10])),
              (qs[10:], list(ids[10:]), list(scores[10:]))]
    res = checks.retrieval(cfg, 1, served, None, row_of)
    assert res["recall_at_10"] == 1.0
    assert res["numbers"]["retrieve.bad_rows"] == 0
    assert res["numbers"]["retrieve.score_gap"] < 1e-6
    ids[3] = np.roll(ids[3], 1)
    altered = [(qs, list(ids), list(scores))]
    res = checks.retrieval(cfg, 1, altered, None, row_of)
    assert res["numbers"]["retrieve.score_gap"] > 1e-3
