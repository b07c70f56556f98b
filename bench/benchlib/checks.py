"""The comparison that decides ``correct``: what the window's timed path
served, against the plain references, after the window has closed and the
program's device state is freed.

Numbers compared, each with its own limit (the configuration's ``limits``,
set from readings of sound runs and of the control, see ``PERF.md``):

* ``serve.unanswered``: queries due in the window that were never
  answered, or failed (limit 0);
* ``retrieve.bad_rows``: searches whose ids repeat, are missing or
  unknown, or whose scores are out of order (limit 0);
* ``retrieve.score_gap``: the widest distance between a served score and
  the exact score of its id and, for an exact index, the exact score at
  its rank, over every search of the window;
* ``gen.short_answers``: answers with fewer tokens than ``max_new``
  (limit 0);
* ``gen.logit_gap``: over a sample of the window's answers drawn from the
  seed, the longest prompt among them, the widest gap by which a served
  token's reference logit lies below the reference's best at its position.

The controls, for the control script only: ``gen.logit_gap`` of the
float8-weight reference put in the program's place, and
``retrieve.score_gap`` of a search at ``precision=HIGH`` put in its place.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import ref_model, ref_retrieve
from benchlib.cell import vector_blocks


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), 3])


def retrieval(cfg: Dict, seed: int, retrievals: List, texts: Optional[List],
              row_of: Dict[int, int], control: bool = False) -> Dict:
    """Numbers of every search the window served, and ``recall_at_10``."""
    k = int(cfg["pipeline"]["retrieve_k"])
    emb_opts = cfg["pipeline"]["embedder"]["options"]
    emb = ref_retrieve.HashEmbed(int(emb_opts["dim"]),
                                 int(emb_opts.get("vocab_size", 32768)),
                                 cfg["corpus"]["seed"])
    flat = cfg["pipeline"]["vectordb"]["options"].get("index_type") == "flat"
    questions = [q for qs, _, _ in retrievals for q in qs]
    ids = np.stack([i for _, ii, _ in retrievals for i in ii]) \
        if retrievals else np.zeros((0, k), np.int64)
    scores = np.stack([s for _, _, ss in retrievals for s in ss]) \
        if retrievals else np.zeros((0, k), np.float32)
    rows = np.vectorize(lambda i: row_of.get(int(i), -1), otypes=[np.int64])(
        ids) if ids.size else ids.astype(np.int64)
    q = emb(questions)
    c = cfg["corpus"]
    if c["kind"] == "synthetic_text":
        x = emb(texts).astype(np.float64)
        exact_all = q.astype(np.float64) @ x.T                  # [nq, n]
        order = np.argsort(-exact_all, axis=1, kind="stable")[:, :k]
        exact_sorted = np.take_along_axis(exact_all, order, axis=1)
        exact_ids = order
        exact_of_served = np.where(
            rows >= 0, np.take_along_axis(exact_all, np.maximum(rows, 0),
                                          axis=1), np.nan)
        ctl = None
        if control:
            cs, ci = ref_retrieve.control_topk(jnp.asarray(emb(texts)), q, k)
            ctl = (ci, cs, np.take_along_axis(exact_all, ci, axis=1))
    else:
        # the corpus made again from its seed, one block of rows at a time:
        # each block's exact top-2k candidates (float32 at HIGHEST), their
        # float64 scores, and those of the served ids the block holds
        q64 = q.astype(np.float64)
        exact_of_served = np.full(rows.shape, np.nan)
        cand_i, cand_s, ctl_parts = [], [], []
        for lo, xb in vector_blocks(c["rows"], c["dim"], c["clusters"],
                                    c["seed"]):
            nb = xb.shape[0]
            ci = ref_retrieve.exact_topk_device(xb, q, min(2 * k, nb))
            cand_i.append(ci + lo)
            cand_s.append(ref_retrieve.exact_of(xb, q64, ci))
            here = (rows >= lo) & (rows < lo + nb)
            if here.any():
                s = ref_retrieve.exact_of(xb, q64, np.where(here, rows - lo, 0))
                exact_of_served[here] = s[here]
            if control:
                cs, cj = ref_retrieve.control_topk(xb, q, min(k, nb))
                ctl_parts.append((cj + lo, cs,
                                  ref_retrieve.exact_of(xb, q64, cj)))
            del xb
        cand_i = np.concatenate(cand_i, axis=1)
        cand_s = np.concatenate(cand_s, axis=1)
        order = np.argsort(-cand_s, axis=1, kind="stable")[:, :k]
        exact_sorted = np.take_along_axis(cand_s, order, axis=1)
        exact_ids = np.take_along_axis(cand_i, order, axis=1)
        ctl = None
        if control:
            # the control's own top-k over all blocks, by its own scores
            c_ids, c_scores, c_exact = (np.concatenate(a, axis=1)
                                        for a in zip(*ctl_parts))
            top = np.argsort(-c_scores, axis=1, kind="stable")[:, :k]
            ctl = tuple(np.take_along_axis(a, top, axis=1)
                        for a in (c_ids, c_scores, c_exact))
    out = ref_retrieve.compare(rows, scores, exact_of_served, exact_sorted,
                               flat, k)
    res = {"numbers": {"retrieve.bad_rows": out["bad_rows"],
                       "retrieve.score_gap": out["score_gap"]},
           "recall_at_10": ref_retrieve.recall(rows, exact_ids, 10)
           if len(rows) else None}
    if ctl is not None:
        c_ids, c_scores, c_exact = ctl
        res["control"] = {"retrieve.score_gap": ref_retrieve.compare(
            c_ids, c_scores, c_exact, exact_sorted, flat, k)["score_gap"]}
    return res


def generation(cfg: Dict, seed: int, retired: List,
               control: bool = False) -> Dict:
    """Numbers of a sample of the window's answers."""
    m = cfg["model"]
    max_new = int(cfg["pipeline"]["llm"]["options"]["max_new"])
    n_check = int(cfg["check"]["answers"])
    short = sum(1 for _, _, out in retired if len(out) != max_new)
    if not retired:
        return {"numbers": {"gen.short_answers": float(short)}}
    longest = int(np.argmax([len(t) for _, t, _ in retired]))
    rest = [i for i in range(len(retired)) if i != longest]
    pick = [longest] + list(_rng(seed).choice(
        rest, size=min(n_check - 1, len(rest)), replace=False))
    seqs, at, served = [], [], []
    for i in pick:
        _, prompt, out = retired[i]
        seqs.append(np.concatenate([prompt, np.asarray(out[:-1], np.int32)]))
        at.append(list(range(len(prompt) - 1, len(prompt) - 1 + len(out))))
        served.append(np.asarray(out))
    w = ref_model.make_weights(m, seed)
    ref = ref_model.logits_at(m, w, seqs, at)
    tok = np.concatenate(served)
    best = ref.max(axis=1)
    gap = best - ref[np.arange(len(tok)), tok]
    res = {"numbers": {"gen.short_answers": float(short),
                       "gen.logit_gap": float(gap.max())},
           "tokens_compared": int(len(tok))}
    if control:
        low = ref_model.logits_at(m, w, seqs, at, fp8=True)
        first = low.argmax(axis=1)
        res["control"] = {"gen.logit_gap": float(
            (best - ref[np.arange(len(first)), first]).max())}
    del w
    return res


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """Each number beside its limit, and whether all are within."""
    out = {}
    for name, v in numbers.items():
        lim = float(limits.get(name, 0.0))
        out[name] = {"value": v, "limit": lim}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return {"ok": ok, "checks": out}
