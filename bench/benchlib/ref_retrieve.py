"""Plain references of the retrieve layer: the hash embedding of a text and
exact scores over a corpus, and the comparison of what a search served.

The hash embedding is the configuration's stated one (``embedder: hash``):
a text's content words (``[a-z0-9]+``, lower case, function words
dropped), each hashed by 64-bit FNV-1a into ``[4, vocab)``; the mean of
those rows of a table ``N(0, 1) / sqrt(dim)`` drawn from ``PRNGKey(seed)``,
scaled to unit length.  Exact scores are float64 dot products.
"""
from __future__ import annotations

import math
import re
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_WORD = re.compile(r"[a-z0-9]+")
STOPWORDS = frozenset(
    "a an the is are was were be of what which who where when how why in on "
    "at to for and or it its this that with as by from".split())
N_SPECIAL = 4


def _fnv1a(word: str) -> int:
    h = 0xCBF29CE484222325
    for b in word.encode():
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def token_ids(text: str, vocab: int) -> List[int]:
    return [N_SPECIAL + _fnv1a(w) % (vocab - N_SPECIAL)
            for w in _WORD.findall(text.lower()) if w not in STOPWORDS]


class HashEmbed:
    def __init__(self, dim: int, vocab: int, seed: int):
        self.dim, self.vocab = dim, vocab
        self.table = np.asarray(jax.random.normal(
            jax.random.PRNGKey(seed), (vocab, dim), jnp.float32)) \
            / math.sqrt(dim)

    def __call__(self, texts: Sequence[str]) -> np.ndarray:
        out = np.zeros((len(texts), self.dim), dtype=np.float32)
        for i, t in enumerate(texts):
            ids = token_ids(t, self.vocab)
            if ids:
                v = self.table[np.asarray(ids)].mean(0)
                out[i] = v / (np.linalg.norm(v) + 1e-9)
        return out


@partial(jax.jit, static_argnums=2)
def _exact_topk(x, q, k: int):
    return jax.lax.top_k(
        jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST), k)[1]


def exact_topk_device(x, q: np.ndarray, k: int, block: int = 256):
    """Candidates of the exact top-``k`` of ``q`` over the device corpus
    ``x`` (float32 at HIGHEST), in blocks of queries."""
    out = []
    for lo in range(0, len(q), block):
        qb = q[lo:lo + block]
        pad = block - len(qb)
        qb = np.pad(qb, ((0, pad), (0, 0)))
        out.append(np.asarray(_exact_topk(x, jnp.asarray(qb), k))
                   [:block - pad])
    return np.concatenate(out) if out else np.zeros((0, k), np.int64)


def exact_of(x, q64: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """float64 dot products of each query ``q64[i]`` with the rows
    ``idx[i, :]`` of the device corpus ``x``."""
    rows = np.asarray(x[jnp.asarray(idx)]).astype(np.float64)  # [nq, c, d]
    return np.einsum("qd,qcd->qc", q64, rows)


def dot_high(q, x):
    """``q . x^T`` as ``precision=HIGH`` computes it on a TPU: each float32
    operand split into a bfloat16 high part and a bfloat16 remainder, and the
    three products that matter summed in float32 (the low x low term is
    dropped).  Written out so that it is the same on every backend."""
    f32, bf16 = jnp.float32, jnp.bfloat16

    def split(a):
        # the high part by masking the low 16 bits: a round trip through
        # bfloat16 would be folded away by XLA's excess-precision rewrites
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), f32)
        return hi.astype(bf16), (a - hi).astype(bf16)

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)
    qh, ql = split(q)
    xh, xl = split(x)
    # the barrier keeps XLA from folding two products that share an operand
    # into one product of a bfloat16 sum, which would drop the low parts
    hh, hl, lh = jax.lax.optimization_barrier(
        (dot(qh, xh), dot(qh, xl), dot(ql, xh)))
    return hh + hl + lh


@partial(jax.jit, static_argnums=2)
def _control_topk(x, q, k: int):
    return jax.lax.top_k(dot_high(q, x), k)


def control_topk(x, q: np.ndarray, k: int, block: int = 256):
    """The control of an exact float32 search: top-``k`` of ``dot_high``."""
    ss, ii = [], []
    for lo in range(0, len(q), block):
        s_, i_ = _control_topk(x, jnp.asarray(q[lo:lo + block]), k)
        ss.append(np.asarray(s_))
        ii.append(np.asarray(i_))
    return np.concatenate(ss), np.concatenate(ii)


def compare(served_ids: np.ndarray, served_scores: np.ndarray,
            exact_of_served: np.ndarray, exact_sorted: np.ndarray,
            flat: bool, k: int) -> Dict[str, float]:
    """The numbers that decide a search's correctness.

    ``score_gap``: the widest distance between a served score and the
    exact score of the id it was served with, and, for an exact (flat)
    index, between the served score and the exact score at the same rank;
    ``bad_rows``: searches whose ids repeat or are unknown
    (``exact_of_served`` NaN), whose scores are out of order, or whose
    padding (id -1, which an IVF search returns when its probed lists hold
    fewer than ``k`` rows) comes before a real id or, for an exact index,
    at all.
    """
    valid = served_ids >= 0
    gap = np.abs(served_scores.astype(np.float64) - exact_of_served)
    if flat:
        gap = np.maximum(gap, np.abs(served_scores.astype(np.float64)
                                     - exact_sorted[:, :served_ids.shape[1]]))
    gap = np.where(valid, gap, np.nan)
    bad = 0
    for ids, sc, ex, ok in zip(served_ids, served_scores, exact_of_served,
                               valid):
        nv = int(ok.sum())
        if (len(ids) != k or not ok[:nv].all() or (flat and nv < k)
                or len(set(ids[ok].tolist())) != nv
                or np.isnan(ex[ok]).any() or (np.diff(sc[ok]) > 0).any()):
            bad += 1
    finite = gap[np.isfinite(gap)]
    return {"score_gap": float(finite.max()) if finite.size else 0.0,
            "bad_rows": float(bad)}


def recall(served_ids: np.ndarray, exact_ids: np.ndarray, k: int) -> float:
    hits = sum(len(set(a[:k].tolist()) & set(b[:k].tolist()))
               for a, b in zip(served_ids, exact_ids))
    return hits / (k * len(served_ids))
