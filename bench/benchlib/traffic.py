"""The load generator: due times and requests, a pure function of the
traffic file and ``--seed``.

A traffic file states its arrival process, rate, op mix and question
popularity; ``check`` refuses, before any set-up, a value this generator
does not implement, so a mix it cannot offer never runs under a cell's
name.  Implemented: Poisson arrivals, queries only, uniform popularity.

The Poisson draw follows ``repro.serving.arrival.arrival_times`` (copied,
so that a change to the program cannot change the load it is measured
under) with one change: the ``n`` gaps of a Poisson process at rate ``r``
are the ``n`` quantiles of the exponential distribution,
``-ln(1 - (i + 1/2) / n) / r``, in an order drawn from the seed.  Every
seed then offers the same number of requests and the same set of gaps in
another order, so seeds differ in when bursts come and not in how much
work a window holds; ``questions`` does the same for the queries.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchlib.spec import SpecError

PROCESSES = ("poisson",)
OPS = ("query",)
POPULARITIES = ("uniform",)


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), salt])


def check(traffic: Dict) -> None:
    """Refuse a mix whose arrival process, op mix or question popularity
    the generator does not implement."""
    try:
        process = traffic["arrival"]["process"]
        ops = traffic["ops"]
        popularity = traffic["questions"]["popularity"]
    except (KeyError, TypeError) as e:
        raise SpecError(f"traffic file lacks {e}") from None
    if process not in PROCESSES:
        raise SpecError(f"arrival process {process!r} is not implemented; "
                        f"known: {list(PROCESSES)}")
    unknown = sorted(set(ops) - set(OPS))
    if unknown or float(ops.get("query", 0.0)) != 1.0:
        raise SpecError(f"op mix {ops!r} is not implemented: queries only "
                        f"({{\"query\": 1.0}})")
    if popularity not in POPULARITIES:
        raise SpecError(f"question popularity {popularity!r} is not "
                        f"implemented; known: {list(POPULARITIES)}")


def arrival_times(rate: float, n: int, seed: int) -> np.ndarray:
    """[n] nondecreasing Poisson due offsets in seconds from the window's
    start: the exponential's quantiles as gaps, in the seed's order."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    g = g[_rng(seed, 1).permutation(n)]
    g[0] = 0.0
    return np.cumsum(g)


def _order(items: List, seed: int) -> List:
    return [items[i] for i in _rng(seed, 4).permutation(len(items))]


def due_times(traffic: Dict, seconds: float, seed: int) -> np.ndarray:
    """Due offsets of every request of one window: ``rate x seconds`` of
    them, all inside ``[0, seconds)``."""
    check(traffic)
    rate = float(traffic["arrival"]["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    t = arrival_times(rate, n, seed)
    return t[t < seconds]


def questions(cfg: Dict, traffic: Dict, n: int, seed: int, corpus=None,
              salt: int = 2) -> List[Dict]:
    """``n`` queries of the traffic's popularity (uniform): one set drawn
    from the corpus's own seed, in an order drawn from ``seed``, so that
    every seed asks the same questions (the same retrievals and prompt
    lengths) and seeds differ only in their order.

    A text corpus (``synthetic_text``) is asked about its own facts, one
    document drawn uniformly per query; a vector corpus is asked free text
    whose hash embedding points in a drawn direction.
    """
    check(traffic)
    rng = _rng(cfg["corpus"]["seed"], salt)
    kind = cfg["corpus"]["kind"]
    out = []
    if kind == "synthetic_text":
        n_docs = len(corpus.facts)
        for i in range(n):
            doc = int(rng.integers(0, n_docs))
            facts = corpus.facts[doc]
            f = facts[int(rng.integers(0, len(facts)))]
            out.append({"question": f.question(), "answer": f.value,
                        "doc_id": doc})
        return _order(out, seed)
    if kind == "clustered_vectors":
        words = cfg["corpus"].get("query_words", 8)
        for i in range(n):
            ws = " ".join(f"w{int(x)}" for x in rng.integers(0, 1 << 20,
                                                             size=words))
            out.append({"question": ws, "answer": "", "doc_id": -1})
        return _order(out, seed)
    raise ValueError(f"unknown corpus kind {kind!r}")
