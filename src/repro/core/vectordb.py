"""JAX-native vector database (paper §3.3.2).

TPU adaptation (DESIGN.md §2): the index families are the MXU-friendly ones —
Flat (exact matmul + top-k), IVF (k-means partitions, ``nprobe`` probing,
fixed-capacity buckets so gathers are static-shaped), and the quantized
variants SQ-int8 and PQ (ADC lookup).  HNSW/DiskANN pointer-chasing graphs do
not map to the TPU memory system and are intentionally not ported.

Update path mirrors the paper's hybrid design: a temporary *flat* index
absorbs inserts/updates so fresh data is immediately searchable; queries merge
top-k from the main ANN index and the flat buffer; ``rebuild()`` folds the
buffer into the main index (paper §5.5 reproduces the latency sawtooth this
creates).  Removals are tombstones until the next rebuild.

All heavy scoring runs in jitted JAX (optionally via the Pallas kernels in
``repro.kernels``); bookkeeping (payloads, id maps) is host-side numpy.  The
host arrays are the source of truth; the index arrays a search reads stay on
the device between searches, as copies refreshed when the host's change.
"""
from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.interfaces import Chunk, DBInstance, SearchResult
from repro.core.registry import register
from repro.kernels import ops as kops
from repro.kernels.ref import HIGHEST

NEG = np.float32(-3.0e38)

# the use_kernel ladder: how much of the retrieve hot path runs in Pallas
#   off   — pure-jnp scoring (the reference ladder)
#   op    — individual kernel ops (topk_search / quant_score), unfused
#   fused — probe -> (dequant-)score -> select in one launch; IVF/PQ search
#           runs over a bucket-contiguous packed mirror (see
#           repro.kernels.fused_retrieve)
KERNEL_LADDER = ("off", "op", "fused")


def kernel_ladder(use_kernel) -> str:
    """Normalize the ``use_kernel`` config value to a ladder rung.

    Accepts the legacy booleans (``False`` -> ``off``, ``True`` -> ``op``)
    and the string rungs; anything else raises naming the allowed values.
    """
    if use_kernel is None or use_kernel is False:
        return "off"
    if use_kernel is True:
        return "op"
    if use_kernel in KERNEL_LADDER:
        return use_kernel
    raise ValueError(
        f"invalid use_kernel={use_kernel!r}; allowed values: "
        f"False/True or {', '.join(KERNEL_LADDER)}")


# ---------------------------------------------------------------------------
# k-means (IVF training / PQ codebooks)
# ---------------------------------------------------------------------------


def kmeans(x: jnp.ndarray, k: int, iters: int = 10, seed: int = 0) -> jnp.ndarray:
    """Lloyd's k-means on the device; returns [k, dim] centroids."""
    n = x.shape[0]
    key = jax.random.PRNGKey(seed)
    idx = jax.random.choice(key, n, (k,), replace=n < k)
    cent = x[idx]

    @jax.jit
    def step(cent):
        scores = x @ cent.T                               # [n, k]
        assign = jnp.argmax(scores, axis=1)
        onehot = jax.nn.one_hot(assign, k, dtype=x.dtype)  # [n, k]
        sums = onehot.T @ x                               # [k, dim]
        counts = onehot.sum(0)[:, None]
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), cent)
        return new / (jnp.linalg.norm(new, axis=1, keepdims=True) + 1e-9)

    for _ in range(iters):
        cent = step(cent)
    return cent


# ---------------------------------------------------------------------------
# jitted search primitives (static shapes; cached per (capacity, k, ...))
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("k", "kernel", "mode"))
def _flat_search(q, vecs, live, k: int, kernel: str = "off",
                 mode: str = "interpret"):
    """Exact search. q:[nq,d] vecs:[cap,d] live:[cap] -> (scores, idx) [nq,k].

    ``mode`` is resolved by the caller *outside* the jit (kernel-dispatch
    contract in ``repro.kernels.ops``: an env read at trace time would be
    baked into the cache).  All rungs/modes return ``(NEG, -1)`` padding
    for rows with fewer than ``k`` live entries.
    """
    if kernel == "fused":
        return kops.fused_flat_topk(q, vecs, live, k, mode=mode)
    if kernel == "op":
        return kops.topk_search(q, vecs, live, k, mode=mode)
    scores = jnp.dot(q, vecs.T, precision=HIGHEST)        # [nq, cap]
    scores = jnp.where(live[None, :], scores, NEG)
    top, idx = jax.lax.top_k(scores, k)
    return top, jnp.where(top <= NEG / 2, -1, idx)


@partial(jax.jit, static_argnames=("nprobe", "k"))
def _ivf_search(q, vecs, live, cent, buckets, bucket_live, nprobe: int, k: int):
    """IVF probe: pick nprobe buckets per query, score their members.

    buckets: [nlist, cap_b] int32 slot ids (-1 pad); bucket_live likewise bool.
    """
    cscores = jnp.dot(q, cent.T, precision=HIGHEST)       # [nq, nlist]
    _, probe = jax.lax.top_k(cscores, nprobe)             # [nq, nprobe]
    cand = buckets[probe]                                 # [nq, nprobe, cap_b]
    cand_ok = bucket_live[probe] & (cand >= 0)
    cand_safe = jnp.maximum(cand, 0)
    cvecs = vecs[cand_safe]                               # [nq, np, cap_b, d]
    scores = jnp.einsum("qd,qpbd->qpb", q, cvecs, precision=HIGHEST)
    ok = cand_ok & live[cand_safe]
    scores = jnp.where(ok, scores, NEG)
    nq = q.shape[0]
    flat = scores.reshape(nq, -1)
    top, pos = jax.lax.top_k(flat, k)
    idx = jnp.take_along_axis(cand_safe.reshape(nq, -1), pos, axis=1)
    idx = jnp.where(top <= NEG / 2, -1, idx)
    return top, idx


@partial(jax.jit, static_argnames=("k", "kernel", "mode"))
def _sq8_flat_search(q, codes, scale, live, k: int, kernel: str = "off",
                     mode: str = "interpret"):
    """Scalar-quantized exact search.

    Unfused rungs score the whole corpus via ``quant_score`` (a full
    ``[nq, N]`` matrix plus an int8->f32 corpus upcast) and reduce
    afterwards; the ``fused`` rung selects in VMEM and never materializes
    either.
    """
    if kernel == "fused":
        return kops.fused_sq8_topk(q, codes, scale, live, k, mode=mode)
    scores = kops.quant_score(q, codes, scale, mode=mode)
    scores = jnp.where(live[None, :], scores, NEG)
    top, idx = jax.lax.top_k(scores, k)
    return top, jnp.where(top <= NEG / 2, -1, idx)


@partial(jax.jit, static_argnames=("nprobe", "k"))
def _pq_ivf_search(q, codes, codebook, live, cent, buckets, bucket_live,
                   nprobe: int, k: int):
    """PQ asymmetric-distance search inside probed IVF buckets.

    codes: [cap, m] int32 in [0,256); codebook: [m, 256, dsub].
    """
    m, _, dsub = codebook.shape
    nq = q.shape[0]
    qs = q.reshape(nq, m, dsub)
    lut = jnp.einsum("qms,mcs->qmc", qs, codebook,
                     precision=HIGHEST)                   # [nq, m, 256]
    cscores = jnp.dot(q, cent.T, precision=HIGHEST)
    _, probe = jax.lax.top_k(cscores, nprobe)
    cand = buckets[probe]                                 # [nq, np, cap_b]
    cand_ok = bucket_live[probe] & (cand >= 0)
    cand_safe = jnp.maximum(cand, 0)
    ccodes = codes[cand_safe]                             # [nq, np, cap_b, m]
    # ADC: sum LUT entries selected by each subspace code
    gath = jnp.take_along_axis(
        lut[:, None, None],                               # [nq,1,1,m,256]
        ccodes[..., None], axis=-1)[..., 0]               # [nq,np,cap_b,m]
    scores = gath.sum(-1)
    ok = cand_ok & live[cand_safe]
    scores = jnp.where(ok, scores, NEG)
    flat = scores.reshape(nq, -1)
    top, pos = jax.lax.top_k(flat, k)
    idx = jnp.take_along_axis(cand_safe.reshape(nq, -1), pos, axis=1)
    idx = jnp.where(top <= NEG / 2, -1, idx)
    return top, idx


@jax.jit
def _put_rows(dev, rows, lo):
    """``dev`` with ``rows`` written from row ``lo``, as a new array: a
    snapshot not yet launched may still hold ``dev``."""
    return jax.lax.dynamic_update_slice(dev, rows, (lo, 0))


# each search program's arguments after the queries: index arrays by name
# (``packed.*`` the fused kernels' mirror), the ``mask`` of rows it scans,
# or the fused kernels' ``ok``, made from that mask per packed row
_ARGS = {
    "flat": ("vectors", "mask"),
    "sq8": ("sq_codes", "sq_scale", "mask"),
    "ivf": ("vectors", "mask", "centroids", "buckets", "bucket_live"),
    "pq_ivf": ("pq_codes", "pq_codebook", "mask", "centroids", "buckets",
               "bucket_live"),
    "fused_ivf": ("centroids", "packed.vecs", "packed.slot", "ok"),
    "fused_pq": ("pq_codebook", "centroids", "packed.codes", "packed.slot",
                 "ok"),
}


def merge_topk(scores_a, idx_a, scores_b, idx_b, k: int):
    """Merge two top-k lists (used for hybrid main+flat and sharded search).

    Output rows are sorted by descending score and deduplicated by id (the
    best-scoring occurrence wins), so a chunk surfaced by both the main index
    and the flat freshness buffer appears once.  Rows with fewer than ``k``
    distinct valid ids are padded with ``(NEG, -1)``.
    """
    scores = np.concatenate([scores_a, scores_b], axis=1)
    idx = np.concatenate([idx_a, idx_b], axis=1)
    nq = scores.shape[0]
    va, vb = idx_a[idx_a >= 0], idx_b[idx_b >= 0]
    if not np.isin(va, vb).any():
        # no id can repeat (within-list top-k ids are distinct; hybrid
        # main/fresh slot sets are disjoint): vectorized merge
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        return (np.take_along_axis(scores, order, axis=1),
                np.take_along_axis(idx, order, axis=1))
    out_s = np.full((nq, k), NEG, dtype=scores.dtype)
    out_i = np.full((nq, k), -1, dtype=idx.dtype)
    order = np.argsort(-scores, axis=1, kind="stable")
    for r in range(nq):
        seen = set()
        j = 0
        for c in order[r]:
            i = int(idx[r, c])
            if i >= 0:
                if i in seen:
                    continue
                seen.add(i)
            out_s[r, j] = scores[r, c]
            out_i[r, j] = i
            j += 1
            if j == k:
                break
    return out_s, out_i


# ---------------------------------------------------------------------------
# the database
# ---------------------------------------------------------------------------


@dataclass
class DBConfig:
    index_type: str = "ivf"          # flat | ivf
    quant: str = "none"              # none | sq8 | pq
    dim: int = 384
    capacity: int = 1 << 16
    nlist: int = 64
    nprobe: int = 8
    bucket_cap: int = 0              # 0 -> auto: 4 * capacity / nlist
    pq_m: int = 8                    # PQ subspaces
    kmeans_iters: int = 8
    use_hybrid: bool = True          # temp flat buffer for fresh inserts
    flat_capacity: int = 4096
    rebuild_threshold: float = 0.75  # rebuild when flat buffer this full
    # kernel ladder rung: False/"off" | True/"op" | "fused" (see KERNEL_LADDER)
    use_kernel: object = False
    train_sample: int = 16384


class JaxVectorDB(DBInstance):
    """Unified vector DB: flat/IVF × {none, sq8, pq} × hybrid updates.

    Thread-safety contract (elastic serving): all mutations
    (insert/remove/update/build_index) serialize on one reentrant lock, and
    ``search`` snapshots every piece of index state it needs under that same
    lock before computing outside it.  Writers only ever (a) fill slots that
    are not yet live, (b) flip ``live``/``indexed`` bits, or (c) swap whole
    index arrays — so a search running against its snapshot sees a
    consistent (possibly slightly stale) view, never a torn one.

    The snapshot also brings the device copies of the index arrays the
    search reads up to date (``_refresh``), so a search hands the device
    only its queries and masks unless a writer changed what it reads.
    """

    def __init__(self, cfg: DBConfig):
        self.cfg = cfg
        self._kernel = kernel_ladder(cfg.use_kernel)  # validated ladder rung
        self._mu = threading.RLock()   # serializes mutations vs snapshots
        d, cap = cfg.dim, cfg.capacity
        self.vectors = np.zeros((cap, d), dtype=np.float32)  # guarded-by: _mu
        self.live = np.zeros((cap,), dtype=bool)             # guarded-by: _mu
        self.n_slots = 0                       # guarded-by: _mu
        self.chunks: Dict[int, Chunk] = {}     # guarded-by: _mu
        self.doc_slots: Dict[int, List[int]] = {}   # guarded-by: _mu
        # main-index state
        self.centroids: Optional[np.ndarray] = None      # guarded-by: _mu
        self.buckets: Optional[np.ndarray] = None        # guarded-by: _mu
        self.bucket_live: Optional[np.ndarray] = None    # guarded-by: _mu
        self.indexed = np.zeros((cap,), dtype=bool)      # guarded-by: _mu
        self.sq_codes: Optional[np.ndarray] = None       # guarded-by: _mu
        self.sq_scale: Optional[np.ndarray] = None       # guarded-by: _mu
        self.pq_codes: Optional[np.ndarray] = None       # guarded-by: _mu
        self.pq_codebook: Optional[np.ndarray] = None    # guarded-by: _mu
        # bucket-contiguous mirror for the fused IVF/PQ kernels: row
        # b*cap_b+j holds bucket b's j-th member (slot map + gathered
        # vectors/codes); rebuilt wholesale with the buckets, rows are
        # immutable in between (inserts always take fresh slots)
        self.packed: Optional[Dict[str, np.ndarray]] = None  # guarded-by: _mu
        # device copies of the index arrays searches read, by ``_ARGS``
        # name: (the host array copied, its device copy, ``n_slots`` then)
        self._mirror: Dict[str, Tuple[np.ndarray, jax.Array, int]] = {}  # guarded-by: _mu
        # profiling counters (read by the monitor)
        self.counters: Dict[str, float] = {   # guarded-by: _mu
            "inserts": 0, "removals": 0, "searches": 0, "rebuilds": 0,
            "fused_searches": 0, "h2d_bytes": 0,
            "insert_time_s": 0.0, "build_time_s": 0.0, "search_time_s": 0.0,
            "flat_fill": 0.0,
        }
        if cfg.quant == "pq":
            assert d % cfg.pq_m == 0, (d, cfg.pq_m)
        # optional obs.Tracer for the search's own spans (serve.py
        # --trace-out attaches one)
        self.tracer = None

    # -- writes ------------------------------------------------------------

    def insert(self, vectors: np.ndarray, chunks: Sequence[Chunk]) -> None:
        t0 = time.perf_counter()
        n = len(chunks)
        assert vectors.shape == (n, self.cfg.dim)
        with self._mu:
            if self.n_slots + n > self.cfg.capacity:
                raise MemoryError(
                    f"vector store full ({self.n_slots}+{n} > "
                    f"{self.cfg.capacity})")
            slots = np.arange(self.n_slots, self.n_slots + n)
            self.n_slots += n
            # fill payloads before flipping live: a concurrent search that
            # snapshotted earlier masks these rows out; one that snapshots
            # after sees complete rows
            self.vectors[slots] = vectors
            for s, c in zip(slots, chunks):
                c.chunk_id = int(s)
                self.chunks[int(s)] = c
                self.doc_slots.setdefault(c.doc_id, []).append(int(s))
            self.live[slots] = True
            self.counters["inserts"] += n
            self.counters["insert_time_s"] += time.perf_counter() - t0
            if self._main_built() and self.cfg.use_hybrid:
                self._maybe_rebuild()
            elif self._main_built():
                # no hybrid buffer: fresh rows invisible until next rebuild
                pass

    def remove(self, doc_id: int) -> int:
        with self._mu:
            slots = self.doc_slots.pop(doc_id, [])
            for s in slots:
                self.live[s] = False
                self.chunks.pop(s, None)
            self.counters["removals"] += len(slots)
            return len(slots)

    def update(self, doc_id: int, vectors: np.ndarray,
               chunks: Sequence[Chunk]) -> None:
        """Replace a document's chunks (delete + insert semantics)."""
        with self._mu:
            self.remove(doc_id)
            self.insert(vectors, chunks)

    def set_nprobe(self, nprobe: int) -> None:
        """Adjust IVF probe depth at runtime (the autoscaler quality knob).

        Takes effect on the next search; each distinct value has its own jit
        cache entry (``nprobe`` is a static argument), so ladders should use
        a handful of levels, not a continuum.
        """
        self.cfg.nprobe = max(1, int(nprobe))

    # -- index build -------------------------------------------------------

    def _main_built(self) -> bool:  # locked-by: _mu
        return self.cfg.index_type == "flat" or self.centroids is not None

    def build_index(self) -> None:
        with self._mu:
            self._build_index_locked()

    def _build_index_locked(self) -> None:  # locked-by: _mu
        t0 = time.perf_counter()
        cfg = self.cfg
        live_idx = np.nonzero(self.live)[0]
        if cfg.quant == "sq8":
            self._train_sq()
        if cfg.quant == "pq":
            self._train_pq(live_idx)
        if cfg.index_type == "ivf" and len(live_idx):
            x = jnp.asarray(self.vectors[live_idx])
            sample = live_idx
            if len(live_idx) > cfg.train_sample:
                rng = np.random.default_rng(0)
                sample = rng.choice(live_idx, cfg.train_sample, replace=False)
            self.centroids = np.asarray(
                kmeans(jnp.asarray(self.vectors[sample]), cfg.nlist,
                       cfg.kmeans_iters))
            assign = np.asarray(
                jnp.argmax(x @ jnp.asarray(self.centroids).T, axis=1))
            cap_b = cfg.bucket_cap or max(
                16, int(4 * cfg.capacity / cfg.nlist))
            buckets = np.full((cfg.nlist, cap_b), -1, dtype=np.int32)
            fill = np.zeros(cfg.nlist, dtype=np.int64)
            overflow = 0
            for slot, b in zip(live_idx, assign):
                if fill[b] < cap_b:
                    buckets[b, fill[b]] = slot
                    fill[b] += 1
                else:
                    # spill to the globally least-full bucket (keeps recall)
                    b2 = int(np.argmin(fill))
                    if fill[b2] < cap_b:
                        buckets[b2, fill[b2]] = slot
                        fill[b2] += 1
                    else:
                        overflow += 1
            self.buckets = buckets
            self.bucket_live = buckets >= 0
            if overflow:
                raise MemoryError(f"{overflow} vectors overflowed IVF buckets")
            if self._kernel == "fused":
                self._build_packed_locked()
        self.indexed[:] = False
        self.indexed[live_idx] = True
        self.counters["rebuilds"] += 1
        self.counters["build_time_s"] += time.perf_counter() - t0

    def _build_packed_locked(self) -> None:  # locked-by: _mu
        """Rebuild the bucket-contiguous mirror for the fused kernels.

        ``slot`` maps packed row -> original slot id (-1 pad); the gathered
        vectors/codes rows are copies, so later tombstones only affect the
        search-time ``ok`` mask, never the mirrored data.  The mirror is
        kept in the kernels' layout: buckets padded to a multiple of 128
        rows, ``slot`` as one ``[1, rows]`` int32 row (a bucket's slots are
        one lane-dense ``(1, cap_b)`` block), PQ codes subspace-major
        ``[m, rows]`` int32.
        """
        nlist, cap_b = self.buckets.shape
        slot = np.full((nlist, -(-cap_b // 128) * 128), -1, np.int32)
        slot[:, :cap_b] = self.buckets
        slot = slot.reshape(1, -1)
        safe = np.maximum(slot[0], 0)
        packed: Dict[str, np.ndarray] = {"slot": slot}
        if self.cfg.quant == "pq" and self.pq_codes is not None:
            packed["codes"] = np.ascontiguousarray(
                self.pq_codes[safe].T, dtype=np.int32)
        else:
            packed["vecs"] = self.vectors[safe]
        self.packed = packed

    def _train_sq(self):  # locked-by: _mu
        live_idx = np.nonzero(self.live)[0]
        x = self.vectors[: self.n_slots]
        scale = np.abs(x[live_idx]).max(axis=0) / 127.0 + 1e-12 \
            if len(live_idx) else np.ones(self.cfg.dim, np.float32)
        self.sq_scale = scale.astype(np.float32)
        codes = np.zeros((self.cfg.capacity, self.cfg.dim), dtype=np.int8)
        codes[: self.n_slots] = np.clip(
            np.round(x / scale), -127, 127).astype(np.int8)
        self.sq_codes = codes

    def _train_pq(self, live_idx):  # locked-by: _mu
        cfg = self.cfg
        m, dsub = cfg.pq_m, cfg.dim // cfg.pq_m
        x = self.vectors[live_idx] if len(live_idx) else self.vectors[:1]
        cb = np.zeros((m, 256, dsub), dtype=np.float32)
        codes = np.zeros((cfg.capacity, m), dtype=np.int32)
        for j in range(m):
            sub = x[:, j * dsub:(j + 1) * dsub]
            cb[j] = np.asarray(kmeans(jnp.asarray(sub), 256, cfg.kmeans_iters,
                                      seed=j))
            scores = sub @ cb[j].T
            codes[live_idx, j] = np.argmax(scores, axis=1)
        self.pq_codebook = cb
        self.pq_codes = codes

    def _maybe_rebuild(self):  # locked-by: _mu
        # only called with self._mu held (insert path)
        fresh = int((self.live & ~self.indexed).sum())
        self.counters["flat_fill"] = fresh / max(self.cfg.flat_capacity, 1)
        if fresh >= self.cfg.rebuild_threshold * self.cfg.flat_capacity:
            self._build_index_locked()

    # -- search ------------------------------------------------------------

    def search(self, vectors: np.ndarray, k: int) -> List[SearchResult]:
        t0 = time.perf_counter()
        q = (vectors if isinstance(vectors, jax.Array)
             else np.asarray(vectors, np.float32))
        scores, idx = self._search_arrays(q, k)
        with self._mu:   # concurrent retrieval replicas share the counters
            self.counters["searches"] += len(vectors)
            if self._kernel == "fused":
                self.counters["fused_searches"] += len(vectors)
            self.counters["search_time_s"] += time.perf_counter() - t0
        with obs.span("stage.retrieval.merge", self.tracer):
            return [SearchResult(chunk_ids=np.asarray(idx[i]),
                                 scores=np.asarray(scores[i]))
                    for i in range(len(vectors))]

    def _snapshot(self, resident: bool = True) -> Dict[str, object]:
        """Grab a consistent view of all search-relevant index state.

        Mask arrays are copied (writers flip their bits in place); index
        arrays are captured by reference (writers swap whole objects).
        ``vectors`` is referenced, not copied — rows mutated after the
        snapshot belong to slots that are non-live in the copied masks.
        ``plan`` names the programs the search runs (``_plan``); unless
        ``resident`` is false, ``dev`` holds the device copies of the
        index arrays they read, brought up to date here.
        """
        with self._mu:
            snap = {
                "built": self._main_built(),
                "live": self.live.copy(),
                "indexed": self.indexed.copy(),
                "vectors": self.vectors,
                "centroids": self.centroids,
                "buckets": self.buckets,
                "bucket_live": self.bucket_live,
                "sq_codes": self.sq_codes, "sq_scale": self.sq_scale,
                "pq_codes": self.pq_codes, "pq_codebook": self.pq_codebook,
                "packed": self.packed,
                "nprobe": self.cfg.nprobe,
                "dev": {},
            }
            snap["plan"] = self._plan(snap)
            if resident:
                with obs.span("stage.retrieval.refresh", self.tracer):
                    snap["dev"] = self._refresh(
                        {key for prog, _ in snap["plan"]
                         for key in _ARGS[prog] if key not in ("mask", "ok")})
            return snap

    def _refresh(self, keys) -> Dict[str, jax.Array]:  # locked-by: _mu
        """Device copies of the index arrays ``keys`` as the host holds them.

        Writers replace these arrays whole, so a copy is current while its
        host object is — except ``vectors``, into which ``insert`` only
        appends rows: its copy takes the rows appended since it was made.
        (On the CPU a copy may share the host's buffer; rows a snapshot
        counts live never change, so searches answer alike.)
        The bytes sent count in ``db.h2d_bytes``; the call counts as a
        ``db.resident_hits`` if nothing was sent, else a
        ``db.resident_pushes``.
        """
        out, sent = {}, 0
        for key in keys:
            host = (self.packed[key[len("packed."):]]
                    if key.startswith("packed.") else getattr(self, key))
            src, dev, n = self._mirror.get(key, (None, None, 0))
            if src is host and (n == self.n_slots or key != "vectors"):
                out[key] = dev
                continue
            if src is host and n:
                rows = host[n:self.n_slots]
                dev = _put_rows(dev, rows, n)
            else:
                # a new array, or every used row new: copy it whole, the
                # stale copy released first so HBM never holds both
                self._mirror.pop(key, None)
                del dev
                rows = host
                dev = jnp.asarray(host)
            sent += rows.nbytes
            self._mirror[key] = (host, dev, self.n_slots)
            out[key] = dev
        obs.count("db.resident_pushes" if sent else "db.resident_hits")
        if sent:
            obs.count("db.h2d_bytes", sent)
            self.counters["h2d_bytes"] += sent
        return out

    def _search_arrays(self, q, k: int,
                       snap: Optional[Dict[str, object]] = None
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k against ``snap`` (defaults to a fresh ``_snapshot()``).

        Callers that coordinate several databases — the sharded wrapper —
        take every snapshot under one lock first, then score outside it.

        Every host array the search hands the device (the queries, the
        masks, an index array the snapshot holds no device copy of) is
        sent before the first launch, in one ``stage.retrieval.h2d`` span,
        and its bytes are counted in ``db.h2d_bytes``; an array already on
        the device counts nothing.
        """
        tr = self.tracer
        with obs.span("stage.retrieval.snapshot", tr):
            if snap is None:
                snap = self._snapshot()
            launches = self._launches(k, snap)
        nbytes = sum(a.nbytes for a in [q] + [a for _, args in launches
                                               for a in args]
                     if isinstance(a, np.ndarray))
        with obs.span("stage.retrieval.h2d", tr, bytes=nbytes):
            qd = jnp.asarray(q, jnp.float32)
            dev = [[jnp.asarray(a) for a in args] for _, args in launches]
        obs.count("db.h2d_bytes", nbytes)
        obs.count("db.search_calls")
        with self._mu:
            self.counters["h2d_bytes"] += nbytes
        with obs.span("stage.retrieval.launch", tr):
            outs = [call(qd, *args)
                    for (call, _), args in zip(launches, dev)]
        with obs.span("stage.retrieval.wait", tr):
            outs = [(np.asarray(s), np.asarray(i)) for s, i in outs]
        if len(outs) == 1:
            return outs[0]
        with obs.span("stage.retrieval.merge", tr):
            return merge_topk(*outs[0], *outs[1], k)

    def _plan(self, snap: Dict[str, object]) -> List[Tuple[str, str]]:
        """The ``_ARGS`` programs a search of ``snap`` runs, each with the
        mask it scans: the main index, then (hybrid) the fresh rows'
        linear scan."""
        cfg = self.cfg
        if not snap["built"]:
            # index never built: brute-force everything (cold start)
            return [("flat", "live")]
        pq = cfg.quant == "pq"
        if cfg.index_type == "flat":
            sq8 = cfg.quant == "sq8" and snap["sq_codes"] is not None
            main = "sq8" if sq8 else "flat"
        elif self._kernel == "fused" and snap["packed"] is not None:
            # fused IVF/PQ probe over the packed mirror (one kernel launch)
            main = ("fused_pq" if pq and "codes" in snap["packed"]
                    else "fused_ivf")
        else:
            main = "pq_ivf" if pq and snap["pq_codes"] is not None else "ivf"
        if not cfg.use_hybrid:
            return [(main, "live")]
        plan = [(main, "main")]
        if (snap["live"] & ~snap["indexed"]).any():
            # linear scan of the temp flat buffer (the paper's freshness
            # path)
            plan.append(("flat", "fresh"))
        return plan

    def _launches(self, k: int, snap: Dict[str, object]
                  ) -> List[Tuple[Callable, Sequence]]:
        """The programs of ``snap["plan"]``, each as ``(call, arrays)``,
        run as ``call(q, *those arrays on the device)``."""
        cfg = self.cfg
        # kernel mode resolved here, OUTSIDE the jitted primitives, and
        # threaded through as a static argument (dispatch contract in
        # repro.kernels.ops: an env read at trace time goes stale)
        mode = kops.kernel_mode()
        # ladder values are sized for the global nlist; a row-partitioned
        # shard has proportionally fewer lists, so clamp
        nprobe = min(int(snap["nprobe"]), cfg.nlist)
        kernel = self._kernel
        calls = {
            "flat": lambda q, *a: _flat_search(q, *a, k, kernel, mode),
            "sq8": lambda q, *a: _sq8_flat_search(q, *a, k, kernel, mode),
            "ivf": lambda q, *a: _ivf_search(q, *a, nprobe, k),
            "pq_ivf": lambda q, *a: _pq_ivf_search(q, *a, nprobe, k),
            "fused_ivf": lambda q, *a: kops.fused_ivf_topk(
                q, *a, nprobe, k, mode=mode),
            "fused_pq": lambda q, *a: kops.fused_pq_topk(
                q, *a, nprobe, k, mode=mode),
        }
        live, indexed = snap["live"], snap["indexed"]
        masks = {"live": live}
        if cfg.use_hybrid:
            masks.update(main=live & indexed, fresh=live & ~indexed)
        out = []
        for prog, mask in snap["plan"]:
            args = []
            for key in _ARGS[prog]:
                if key == "mask":
                    args.append(masks[mask])
                elif key == "ok":
                    # the packed mirror's rows are immutable between
                    # rebuilds, so post-snapshot mutations are reflected
                    # exactly as in the unfused path: through the
                    # liveness mask alone.  A tombstone lands as ``ok=0``
                    # on the dead row, identical to ``_ivf_search``
                    # masking it to NEG.
                    slot = snap["packed"]["slot"]
                    args.append(((slot >= 0)
                                 & masks[mask][np.maximum(slot, 0)]
                                 ).astype(np.int32))
                elif key in snap["dev"]:
                    args.append(snap["dev"][key])
                elif key.startswith("packed."):
                    args.append(snap["packed"][key[len("packed."):]])
                else:
                    args.append(snap[key])
            out.append((calls[prog], args))
        return out

    # -- misc --------------------------------------------------------------

    def get_chunk(self, chunk_id: int) -> Optional[Chunk]:
        with self._mu:
            return self.chunks.get(int(chunk_id))

    def get_chunks(self, chunk_ids: Sequence[int]) -> List[Optional[Chunk]]:
        """Batched payload lookup: one call for a whole candidate set."""
        with self._mu:
            return [self.chunks.get(int(c)) for c in chunk_ids]

    def stats(self) -> Dict[str, float]:
        with self._mu:
            return self._stats_locked()

    def _stats_locked(self) -> Dict[str, float]:  # locked-by: _mu
        cfg = self.cfg
        vec_bytes = self.n_slots * cfg.dim * 4
        index_bytes = 0
        if self.centroids is not None:
            index_bytes += self.centroids.nbytes + self.buckets.nbytes
        if self.sq_codes is not None:
            index_bytes += self.n_slots * cfg.dim
        if self.pq_codes is not None:
            index_bytes += self.n_slots * cfg.pq_m + self.pq_codebook.nbytes
        return {
            "live": float(self.live.sum()),
            "slots": float(self.n_slots),
            "vector_bytes": float(vec_bytes),
            "index_bytes": float(index_bytes),
            "fresh": float((self.live & ~self.indexed).sum()),
            **self.counters,
        }


@register("vectordb", "jax")
def make_db(index_type: str = "ivf", quant: str = "none", dim: int = 384,
            **kw) -> JaxVectorDB:
    return JaxVectorDB(DBConfig(index_type=index_type, quant=quant, dim=dim,
                                **kw))


@register("vectordb", "fused")
def make_fused_db(index_type: str = "ivf", quant: str = "none",
                  dim: int = 384, **kw) -> JaxVectorDB:
    """``vectordb:jax`` pinned to the fused retrieve backend.

    Spec-selectable shorthand for ``{"component": "jax", "options":
    {"use_kernel": "fused"}}`` — one coalesced retrieve micro-batch is one
    kernel launch (``repro.kernels.fused_retrieve``).
    """
    kw.setdefault("use_kernel", "fused")
    if kernel_ladder(kw["use_kernel"]) != "fused":
        raise ValueError(
            f"vectordb:fused requires use_kernel='fused', got "
            f"{kw['use_kernel']!r}")
    return JaxVectorDB(DBConfig(index_type=index_type, quant=quant, dim=dim,
                                **kw))
