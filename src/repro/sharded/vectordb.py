"""Row-partitioned vector DB over N shards (ROADMAP item 1).

``ShardedVectorDB`` implements the same ``DBInstance`` abstraction as
``JaxVectorDB`` and registers as the ``sharded`` vectordb backend, so any
``PipelineSpec`` selects it (and its shard count) declaratively::

    "vectordb": {"component": "sharded",
                 "options": {"n_shards": 4, "index_type": "ivf"}}

Design
------
- **Partitioning** — the corpus is row-partitioned into ``n_shards``
  independent ``JaxVectorDB`` instances (flat and IVF, incl. sq8/pq quant).
  Documents route to shards by a deterministic hash of ``doc_id``
  (``doc_shard``), so every chunk of a document lands on one shard and
  removals/updates find it again without a global id map.
- **Global ids** — ``global_id = shard * shard_capacity + local_slot``.
  The stride matches ``make_sharded_topk``'s id arithmetic, and at
  ``n_shards=1`` global ids equal local slots, making the single-shard
  configuration output-identical to a bare ``JaxVectorDB``.
- **Search** — each shard computes a local top-k against a *consistent
  cross-shard snapshot* (all shard snapshots taken under one wrapper lock),
  then lists fold pairwise through ``merge_topk`` — only O(shards·k)
  winners cross shard boundaries, never full score matrices.  A database
  given a JAX mesh (a plain flat scan whose ``corpus`` axes hold exactly
  ``n_shards`` devices; anything else raises at construction) instead runs
  the fused ``make_sharded_topk`` shard_map path over one
  ``[n_shards·cap, d]`` array placed row-sharded on the mesh (one shard
  per device).  The mesh is an attribute of the database, not of the
  calling thread, so serving worker threads take the mesh path too.  The
  registered ``sharded`` factory builds that mesh itself when the shard
  count equals the device count.
- **Mutations** — the elastic executor's serialized writer calls
  ``insert``/``remove``/``update`` here; the wrapper groups the batch by
  target shard and applies groups shard-parallel (shards are independent,
  each with its own lock).  Rebuild thresholds are per shard: a hot shard
  folds its freshness buffer without stalling the others.
- **Knobs** — ``set_nprobe`` updates every shard under the same lock that
  search snapshots under, so the autoscale ladder can never be observed
  half-applied across shards.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Mapping, Optional, \
    Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.interfaces import Chunk, DBInstance, SearchResult
from repro.core.registry import register
from repro.core.vectordb import DBConfig, JaxVectorDB, NEG, merge_topk
from repro.distributed.collectives import make_sharded_topk
from repro.launch.mesh import make_mesh


def doc_shard(doc_id: int, n_shards: int) -> int:
    """Deterministic doc→shard assignment (murmur-style integer mix, so
    sequential doc ids spread instead of striping)."""
    if n_shards <= 1:
        return 0
    x = (int(doc_id) ^ 0x9E3779B9) & 0xFFFFFFFF
    x = (x * 0x85EBCA6B) & 0xFFFFFFFF
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & 0xFFFFFFFF
    x ^= x >> 16
    return x % n_shards


@dataclass
class ShardedDBConfig:
    """Global-view config; per-shard ``DBConfig`` values are derived."""

    n_shards: int = 4
    index_type: str = "ivf"          # flat | ivf
    quant: str = "none"              # none | sq8 | pq
    dim: int = 384
    capacity: int = 1 << 16          # global row budget
    nlist: int = 64                  # global IVF lists (split across shards)
    nprobe: int = 8
    use_hybrid: bool = True
    flat_capacity: int = 4096        # global freshness budget (split)
    rebuild_threshold: float = 0.75
    # kernel ladder rung, passed through to every shard's DBConfig:
    # False/"off" | True/"op" | "fused".  The fused retrieve backend
    # composes with the per-shard scan for free — each shard's
    # ``_search_arrays`` dispatches its own fused probe over its own
    # packed mirror, and the O(shards·k) merge is unchanged.
    use_kernel: object = False
    train_sample: int = 16384
    balance_slack: float = 1.5       # per-shard headroom over an even split
    corpus_axes: Tuple[str, ...] = ("pod", "data")


class _DocSlotsView(Mapping):
    """Read-only ``doc_id -> [global chunk ids]`` view over all shards
    (keeps ``gold_chunks_for`` and other ``db.doc_slots`` users working)."""

    def __init__(self, db: "ShardedVectorDB"):
        self._db = db

    def __getitem__(self, doc_id: int) -> List[int]:
        sid = doc_shard(doc_id, self._db.cfg.n_shards)
        slots = self._db.shards[sid].doc_slots[doc_id]
        return [sid * self._db.shard_capacity + int(s) for s in slots]

    def __iter__(self) -> Iterator[int]:
        for sh in self._db.shards:
            yield from sh.doc_slots

    def __len__(self) -> int:
        return sum(len(sh.doc_slots) for sh in self._db.shards)

    def __contains__(self, doc_id) -> bool:
        sid = doc_shard(doc_id, self._db.cfg.n_shards)
        return doc_id in self._db.shards[sid].doc_slots


class ShardedVectorDB(DBInstance):
    """N-way row-partitioned vector DB with O(shards·k) merge reduction."""

    def __init__(self, cfg: ShardedDBConfig, mesh: Optional[Mesh] = None):
        assert cfg.n_shards >= 1, cfg.n_shards
        self.cfg = cfg
        self.mesh = mesh               # device mesh for the shard_map scan
        self._mesh_axes: Tuple[str, ...] = ()
        if mesh is not None:
            self._mesh_axes = tuple(a for a in cfg.corpus_axes
                                    if a in mesh.shape)
            size = int(np.prod([mesh.shape[a] for a in self._mesh_axes]))
            if (cfg.index_type, cfg.quant) != ("flat", "none") or (
                    not self._mesh_axes or size != cfg.n_shards):
                raise ValueError(
                    f"the mesh path scans a flat/none corpus with one shard "
                    f"per device of the corpus axes {cfg.corpus_axes}; got "
                    f"{cfg.index_type}/{cfg.quant}, {cfg.n_shards} shards, "
                    f"mesh {dict(mesh.shape)}")
        self._mu = threading.RLock()   # cross-shard snapshot/mutation fence
        self.shards: List[JaxVectorDB] = [
            JaxVectorDB(self._shard_cfg()) for _ in range(cfg.n_shards)]
        self.shard_capacity = self.shards[0].cfg.capacity
        self.doc_slots = _DocSlotsView(self)
        self.counters: Dict[str, float] = {   # guarded-by: _mu
            "searches": 0, "search_time_s": 0.0, "mesh_searches": 0,
            "merge_time_s": 0.0,
        }
        self._epoch = 0                # guarded-by: _mu
        # fused-path caches: jitted shard_map fn per k + stacked device
        # arrays valid for one mutation epoch
        self._mesh_fns: Dict[int, Callable] = {}  # guarded-by: _mu
        self._mesh_arrays: Optional[Tuple[int, object, object]] = None   # guarded-by: _mu
        # optional obs.Tracer: fan-out/merge spans on the "db" thread lane
        self.tracer = None

    def _shard_cfg(self) -> DBConfig:
        """Derive one shard's ``DBConfig`` from the global view.

        At ``n_shards=1`` every value passes through unchanged (the parity
        guarantee); otherwise capacities/lists split proportionally with
        ``balance_slack`` headroom absorbing hash-routing imbalance.
        """
        c = self.cfg
        n = c.n_shards
        if n == 1:
            cap, nlist, flat = c.capacity, c.nlist, c.flat_capacity
        else:
            cap = min(c.capacity,
                      int(np.ceil(c.capacity / n * c.balance_slack)))
            nlist = max(4, c.nlist // n)
            flat = max(16, int(np.ceil(c.flat_capacity / n)))
        return DBConfig(index_type=c.index_type, quant=c.quant, dim=c.dim,
                        capacity=cap, nlist=nlist, nprobe=c.nprobe,
                        flat_capacity=flat,
                        rebuild_threshold=c.rebuild_threshold,
                        use_hybrid=c.use_hybrid, use_kernel=c.use_kernel,
                        train_sample=c.train_sample)

    # -- id codec ----------------------------------------------------------

    def _to_global(self, sid: int, local: int) -> int:
        return sid * self.shard_capacity + int(local)

    def _locate(self, global_id: int) -> Tuple[int, int]:
        return divmod(int(global_id), self.shard_capacity)

    def _parallel(self, fns: List[Callable[[], None]]) -> None:
        """Apply per-shard closures shard-parallel (shards are independent
        databases; each serializes internally on its own lock)."""
        if len(fns) <= 1:
            for fn in fns:
                fn()
            return
        with ThreadPoolExecutor(max_workers=len(fns)) as ex:
            for f in [ex.submit(fn) for fn in fns]:
                f.result()

    # -- writes ------------------------------------------------------------

    def insert(self, vectors: np.ndarray, chunks: Sequence[Chunk]) -> None:
        n = len(chunks)
        assert vectors.shape == (n, self.cfg.dim)
        with self._mu:
            groups: Dict[int, List[int]] = {}
            for j, c in enumerate(chunks):
                groups.setdefault(
                    doc_shard(c.doc_id, self.cfg.n_shards), []).append(j)

            def apply(sid: int, rows: List[int]) -> None:
                sub = [chunks[j] for j in rows]
                self.shards[sid].insert(vectors[rows], sub)
                for c in sub:   # shard assigned local slots; re-key globally
                    c.chunk_id = self._to_global(sid, c.chunk_id)

            self._parallel([lambda s=s, r=r: apply(s, r)
                            for s, r in groups.items()])
            self._epoch += 1

    def remove(self, doc_id: int) -> int:
        with self._mu:
            sid = doc_shard(doc_id, self.cfg.n_shards)
            n = self.shards[sid].remove(doc_id)
            if n:
                self._epoch += 1
            return n

    def update(self, doc_id: int, vectors: np.ndarray,
               chunks: Sequence[Chunk]) -> None:
        with self._mu:
            self.remove(doc_id)
            self.insert(vectors, chunks)

    def set_nprobe(self, nprobe: int) -> None:
        """Quality-knob update, atomic across shards: holds the same lock
        search snapshots under, so one search never mixes nprobe levels."""
        with self._mu:
            for sh in self.shards:
                sh.set_nprobe(nprobe)
            self.cfg.nprobe = max(1, int(nprobe))

    def build_index(self) -> None:
        with self._mu:
            self._parallel([sh.build_index for sh in self.shards])
            self._epoch += 1

    # -- search ------------------------------------------------------------

    def search(self, vectors: np.ndarray, k: int) -> List[SearchResult]:
        t0 = time.perf_counter()
        q = jnp.asarray(vectors, jnp.float32)
        with self._mu:   # consistent cross-shard snapshot; the mesh path
            # places host rows itself, so its shards keep no device copies
            snaps = [sh._snapshot(resident=self.mesh is None)
                     for sh in self.shards]
            epoch = self._epoch
        out = self._mesh_search(q, k, snaps, epoch)
        if out is None:
            out = self._merge_search(q, k, snaps)
        scores, idx = out
        dt = time.perf_counter() - t0
        with self._mu:
            self.counters["searches"] += len(vectors)
            self.counters["search_time_s"] += dt
        tr = self.tracer
        if tr is not None:
            te = tr.now()
            tr.add_span("db.search", te - dt, te, cat="db", tid="db",
                        n=len(vectors), k=k, shards=self.cfg.n_shards)
        return [SearchResult(chunk_ids=np.asarray(idx[i]),
                             scores=np.asarray(scores[i]))
                for i in range(len(vectors))]

    def _merge_search(self, q, k: int, snaps) -> Tuple[np.ndarray, np.ndarray]:
        """Per-shard local top-k → global ids → pairwise merge reduction."""
        tr = self.tracer
        per: List[Tuple[np.ndarray, np.ndarray]] = []
        for sid, (sh, snap) in enumerate(zip(self.shards, snaps)):
            kl = min(k, sh.cfg.capacity)
            ts = time.perf_counter()
            s, i = sh._search_arrays(q, kl, snap)
            if tr is not None:
                dts = time.perf_counter() - ts
                te = tr.now()
                tr.add_span("db.shard_scan", te - dts, te, cat="db",
                            tid="db", shard=sid)
            s, i = np.asarray(s), np.asarray(i)
            # flat scans keep dead-slot ids at NEG score; mask them out so
            # they never shadow a real winner from another shard
            i = np.where(s <= NEG / 2, -1, i)
            gi = np.where(i >= 0, i + sid * self.shard_capacity, -1)
            if kl < k:   # tiny shard: pad to k so merge shapes line up
                pad = ((0, 0), (0, k - kl))
                s = np.pad(s, pad, constant_values=NEG)
                gi = np.pad(gi, pad, constant_values=-1)
            per.append((s, gi.astype(i.dtype)))
        t0 = time.perf_counter()
        s, gi = per[0]
        for s2, gi2 in per[1:]:   # cross-shard id ranges are disjoint, so
            s, gi = merge_topk(s, gi, s2, gi2, k)   # the vectorized path runs
        dtm = time.perf_counter() - t0
        with self._mu:
            self.counters["merge_time_s"] += dtm
        if tr is not None:
            te = tr.now()
            tr.add_span("db.merge", te - dtm, te, cat="db", tid="db",
                        shards=len(per))
        return s, gi

    def _mesh_search(self, q, k: int, snaps, epoch: int
                     ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Fused shard_map scan over the database's mesh, if it has one.

        The constructor admits a mesh only for the plain flat scan (exact
        over all live rows — hybrid freshness folds in for free since flat
        main + flat buffer together cover exactly ``live``); without a mesh
        every index takes the host-side merge reduction.
        """
        mesh, axes = self.mesh, self._mesh_axes
        if mesh is None:
            return None
        with self._mu:
            if k not in self._mesh_fns:
                self._mesh_fns[k], _ = make_sharded_topk(mesh, k,
                                                         corpus_axes=axes)
            fn = self._mesh_fns[k]
            if self._mesh_arrays is None or self._mesh_arrays[0] != epoch:
                # each shard's rows go straight to its own device
                rows = NamedSharding(mesh, P(axes))
                vecs = jax.device_put(
                    np.concatenate([s["vectors"] for s in snaps], axis=0),
                    rows)
                live = jax.device_put(
                    np.concatenate([s["live"] for s in snaps]), rows)
                self._mesh_arrays = (epoch, vecs, live)
            _, vecs, live = self._mesh_arrays
        # the device computation itself runs lock-free: vecs/live are
        # immutable device arrays pinned to this epoch's snapshot
        s, gi = fn(q, vecs, live)
        with self._mu:
            self.counters["mesh_searches"] += q.shape[0]
        return np.asarray(s), np.asarray(gi)

    # -- payloads / stats --------------------------------------------------

    def get_chunk(self, chunk_id: int) -> Optional[Chunk]:
        cid = int(chunk_id)
        if cid < 0:
            return None
        sid, slot = self._locate(cid)
        if sid >= self.cfg.n_shards:
            return None
        return self.shards[sid].chunks.get(slot)

    def get_chunks(self, chunk_ids: Sequence[int]) -> List[Optional[Chunk]]:
        return [self.get_chunk(c) for c in chunk_ids]

    def shard_stats(self) -> List[Dict[str, float]]:
        """Per-shard stats rows (monitor gauges / dashboards)."""
        return [sh.stats() for sh in self.shards]

    def stats(self) -> Dict[str, float]:
        per = self.shard_stats()
        agg: Dict[str, float] = {}
        for row in per:
            for key, val in row.items():
                agg[key] = agg.get(key, 0.0) + float(val)
        lives = [row["live"] for row in per]
        mean_live = float(np.mean(lives)) if lives else 0.0
        with self._mu:
            agg.update(self.counters)
        agg["n_shards"] = float(self.cfg.n_shards)
        agg["shard_live_min"] = float(min(lives)) if lives else 0.0
        agg["shard_live_max"] = float(max(lives)) if lives else 0.0
        # 1.0 == perfectly balanced; the hash router should stay near it
        agg["shard_imbalance"] = (float(max(lives)) / mean_live
                                  if mean_live > 0 else 1.0)
        return agg

    def gauges(self) -> Dict[str, Callable[[], float]]:
        """Monitor gauges: shard count, balance, fused-path usage."""
        return {
            "db_shards": lambda: float(self.cfg.n_shards),
            "db_shard_imbalance": lambda: self.stats()["shard_imbalance"],
            "db_mesh_searches": lambda: float(
                self.counters["mesh_searches"]),  # noqa: lock-discipline -- monitor-only sample; single dict read is GIL-atomic
        }


@register("vectordb", "sharded")
def make_sharded_db(n_shards: int = 4, index_type: str = "ivf",
                    quant: str = "none", dim: int = 384,
                    **kw) -> ShardedVectorDB:
    """A flat/none corpus with one shard per device is spread over all
    devices on a ``("data",)`` mesh; any other layout merges on the host."""
    cfg = ShardedDBConfig(n_shards=n_shards, index_type=index_type,
                          quant=quant, dim=dim, **kw)
    mesh = None
    if ((index_type, quant) == ("flat", "none") and n_shards > 1
            and n_shards == jax.device_count()
            and "data" in cfg.corpus_axes):
        mesh = make_mesh((n_shards,), ("data",))
    return ShardedVectorDB(cfg, mesh=mesh)
