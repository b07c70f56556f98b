"""Fused retrieve backend gate: exact parity, roofline bytes, latency.

Three halves, one claim (ROADMAP item 2 / RAGO's stage-fusion argument —
the retrieve hot path should move only the bytes the search fundamentally
requires):

* **Equivalence** — the ``use_kernel="fused"`` backend must meet the
  cross-mode contract (``repro.kernels.ref.topk_mismatch``: equal ids,
  scores equal up to f32 reduction order) against the reference ladder on every
  index_type×quant config, both freshly built and after mutations
  (tombstones + fresh inserts in the hybrid buffer), under both
  ``REPRO_KERNEL_MODE=interpret`` (Pallas kernels) and ``=xla`` (scan
  fallbacks).
* **Roofline** — ``repro.roofline.retrieve``'s byte model: the fused path
  must move strictly fewer HBM bytes than the unfused path and sit
  strictly closer to the bandwidth bound (``bound_fraction``) on every
  ladder config at serving scale.
* **Latency** — the micro-batch retrieve primitives timed head-to-head in
  ``xla`` mode (the fallbacks implement the same tiled algorithm the TPU
  kernel runs, so the CPU timing reflects the smaller working set): the
  fused sq8 scan and fused PQ probe must beat their unfused references.

``--check`` asserts all three (the tier-1 gate); ``--smoke`` shrinks the
corpora for CI.
"""
from __future__ import annotations

import argparse
import os
import time
from contextlib import contextmanager
from typing import Dict, List

import numpy as np

from benchmarks.common import emit

MIN_SPEEDUP = 1.05        # fused must beat unfused by at least this in xla


@contextmanager
def _kernel_mode(mode: str):
    prev = os.environ.get("REPRO_KERNEL_MODE")
    os.environ["REPRO_KERNEL_MODE"] = mode
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("REPRO_KERNEL_MODE", None)
        else:
            os.environ["REPRO_KERNEL_MODE"] = prev


def _corpus(n: int, dim: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    q = vecs[:: max(1, n // 16)][:12].copy()
    q += 0.02 * rng.standard_normal(q.shape).astype(np.float32)
    return vecs, q


CONFIGS = [("flat", "none"), ("flat", "sq8"), ("flat", "pq"),
           ("ivf", "none"), ("ivf", "sq8"), ("ivf", "pq")]


def equivalence(n: int = 512, dim: int = 32, k: int = 8) -> List[Dict]:
    """Fused vs reference ladder, pre and post mutation."""
    import jax.numpy as jnp

    from repro.core.interfaces import Chunk
    from repro.core.vectordb import DBConfig, JaxVectorDB
    from repro.kernels.ref import topk_mismatch

    vecs, q = _corpus(n, dim)
    qj = jnp.asarray(q)
    rng = np.random.default_rng(7)
    fresh = rng.standard_normal((12, dim)).astype(np.float32)
    rows: List[Dict] = []

    def mk(index_type, quant, use_kernel):
        db = JaxVectorDB(DBConfig(
            index_type=index_type, quant=quant, dim=dim,
            capacity=n + 64, nlist=8, nprobe=4, flat_capacity=64, pq_m=4,
            use_kernel=use_kernel))
        db.insert(vecs.copy(),
                  [Chunk(chunk_id=-1, doc_id=i // 4, text=f"c{i}")
                   for i in range(n)])
        db.build_index()
        return db

    for mode in ("interpret", "xla"):
        with _kernel_mode(mode):
            for index_type, quant in CONFIGS:
                ref = mk(index_type, quant, False)
                fus = mk(index_type, quant, "fused")
                exact = {}
                for phase in ("built", "mutated"):
                    if phase == "mutated":
                        for db in (ref, fus):
                            db.remove(1)          # tombstones
                            db.remove(17)
                            db.insert(
                                fresh.copy(),
                                [Chunk(chunk_id=-1, doc_id=9000 + i,
                                       text=f"f{i}")
                                 for i in range(len(fresh))])
                    sa, ia = ref._search_arrays(qj, k)
                    sb, ib = fus._search_arrays(qj, k)
                    exact[phase] = float(
                        (np.asarray(ia) == np.asarray(ib)).all()
                        and topk_mismatch(sa, ia, sb, ib) is None)
                rows.append({
                    "bench": (f"fused_retrieve/equiv_{mode}_"
                              f"{index_type}_{quant}"),
                    "mode": mode, "index_type": index_type, "quant": quant,
                    "exact_built": exact["built"],
                    "exact_mutated": exact["mutated"],
                })
    return rows


# serving-scale micro-batch shapes for the roofline byte model
ROOFLINE_SHAPES = [
    ("flat", "none", dict(nq=64, n=1 << 17, d=256, k=16)),
    ("flat", "sq8", dict(nq=64, n=1 << 17, d=256, k=16)),
    ("ivf", "none", dict(nq=64, n=1 << 20, d=256, k=16, nlist=256,
                         nprobe=16)),
    ("ivf", "pq", dict(nq=64, n=1 << 20, d=256, k=16, nlist=256,
                       nprobe=16, pq_m=8)),
]


def roofline_rows() -> List[Dict]:
    """The analytic HBM-bytes comparison (no hardware needed)."""
    from repro.roofline.retrieve import RetrieveShape, roofline

    rows: List[Dict] = []
    for index_type, quant, kw in ROOFLINE_SHAPES:
        r = roofline(RetrieveShape(index_type=index_type, quant=quant, **kw))
        rows.append({
            "bench": f"fused_retrieve/roofline_{index_type}_{quant}",
            "index_type": index_type, "quant": quant,
            "bound_bytes": r["bound_bytes"],
            "fused_bytes": r["fused_bytes"],
            "unfused_bytes": r["unfused_bytes"],
            "fused_bound_fraction": r["fused_bound_fraction"],
            "unfused_bound_fraction": r["unfused_bound_fraction"],
            "bytes_saved_ratio": r["unfused_bytes"] / r["fused_bytes"],
        })
    return rows


def _time(fn, *args, reps: int = 3) -> float:
    fn(*args)[0].block_until_ready()          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args)[0].block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def latency(smoke: bool = False) -> List[Dict]:
    """Head-to-head micro-batch timing of the two ladders in xla mode."""
    import jax.numpy as jnp

    from repro.core.vectordb import _pq_ivf_search, _sq8_flat_search
    from repro.kernels import ops as kops

    rng = np.random.default_rng(0)
    rows: List[Dict] = []
    with _kernel_mode("xla"):
        # -- sq8 flat micro-batch ------------------------------------------
        nq, n, d, k = (32, 1 << 15, 256, 16) if smoke \
            else (64, 1 << 17, 256, 16)
        q = jnp.asarray(rng.standard_normal((nq, d)), jnp.float32)
        codes = jnp.asarray(rng.integers(-127, 128, (n, d)), jnp.int8)
        scale = jnp.asarray(rng.random(d) + 0.5, jnp.float32)
        live = jnp.asarray(rng.random(n) < 0.95)
        t_un = _time(lambda: _sq8_flat_search(q, codes, scale, live, k,
                                              "off", "xla"))
        t_fu = _time(lambda: _sq8_flat_search(q, codes, scale, live, k,
                                              "fused", "xla"))
        rows.append({
            "bench": "fused_retrieve/latency_sq8",
            "nq": nq, "n": n, "d": d, "k": k,
            "unfused_ms": t_un * 1e3, "fused_ms": t_fu * 1e3,
            "speedup": t_un / t_fu,
        })
        # -- pq ivf micro-batch --------------------------------------------
        nq, d, k, m = (32, 256, 16, 8) if smoke else (64, 256, 16, 8)
        nlist, cap_b, nprobe = (32, 1024, 8) if smoke else (64, 4096, 16)
        q = jnp.asarray(rng.standard_normal((nq, d)), jnp.float32)
        cent = jnp.asarray(rng.standard_normal((nlist, d)), jnp.float32)
        codebook = jnp.asarray(
            rng.standard_normal((m, 256, d // m)), jnp.float32)
        pcodes = jnp.asarray(
            rng.integers(0, 256, (nlist * cap_b, m)), jnp.int32)
        # the packed mirror's layout: codes subspace-major, [1, rows] rows
        pcodes_sm = pcodes.T
        pslot = jnp.asarray(np.arange(nlist * cap_b, dtype=np.int32)[None])
        pok = jnp.asarray(
            (rng.random((1, nlist * cap_b)) < 0.95).astype(np.int32))
        # unfused reference over the identical layout (buckets == packed
        # rows, so both paths score exactly the same candidates)
        buckets = jnp.asarray(
            np.arange(nlist * cap_b, dtype=np.int32).reshape(nlist, cap_b))
        t_un = _time(lambda: _pq_ivf_search(
            q, pcodes, codebook, pok[0].astype(bool), cent, buckets,
            buckets >= 0, nprobe, k))
        t_fu = _time(lambda: kops.fused_pq_topk(
            q, codebook, cent, pcodes_sm, pslot, pok, nprobe, k,
            mode="xla"))
        rows.append({
            "bench": "fused_retrieve/latency_pq",
            "nq": nq, "nlist": nlist, "cap_b": cap_b, "nprobe": nprobe,
            "unfused_ms": t_un * 1e3, "fused_ms": t_fu * 1e3,
            "speedup": t_un / t_fu,
        })
    return rows


def run(scale: float = 1.0) -> List[Dict]:
    """benchmarks.run entry point."""
    n = max(256, int(512 * scale))
    return equivalence(n=n) + roofline_rows() + latency(smoke=scale < 1.0)


def check(rows: List[Dict]) -> List[str]:
    """The acceptance assertions over a finished sweep's rows."""
    errs: List[str] = []
    for r in rows:
        b = r["bench"]
        if "/equiv_" in b:
            if r["exact_built"] != 1.0:
                errs.append(f"{b}: fused != reference on fresh index")
            if r["exact_mutated"] != 1.0:
                errs.append(f"{b}: fused != reference after mutations")
        elif "/roofline_" in b:
            if not r["fused_bytes"] < r["unfused_bytes"]:
                errs.append(f"{b}: fused moves {r['fused_bytes']:.3g}B, not "
                            f"less than unfused {r['unfused_bytes']:.3g}B")
            if not (r["fused_bound_fraction"]
                    > r["unfused_bound_fraction"]):
                errs.append(f"{b}: fused bound_fraction "
                            f"{r['fused_bound_fraction']:.3f} does not beat "
                            f"unfused {r['unfused_bound_fraction']:.3f}")
        elif "/latency_" in b:
            if r["speedup"] < MIN_SPEEDUP:
                errs.append(f"{b}: speedup {r['speedup']:.2f}x below "
                            f"{MIN_SPEEDUP}x")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized corpora and micro-batches")
    ap.add_argument("--check", action="store_true",
                    help="assert parity + roofline + latency criteria")
    args = ap.parse_args(argv)
    if args.smoke:
        rows = (equivalence(n=384) + roofline_rows() + latency(smoke=True))
    else:
        rows = run(args.scale)
    emit([dict(r) for r in rows])
    if args.check:
        errs = check(rows)
        if errs:
            print("CHECK FAILED:", "; ".join(errs))
            return 1
        print("CHECK OK: fused backend matches the reference on all "
              f"{len(CONFIGS)} configs x 2 modes (incl. post-mutation), "
              "HBM bytes strictly closer to the bandwidth bound, "
              f"micro-batch speedup >= {MIN_SPEEDUP}x (sq8 + pq)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
