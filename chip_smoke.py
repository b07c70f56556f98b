"""Chip smoke: drive the served RAG path once on a TPU, at full width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded corpus over four chips

One chip, in one process, with one persistent compilation cache:

1. Device gate: fails unless JAX's first device is a TPU, and unless the
   retrieve kernels run compiled (``REPRO_KERNEL_MODE`` unset or ``pallas``).
2. Retrieval at real widths: a seeded clustered corpus (2^18 x 768 f32)
   inserted straight into ``JaxVectorDB``; 64 queries, k=10, searched on
   every rung the chip runs (XLA ``off``; flat ``op`` and ``fused``;
   ``fused`` flat/sq8, ivf and ivf/pq).  Each rung must meet the cross-mode
   contract (``repro.kernels.ref.topk_mismatch``) against the ``off`` rung
   of its index; recall@10 against an exact f32 brute force is printed.
3. The served path: ``repro.launch.serve.main`` on
   ``examples/specs/chip_phi4_mini.json`` (phi4-mini-3.8B at its published
   widths, random weights from a seed, continuous-batching engine), 16
   open-loop queries.  Fails unless all 16 completed, none failed, every
   request generated ``max_new`` tokens and TTFT/TPOT are finite.

``--four-chips`` runs only the sharded phase: ``repro.launch.serve.main`` on
``examples/specs/chip_sharded_flat.json`` (a four-shard flat corpus, which
the ``sharded`` factory lays over the four devices on a ``("data",)`` mesh;
the harness's thread searches it), then a 2^20 x 768 corpus from the same
factory, searched directly and from a worker thread.  Fails unless every
search took the mesh path, each device holds a quarter of the stacked
rows, and the ids equal a one-device exact reference.

The last line of output is ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before it.  Timings printed on the way name the device
they ran on and are readings of this run, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# The device this script is for; a test steers it to "cpu" to rehearse the
# script at a tiny size (together with the sizes below).
PLATFORM = "tpu"
RETRIEVE = dict(n=1 << 18, d=768, clusters=1024, nq=64, k=10, nlist=1024,
                nprobe=16, pq_m=8, seed=0)
SERVE_SPEC = ROOT / "examples" / "specs" / "chip_phi4_mini.json"
SERVE_REQUESTS = 16
FOUR = dict(n=1 << 20, d=768, clusters=1024, nq=64, k=10, requests=16,
            seed=0)
FOUR_SPEC = ROOT / "examples" / "specs" / "chip_sharded_flat.json"
OUT_DIR = ROOT / "chiprun_out"

# (index_type, quant) -> the rungs compared against that index's "off" rung
RUNGS = [
    ("flat", "none", ("op", "fused")),
    ("flat", "sq8", ("fused",)),
    ("ivf", "none", ("fused",)),
    ("ivf", "pq", ("fused",)),
]


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_gate(n_devices: int):
    """Fail unless the run is on the intended accelerator, compiled."""
    mode = os.environ.get("REPRO_KERNEL_MODE")
    check(mode in (None, "", "pallas"),
          f"REPRO_KERNEL_MODE={mode!r}: the chip path runs the compiled "
          f"kernels only (unset it or set it to 'pallas')")
    import jax
    devs = jax.devices()
    check(devs[0].platform == PLATFORM,
          f"no {PLATFORM.upper()} found: JAX's first device is "
          f"{devs[0].platform!r} ({devs[0].device_kind})")
    check(len(devs) >= n_devices,
          f"needs {n_devices} devices, found {len(devs)}")
    print(f"jax {jax.__version__}; device_kind {devs[0].device_kind!r}; "
          f"{len(devs)} device(s) on platform {devs[0].platform!r}",
          flush=True)
    return devs


def corpus(n: int, d: int, clusters: int, nq: int, seed: int):
    """Seeded unit-norm clustered vectors and queries near corpus rows,
    generated on the device in one program; returned as host arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def make(key):
        kc, ka, kx, kq, kn = jax.random.split(key, 5)
        centers = jax.random.normal(kc, (clusters, d))
        x = centers[jax.random.randint(ka, (n,), 0, clusters)]
        x = x + jax.random.normal(kx, (n, d))
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        q = x[jax.random.randint(kq, (nq,), 0, n)]
        q = q + 0.05 * jax.random.normal(kn, (nq, d))
        return x, q / jnp.linalg.norm(q, axis=1, keepdims=True)

    x, q = make(jax.random.PRNGKey(seed))
    return np.asarray(x), np.asarray(q)


def exact_topk(x, q, k: int, device=None):
    """Exact f32 brute force (precision HIGHEST: a TPU's default f32 dot
    rounds its operands to bf16)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    xd, qd = jax.device_put(x, device), jax.device_put(q, device)
    s, i = jax.lax.top_k(jnp.dot(qd, xd.T, precision=jax.lax.Precision.HIGHEST),
                         k)
    return np.asarray(s), np.asarray(i)


def print_peak(devs, when: str) -> None:
    """The process's device-memory high-water mark so far."""
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[{devs[0].device_kind}] peak_bytes_in_use {when}: "
          f"{peak if peak is not None else 'not reported'}", flush=True)


def recall(ids, ref_ids) -> float:
    return sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(ids, ref_ids)) / ref_ids.size


def search(db, q, k: int):
    import numpy as np
    res = db.search(q, k)
    return (np.stack([r.scores for r in res]),
            np.stack([r.chunk_ids for r in res]))


def retrieval_phase(devs) -> None:
    import gc

    from repro.core.interfaces import Chunk
    from repro.core.vectordb import DBConfig, JaxVectorDB
    from repro.kernels import ops as kops
    from repro.kernels.ref import topk_mismatch

    p = RETRIEVE
    if PLATFORM == "tpu":
        check(kops.kernel_mode() == "pallas",
              f"kernel mode {kops.kernel_mode()!r} on a TPU")
    t0 = time.perf_counter()
    x, q = corpus(p["n"], p["d"], p["clusters"], p["nq"], p["seed"])
    _, ref_i = exact_topk(x, q, p["k"], devs[0])
    chunks = [Chunk(chunk_id=-1, doc_id=i, text="") for i in range(p["n"])]
    print(f"retrieval corpus {p['n']} x {p['d']} f32, {p['nq']} queries, "
          f"k={p['k']} (set-up {time.perf_counter() - t0:.1f} s)", flush=True)
    for index_type, quant, rungs in RUNGS:
        base = None
        for rung in ("off",) + rungs:
            t0 = time.perf_counter()
            db = JaxVectorDB(DBConfig(
                index_type=index_type, quant=quant, dim=p["d"],
                capacity=p["n"], nlist=p["nlist"], nprobe=p["nprobe"],
                pq_m=p["pq_m"], use_kernel=rung))
            db.insert(x, chunks)
            db.build_index()
            t_build = time.perf_counter() - t0
            t0 = time.perf_counter()
            s, i = search(db, q, p["k"])
            t_first = time.perf_counter() - t0
            t0 = time.perf_counter()
            s, i = search(db, q, p["k"])
            t_warm = time.perf_counter() - t0
            name = f"{index_type}/{quant}/{rung}"
            print(f"  {name:18s} recall@{p['k']} {recall(i, ref_i):.4f}  "
                  f"build {t_build:.1f} s, first search {t_first:.2f} s, "
                  f"warm search {t_warm * 1e3:.1f} ms "
                  f"[{devs[0].device_kind}]", flush=True)
            if rung == "fused":
                check(db.counters["fused_searches"] == 2 * p["nq"],
                      f"{name}: fused kernel not used")
            if base is None:
                base = (s, i)
            else:
                bad = topk_mismatch(base[0], base[1], s, i)
                check(bad is None, f"{name} differs from the off rung: {bad}")
            del db
            gc.collect()
    print_peak(devs, "after retrieval")


def serve_phase(devs) -> None:
    from repro.core.spec import PipelineSpec
    from repro.launch import serve

    OUT_DIR.mkdir(exist_ok=True)
    out = OUT_DIR / "chip_smoke_serve.json"
    spec = PipelineSpec.from_file(str(SERVE_SPEC))
    max_new = int(spec.llm.options["max_new"])
    print(f"serving {SERVE_REQUESTS} queries through repro.launch.serve "
          f"({SERVE_SPEC.name}: {spec.llm.options.get('arch')}, "
          f"max_new {max_new})", flush=True)
    t0 = time.perf_counter()
    rc = serve.main(["--config", str(SERVE_SPEC), "--mode", "open",
                     "--requests", str(SERVE_REQUESTS), "--update-frac", "0",
                     "--json-out", str(out)])
    print(f"serve.main returned {rc} after {time.perf_counter() - t0:.1f} s "
          f"(compilation included)", flush=True)
    check(rc == 0, f"serve.main exited {rc}")
    doc = json.loads(out.read_text())
    s, gen = doc["summary"], doc["gen"]
    check(int(s["n_queries"]) == SERVE_REQUESTS and int(s["n_failed"]) == 0,
          f"{int(s['n_queries'])} of {SERVE_REQUESTS} queries completed, "
          f"{int(s['n_failed'])} failed")
    check(int(gen["tokens_out"]) == SERVE_REQUESTS * max_new,
          f"tokens_out {gen['tokens_out']} != {SERVE_REQUESTS} x {max_new}")
    for key in ("ttft_p50_s", "tpot_p50_s"):
        check(math.isfinite(gen[key]) and gen[key] > 0, f"{key}={gen[key]}")
    print_peak(devs, "after serving")
    kind = devs[0].device_kind
    print(f"[{kind}] TTFT p50 {gen['ttft_p50_s'] * 1e3:.1f} ms, TPOT p50 "
          f"{gen['tpot_p50_s'] * 1e3:.2f} ms, query latency p50 "
          f"{s['p50_latency_ms']:.1f} ms over {SERVE_REQUESTS} queries",
          flush=True)


def four_chip_phase(devs) -> None:
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from repro.core import registry
    from repro.core.interfaces import Chunk
    from repro.kernels.ref import topk_mismatch
    from repro.launch import serve

    p = FOUR
    # the served path: serve.main builds the sharded database from the
    # spec, whose factory lays it over the devices; the harness's executor
    # thread searches it
    OUT_DIR.mkdir(exist_ok=True)
    out = OUT_DIR / "chip_smoke_four_serve.json"
    t0 = time.perf_counter()
    rc = serve.main(["--config", str(FOUR_SPEC), "--mode", "open",
                     "--requests", str(p["requests"]), "--update-frac", "0",
                     "--json-out", str(out)])
    check(rc == 0, f"serve.main exited {rc}")
    doc = json.loads(out.read_text())
    s, st = doc["summary"], doc["db"]
    print(f"served {int(s['n_queries'])} queries through repro.launch.serve "
          f"({FOUR_SPEC.name}) in {time.perf_counter() - t0:.1f} s, "
          f"{int(s['n_failed'])} failed; mesh_searches "
          f"{int(st['mesh_searches'])} of {int(st['searches'])} searches",
          flush=True)
    check(int(s["n_queries"]) == p["requests"] and int(s["n_failed"]) == 0,
          "served queries failed")
    check(st["mesh_searches"] == st["searches"] > 0,
          "a served search left the mesh path")

    # directly, at scale, through the same factory: from this thread and
    # from a worker thread
    t0 = time.perf_counter()
    x, q = corpus(p["n"], p["d"], p["clusters"], p["nq"], p["seed"])
    ref_s, ref_i = exact_topk(x, q, p["k"], devs[0])
    db = registry.create("vectordb", "sharded", n_shards=4,
                         index_type="flat", quant="none", dim=p["d"],
                         capacity=p["n"] + 8192)
    check(db.mesh is not None, "the sharded factory made no device mesh")
    db.insert(x, [Chunk(chunk_id=-1, doc_id=i, text="")
                  for i in range(p["n"])])
    print(f"sharded corpus {p['n']} x {p['d']} f32 over mesh "
          f"{dict(db.mesh.shape)} (set-up {time.perf_counter() - t0:.1f} s)",
          flush=True)
    got_s, got_i = search(db, q, p["k"])
    with ThreadPoolExecutor(1) as pool:
        w_s, w_i = pool.submit(search, db, q, p["k"]).result()
    check((w_i == got_i).all(), "worker-thread search differs")
    c = db.counters
    print(f"mesh_searches {int(c['mesh_searches'])}, searches "
          f"{int(c['searches'])}", flush=True)
    check(c["mesh_searches"] == c["searches"] == 2 * p["nq"],
          "a search left the mesh path")
    _, stacked, _ = db._mesh_arrays
    rows = {str(sh.device): sh.data.shape[0]
            for sh in stacked.addressable_shards}
    live = [int(sh.stats()["live"]) for sh in db.shards]
    print(f"stacked rows per device {rows} of {stacked.shape[0]}; live rows "
          f"per shard {live}", flush=True)
    check(len(rows) == 4 and set(rows.values()) == {stacked.shape[0] // 4},
          "stacked corpus is not a quarter per device")
    rows_of = np.vectorize(lambda g: db.get_chunk(g).doc_id)
    ids = np.where(got_i >= 0, rows_of(np.maximum(got_i, 0)), -1)
    print(f"recall@{p['k']} vs one-device exact {recall(ids, ref_i):.4f}",
          flush=True)
    bad = topk_mismatch(ref_s, ref_i, got_s, ids)
    check(bad is None, f"sharded ids differ from the exact reference: {bad}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-corpus phase on four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: FAIL: the repository's src/repro is not next to "
              f"{Path(__file__).name}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devs = device_gate(4 if args.four_chips else 1)
        from repro.launch.compile_cache import enable_compile_cache
        print(f"compilation cache: {enable_compile_cache()}", flush=True)
        t0 = time.perf_counter()
        if args.four_chips:
            four_chip_phase(devs)
        else:
            retrieval_phase(devs)
            serve_phase(devs)
        print(f"all phases passed in {time.perf_counter() - t0:.1f} s",
              flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
