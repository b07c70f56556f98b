"""Set-up and the measured window of one cell, on the program's served path.

The entry the window drives is the one ``repro.launch.serve.main --mode
open`` wires: a ``RAGPipeline`` from ``repro.core.registry.build(spec)``,
served by ``repro.serving.harness.ServingHarness`` (continuous batcher, then
lock-step ``pipe.query``, then the ``GenEngine`` where a generator runs),
non-elastic.  The benchmark keeps only the load generator: ``_Harness``
replaces the harness's own injection loop with the benchmark's due times
and keeps, per request, when it was due and when it was submitted.

The benchmark's spans (``jax.profiler.TraceAnnotation``, so they share the
device trace's clock) and logs are placed by wrapping objects the built
pipeline holds: each query stage's ``run``, the database's ``search``, the
batcher's ``get_batch`` and the engine's prefill, decode and retire steps.
Nothing in the program is edited.
"""
from __future__ import annotations

import copy
import gc
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import traffic as traffic_lib

Annotation = jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# data made from the seed
# ---------------------------------------------------------------------------


def vector_blocks(n: int, d: int, clusters: int, seed: int,
                  block: int = 1 << 18):
    """Unit vectors around ``clusters`` centres (``chip_smoke.corpus``
    without its queries), made on the device ``block`` rows at a time:
    yields ``(first row, device array)``, so that neither the set-up nor
    the reference ever holds more than one block beside what it keeps."""
    kc, ka, kx = jax.random.split(jax.random.PRNGKey(seed), 3)
    centers = jax.random.normal(kc, (clusters, d))

    @jax.jit
    def make(b, centers):
        rows = min(block, n)
        x = centers[jax.random.randint(jax.random.fold_in(ka, b), (rows,), 0,
                                       clusters)]
        x = x + jax.random.normal(jax.random.fold_in(kx, b), (rows, d))
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    for b in range(-(-n // block)):
        yield b * block, make(b, centers)[: n - b * block]


# ---------------------------------------------------------------------------
# instrumentation
# ---------------------------------------------------------------------------


@dataclass
class Log:
    """What the benchmark's wrappers saw while ``on``."""
    on: bool = False
    retrievals: List[Tuple[List[str], List[np.ndarray], List[np.ndarray]]] \
        = field(default_factory=list)
    searches: List[Tuple[float, float, int, int]] = field(default_factory=list)
    # ("prefill", t0, t1, first position, tokens, logits used) or
    # ("decode", t0, t1, [write position of each active sequence])
    engine_steps: List[Tuple] = field(default_factory=list)
    retired: List[Tuple[float, np.ndarray, List[int]]] = field(
        default_factory=list)


def instrument(pipe, log: Log) -> None:
    for stage in pipe.stages:
        _wrap_stage(stage, log)
    _wrap_search(pipe.db, log)
    eng = getattr(pipe.llm, "engine", None)
    if eng is not None:
        _wrap_engine(eng, log)


def _wrap_stage(stage, log: Log) -> None:
    run, name = stage.run, stage.name

    def wrapped(batch):
        with Annotation(f"stage.{name}"):
            out = run(batch)
        if log.on and name == "retrieval":
            log.retrievals.append((
                list(batch.questions),
                [np.array(r.chunk_ids) for r in batch.results],
                [np.array(r.scores) for r in batch.results]))
        return out

    stage.run = wrapped


def _wrap_search(db, log: Log) -> None:
    search = db.search

    def wrapped(vectors, k):
        t0 = time.perf_counter()
        with Annotation("stage.db_search"):
            out = search(vectors, k)
        if log.on:
            log.searches.append((t0, time.perf_counter(), len(vectors), k))
        return out

    db.search = wrapped


def _wrap_engine(eng, log: Log) -> None:
    prefill, decode, retire = (eng._prefill_chunks, eng._decode_work,
                               eng._retire)

    def prefill_w(req, k):
        off = req.filled
        n = min(k * eng.chunk_tokens, req.prompt_len - off)
        t0 = time.perf_counter()
        with Annotation("engine.prefill_chunk"):
            prefill(req, k)
        if log.on:
            log.engine_steps.append(("prefill", t0, time.perf_counter(), off,
                                     n, req.filled >= req.prompt_len))

    def decode_w():
        pos = [int(eng._pos[s]) for s in eng._decode_slots()]
        t0 = time.perf_counter()
        with Annotation("engine.decode"):
            did = decode()
        if did and log.on:
            log.engine_steps.append(("decode", t0, time.perf_counter(), pos))
        return did

    def retire_w(req):
        retire(req)
        if log.on:
            log.retired.append((time.perf_counter(), np.array(req.tokens),
                                list(req.out)))

    eng._prefill_chunks, eng._decode_work, eng._retire = (prefill_w, decode_w,
                                                         retire_w)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def pipeline_spec(cfg: Dict, seed: int):
    """The configuration's pipeline: the generator's weights from ``seed``,
    the embedding table from the corpus's own seed (the data of a
    deployment is fixed; its traffic and weights are drawn)."""
    from repro.core.spec import PipelineSpec
    d = copy.deepcopy(cfg["pipeline"])
    d["embedder"].setdefault("options", {})["seed"] = cfg["corpus"]["seed"]
    if d["llm"]["component"] == "model":
        d["llm"]["options"]["seed"] = seed
    return PipelineSpec.from_dict(d)


# the source's (Hugging Face) keys, as the program's model names them
HF_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
           "vocab_size": "vocab_size", "rope_theta": "rope_theta",
           "rms_norm_eps": "norm_eps", "torch_dtype": "dtype",
           "tie_word_embeddings": "tie_embeddings",
           "partial_rotary_factor": "partial_rotary_factor",
           "rope_scaling": "rope_scaling"}


def check_model(cfg: Dict) -> None:
    """The program serves the model the configuration file states: its
    ``model`` block exactly, and the source's ``hf_config`` but for the
    departures that ``assumed`` names."""
    m = cfg.get("model")
    if not m:
        return
    from repro import configs as arch_configs
    opts = cfg["pipeline"]["llm"]["options"]
    mc = (arch_configs.get_smoke(opts["arch"]) if opts.get("smoke")
          else arch_configs.get_config(opts["arch"]))
    have = {"n_layers": mc.n_layers, "d_model": mc.d_model,
            "n_heads": mc.n_heads, "n_kv_heads": mc.n_kv_heads,
            "d_ff": mc.d_ff, "vocab_size": mc.vocab_size,
            "head_dim": mc.resolved_head_dim, "rope_theta": mc.rope_theta,
            "norm_eps": mc.norm_eps, "dtype": mc.dtype,
            "family": mc.family, "activation": mc.activation,
            "rope_type": mc.rope_type, "tie_embeddings": mc.tie_embeddings,
            "attn_window": mc.attn_window,
            # the program's rotary embedding has no such options: it
            # rotates every dimension of a head, unscaled
            "partial_rotary_factor": 1.0, "rope_scaling": None}
    for key, want in m.items():
        if key in have and have[key] != want:
            raise ValueError(f"the program's {opts['arch']} has {key}="
                             f"{have[key]!r}, the configuration states "
                             f"{want!r}")
    stated = cfg.get("assumed", {})
    for hf_key, key in HF_KEYS.items():
        hf = cfg.get("hf_config", {})
        if hf_key in hf and hf_key not in stated and have[key] != hf[hf_key]:
            raise ValueError(f"the program's {opts['arch']} has {key}="
                             f"{have[key]!r}, the source's {hf_key} is "
                             f"{hf[hf_key]!r}, and `assumed` states no "
                             f"such departure")


@dataclass
class Served:
    """A built and warmed pipeline, and what the window needs of its data."""
    pipe: object
    corpus: object = None


def set_up(cfg: Dict, traffic: Dict, seed: int, log: Log) -> Served:
    from repro.core.interfaces import Chunk
    from repro.core.registry import build
    from repro.workload.corpus import CorpusConfig, SyntheticCorpus

    check_model(cfg)
    pipe = build(pipeline_spec(cfg, seed))
    c = cfg["corpus"]
    served = Served(pipe=pipe)
    if c["kind"] == "synthetic_text":
        corpus = SyntheticCorpus(CorpusConfig(
            n_docs=c["n_docs"], sentences_per_doc=c["sentences_per_doc"],
            facts_per_doc=c["facts_per_doc"], seed=c["seed"]))
        pipe.index_documents(corpus.all_documents())
        served.corpus = corpus
    elif c["kind"] == "clustered_vectors":
        for lo, x in vector_blocks(c["rows"], c["dim"], c["clusters"],
                                   c["seed"]):
            pipe.db.insert(np.asarray(x), [
                Chunk(chunk_id=-1, doc_id=lo + i, text="")
                for i in range(x.shape[0])])
            del x
        pipe.db.build_index()
    else:
        raise ValueError(f"unknown corpus kind {c['kind']!r}")
    instrument(pipe, log)
    warm_up(cfg, traffic, served)
    return served


def warm_up(cfg: Dict, traffic: Dict, served: Served) -> None:
    """Every batch size the batcher can form, once, through the served
    path: the shapes this cell's traffic uses and no others."""
    pipe = served.pipe
    qs = traffic_lib.questions(cfg, traffic, traffic["batch"]["max_batch"],
                               0, served.corpus, salt=3)
    for b in range(1, traffic["batch"]["max_batch"] + 1):
        pipe.query([q["question"] for q in qs[:b]])
    pipe.traces.clear()
    stats = getattr(pipe.llm, "stats", None)
    if hasattr(stats, "reset"):
        stats.reset()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


def _harness_class():
    from repro.serving.harness import ServingHarness

    class _Harness(ServingHarness):
        """``ServingHarness`` driven by the benchmark's own due times."""

        def __init__(self, pipe, requests, due, deadline, **kw):
            super().__init__(pipe, None, **kw)
            self._requests, self._due, self._deadline = (requests, due,
                                                         deadline)
            self.subs: List[Tuple[object, float, float]] = []
            get_batch = self.batcher.get_batch

            def wait_batch():
                with Annotation("harness.wait_batch"):
                    return get_batch()

            self.batcher.get_batch = wait_batch

        def _materialize(self):
            return self._requests

        def _drive_open(self, requests):
            for req, due in zip(requests, self._due):
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                t = time.perf_counter()
                self.subs.append((self._submit(req), due, t))
            for sub, _, _ in self.subs:
                left = self._deadline - time.perf_counter()
                if left <= 0 or not sub.done.wait(left):
                    break
            # past the deadline: what never started is not waited for
            b = self.batcher
            with b._cv:
                late = list(b._queries)
                b._queries.clear()
            for sub in late:
                self._finish(sub, ok=False,
                             err=TimeoutError("not served by the deadline"))

    return _Harness


@dataclass
class Window:
    t0: float
    seconds: float
    records: List[Dict]
    batch_sizes: List[int]
    stage_series: Dict[str, List[float]]
    tpot_s: List[float]
    compiles: int
    gc_full: Tuple[int, float]
    trace_pc: Optional[Tuple[float, float]] = None


class _GcPauses:
    """Python's full collections inside the window, and the longest."""

    def __init__(self):
        self.on, self.n, self.longest, self._t = False, 0, 0.0, 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase: str, info: Dict) -> None:
        if not self.on or info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.n += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)


class _CompileCounter:
    def __init__(self):
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and "compile" in event:
            self.n += 1


_COMPILES: Optional[_CompileCounter] = None
_GC: Optional[_GcPauses] = None


def run_window(cfg: Dict, traffic: Dict, served: Served, seed: int,
               seconds: float, log: Log, trace_dir: Optional[str] = None
               ) -> Window:
    """Offer the cell's load for ``seconds`` from a fixed start, and wait
    for the answers up to ``drain_s`` past the close."""
    global _COMPILES, _GC
    from repro.serving.arrival import ArrivalConfig
    from repro.serving.batcher import BatchPolicy
    from repro.serving.harness import ServingConfig
    from repro.workload.generator import Request, WorkloadConfig

    if _COMPILES is None:
        _COMPILES, _GC = _CompileCounter(), _GcPauses()
    pipe = served.pipe
    # a traffic file that fixes ``order_seed`` replays one order of its
    # gaps and questions for every --seed (see PERF.md, generation cell)
    order = int(traffic.get("order_seed", seed))
    offs = traffic_lib.due_times(traffic, seconds, order)
    qs = traffic_lib.questions(cfg, traffic, len(offs), order, served.corpus)
    reqs = [Request("query", i, doc_id=q["doc_id"], question=q["question"],
                    answer=q["answer"], gold_doc_id=q["doc_id"])
            for i, q in enumerate(qs)]
    b = traffic["batch"]
    policy = BatchPolicy(max_batch=b["max_batch"],
                         max_wait_s=b["max_wait_ms"] / 1e3)
    scfg = ServingConfig(
        arrival=ArrivalConfig(mode="open", target_qps=float(
            traffic["arrival"]["rate_qps"]), n_requests=len(reqs)),
        policy=policy, slo_ms=1e9, evaluate=False)
    # queries only: traffic_lib.check refuses any other op mix
    wcfg = WorkloadConfig(query_frac=float(traffic["ops"]["query"]),
                          update_frac=0.0, n_requests=len(reqs))
    t0 = time.perf_counter() + 0.05
    deadline = t0 + seconds + float(traffic.get("drain_s", 60))
    h = _harness_class()(pipe, reqs, [t0 + o for o in offs], deadline,
                         wcfg=wcfg, scfg=scfg)
    timer = pipe.timer
    n_series = {k: len(v) for k, v in timer.series.items()}
    tracer = None
    if trace_dir is not None:
        tracer = _Tracer(trace_dir, t0 + float(traffic["trace"]["offset_s"]),
                         float(traffic["trace"]["seconds"]))
        tracer.start()
    log.on = True
    _COMPILES.on, _COMPILES.n = True, 0
    _GC.on, _GC.n, _GC.longest = True, 0, 0.0
    h.run()
    _COMPILES.on = _GC.on = False
    log.on = False
    if tracer is not None:
        tracer.join()
    records = []
    for sub, due, t_submit in h.subs:
        r = sub.record
        records.append({"due": due, "submit": t_submit, "start": r.start_s,
                        "end": r.end_s, "ok": bool(r.ok and sub.finished)})
    series = {k: list(v[n_series.get(k, 0):])
              for k, v in timer.series.items()}
    stats = getattr(pipe.llm, "stats", None)
    tpot = list(getattr(stats, "tpot_s", []) or [])
    return Window(t0=t0, seconds=seconds, records=records,
                  batch_sizes=list(h.batch_sizes), stage_series=series,
                  tpot_s=tpot, compiles=_COMPILES.n,
                  gc_full=(_GC.n, _GC.longest),
                  trace_pc=tracer.pc if tracer else None)


class _Tracer(threading.Thread):
    """Trace ``seconds`` of the window from ``start`` (perf_counter)."""

    def __init__(self, log_dir: str, start: float, seconds: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.log_dir, self.start_at, self.seconds = log_dir, start, seconds
        self.pc: Optional[Tuple[float, float]] = None

    def run(self) -> None:
        time.sleep(max(0.0, self.start_at - time.perf_counter()))
        # device ops and the benchmark's own spans; tracing every Python
        # call would slow the host it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        try:
            with Annotation("bench.trace"):
                a = time.perf_counter()
                time.sleep(self.seconds)
                b = time.perf_counter()
            self.pc = (a, b)
        finally:
            jax.profiler.stop_trace()
