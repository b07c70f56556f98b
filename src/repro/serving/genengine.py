"""Token-level continuous-batching generation engine (the vLLM analogue).

``ModelLLM`` schedules at *request-batch* granularity: a batch prefills
together, decodes in lock-step for ``max_new`` steps, and only then admits
the next batch — one long prompt stalls every request behind it (RAGO,
arXiv 2503.14649: prefill/decode-aware scheduling dominates RAG serving
tails).  ``GenEngine`` schedules at *token* granularity over a fixed pool of
KV-cache slots:

* **slot pool** — the KV cache is allocated once as ``[L, slots, max_len]``;
  each slot holds one in-flight sequence at its own decode position (vector
  ``cache["pos"]`` — ``repro.models.layers.cached_attention_step``).
* **chunked prefill** — prompts are split into ``chunk_tokens``-sized chunks
  processed between decode steps under a ``prefill_chunks_per_step`` budget,
  so admitting a long prompt inflates in-flight requests' inter-token gaps
  by at most one chunk, not one full prompt.
* **continuous admission** — every engine step moves newly arrived requests
  into free slots (``fcfs`` or shortest-prompt-first ``sjf``) and retires
  finished sequences per-slot; the decode batch never drains to admit.
* **per-request metrics** — TTFT is measured per request from its submitted
  arrival time to its first token, TPOT from its own decode cadence; samples
  land in a thread-safe ``GenStats`` (replica engines may share one).
* **spans** — each step's parts are ``repro.obs.span``s, on the device
  trace's clock under the profiler (and on an attached ``Tracer``):
  ``engine.admit``; ``engine.prefill.launch`` and
  ``engine.prefill.first_token``; ``engine.decode.upload`` (positions and
  tokens to the device), ``.launch`` (the decode program and its argmax),
  ``.sync`` (the next tokens back to the host) and ``.bookkeep`` (the
  per-slot loop and retirement).  The jitted programs are named
  ``gen_decode_step`` and ``gen_prefill_chunk``.

Greedy decode attends only within a sequence's own cache row, so the engine
is **output-identical** to the lock-step ``ModelLLM`` (same seed, same
prompts, same admission order) — scheduling freedom, never semantics.

Correctness of slot reuse: a retiring sequence's K/V is *not* zeroed.  Every
attention mask bounds reads at the row's current position, writes proceed
strictly forward from 0 (prefill chunks) then position P (decode), and each
position is overwritten before it first becomes readable — stale K/V from a
previous occupant or a right-padded final chunk is never attended.

Threading contract (lock-discipline audit): one ``GenEngine`` is owned by
exactly one worker thread — the elastic executor clones a warm engine per
generation replica (``replica_copy``) rather than sharing one — so the
engine itself holds no locks and declares no guarded fields.  The only
cross-thread state is the shared ``GenStats``, whose fields are
``# guarded-by: _lock`` in ``repro.core.generator``.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.generator import (PER_ROW_POS_FAMILIES, GenStats, ModelLLM,
                                  build_prompt, render_tokens)
from repro.core.interfaces import BaseLLM, Chunk
from repro.core.registry import register
from repro.core.tokenizer import HashTokenizer
from repro.models import api
from repro.models.config import ModelConfig

ADMISSION_POLICIES = ("fcfs", "sjf")


@dataclass
class GenRequest:
    """One generation request's lifecycle through the slot pool."""

    rid: int
    tokens: np.ndarray              # [P] int32, unpadded true prompt
    max_new: int
    t_arrive: float
    prompt_len: int = 0
    filled: int = 0                 # prompt tokens prefilled so far
    slot: int = -1
    out: List[int] = field(default_factory=list)
    t_first: float = 0.0            # wall time of the first token
    t_done: float = 0.0
    state: str = "queued"           # queued | prefill | decode | done

    def __post_init__(self):
        self.prompt_len = len(self.tokens)

    @property
    def ttft_s(self) -> float:
        return self.t_first - self.t_arrive

    @property
    def tpot_s(self) -> float:
        return ((self.t_done - self.t_first) / max(len(self.out) - 1, 1)
                if len(self.out) > 1 else 0.0)


class _EngineCore:
    """Everything replica engines share: model module, params, jit caches.

    Cloning an engine reuses the core, so a warm-pool replica costs one
    cache allocation — no re-init, no recompilation.
    """

    def __init__(self, cfg: ModelConfig, seed: int = 0,
                 params=None, model=None):
        assert cfg.family in PER_ROW_POS_FAMILIES and cfg.uses_tokens, (
            f"GenEngine needs a token-input transformer family "
            f"(one of {PER_ROW_POS_FAMILIES} using tokens), got "
            f"{cfg.family!r}")
        assert cfg.rope_type in ("rope", "none"), (
            f"chunked prefill supports rope/none positions, "
            f"got {cfg.rope_type!r}")
        self.cfg = cfg
        self.model = model if model is not None else api.get_model(cfg)
        self.params = (params if params is not None
                       else self.model.init(jax.random.PRNGKey(seed), cfg))
        self.tok = HashTokenizer(cfg.vocab_size)
        decode_step = self.model.decode_step

        def gen_decode_step(params, batch, cache):
            return decode_step(params, cfg=cfg, batch=batch, cache=cache)

        # named programs: the trace shows ``jit_gen_decode_step`` and
        # ``jit_gen_prefill_chunk``
        self._decode = jax.jit(gen_decode_step)
        self._chunk = jax.jit(self.gen_prefill_chunk)

    def gen_prefill_chunk(self, params, tokens, k, v, slot, offset):
        """Prefill one chunk of one slot inside the pooled cache: slice the
        slot's row, run the chunk, write the row back."""
        row = {"k": jax.lax.dynamic_slice_in_dim(k, slot, 1, axis=1),
               "v": jax.lax.dynamic_slice_in_dim(v, slot, 1, axis=1)}
        logits, row = self.model.prefill_chunk(
            params, self.cfg, {"tokens": tokens}, row, offset)
        k = jax.lax.dynamic_update_slice_in_dim(k, row["k"], slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(v, row["v"], slot, axis=1)
        return logits, k, v


class GenEngine:
    """Fixed-slot continuous-batching engine over one ``_EngineCore``.

    Drive it either as a service (``submit`` + ``step`` in a loop — the
    serving benchmarks' real-time mode) or in batch (``run``/``generate``),
    which steps to completion and returns answers in submission order.
    """

    def __init__(self, cfg: Optional[ModelConfig] = None, slots: int = 4,
                 chunk_tokens: int = 32, prefill_chunks_per_step: int = 1,
                 admission: str = "fcfs", max_prompt: int = 256,
                 max_new: int = 16, seed: int = 0,
                 stats: Optional[GenStats] = None,
                 core: Optional[_EngineCore] = None):
        assert slots >= 1 and chunk_tokens >= 1 and max_new >= 1
        assert prefill_chunks_per_step >= 1
        assert admission in ADMISSION_POLICIES, admission
        assert (cfg is not None) or (core is not None), "need cfg or core"
        self.core = core if core is not None else _EngineCore(cfg, seed=seed)
        self.cfg = self.core.cfg
        self.slots = slots
        self.chunk_tokens = chunk_tokens
        self.prefill_chunks_per_step = prefill_chunks_per_step
        self.admission = admission
        self.max_prompt = max_prompt
        self.max_new = max_new
        self._max_new_cap = max_new
        self.stats = stats if stats is not None else GenStats()
        self.tok = self.core.tok
        # the prompt region is rounded up to the chunk grid so a right-padded
        # final chunk always fits before the decode region
        n_chunks = -(-max_prompt // chunk_tokens)
        self.max_len = n_chunks * chunk_tokens + max_new
        self.cache = self.core.model.init_cache(self.cfg, slots, self.max_len)
        # per-slot decode positions (vector pos — one sequence per row)
        self._pos = np.zeros(slots, dtype=np.int32)
        self._cur = np.zeros(slots, dtype=np.int32)   # last emitted token
        self._slot_req: List[Optional[GenRequest]] = [None] * slots
        self._free: List[int] = list(range(slots))
        self._queue: deque = deque()
        self._rr = 0                 # round-robin cursor over prefill slots
        self._next_rid = 0
        self.records: Dict[int, GenRequest] = {}
        self.n_steps = 0
        self.n_prefill_chunks = 0
        self.n_decode_steps = 0
        # optional obs.Tracer for token-level events (prefill chunks, first
        # token, retirement); replicas inherit it via clone()
        self.tracer = None

    # -- replica support ----------------------------------------------------

    def clone(self, stats: Optional[GenStats] = None) -> "GenEngine":
        """A warm replica: shares params + jit caches (via the core) and, by
        default, the thread-safe stats; gets its own slot pool.  The clone's
        cache is sized for the *configured* ``max_new`` ceiling, with the
        current (possibly ladder-degraded) value carried as the runtime
        knob — so a replica created under SLO pressure can still step back
        up when the quality ladder recovers."""
        twin = GenEngine(core=self.core, slots=self.slots,
                         chunk_tokens=self.chunk_tokens,
                         prefill_chunks_per_step=self.prefill_chunks_per_step,
                         admission=self.admission, max_prompt=self.max_prompt,
                         max_new=self._max_new_cap,
                         stats=stats if stats is not None else self.stats)
        twin.set_max_new(self.max_new)
        twin.tracer = self.tracer
        return twin

    def set_max_new(self, n: int) -> int:
        """Autoscale knob: decode length for *newly admitted* requests,
        clamped to the cache's configured ceiling."""
        self.max_new = max(1, min(int(n), self._max_new_cap))
        return self.max_new

    # -- submission ---------------------------------------------------------

    def encode_prompt(self, text: str) -> np.ndarray:
        ids = self.tok.encode(text, self.max_prompt)
        if not ids:
            ids = [self.tok.pad_id]     # empty prompt still reads position 0
        return np.asarray(ids, dtype=np.int32)

    def submit(self, prompt: str, t_arrive: Optional[float] = None,
               max_new: Optional[int] = None) -> int:
        """Queue one prompt; returns the request id.  ``t_arrive`` anchors
        the TTFT measurement (defaults to now)."""
        req = GenRequest(
            rid=self._next_rid, tokens=self.encode_prompt(prompt),
            max_new=max(1, min(int(max_new or self.max_new),
                               self._max_new_cap)),
            t_arrive=time.perf_counter() if t_arrive is None else t_arrive)
        self._next_rid += 1
        self._queue.append(req)
        self.records[req.rid] = req
        return req.rid

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def n_active(self) -> int:
        return self.slots - len(self._free)

    def busy(self) -> bool:
        return bool(self._queue) or self.n_active > 0

    # -- the engine step ----------------------------------------------------

    def step(self) -> bool:
        """One scheduling iteration: admit → prefill budget → one decode
        step → retire.  Returns True if any work was done."""
        self.n_steps += 1
        if self._free and self._queue:   # a span only where one moves
            with obs.span("engine.admit", self.tracer):
                self._admit()
        did = self._prefill_work()
        did = self._decode_work() or did
        return did

    def _admit(self) -> None:
        while self._free and self._queue:
            if self.admission == "sjf":
                # shortest remaining prompt first; FIFO tie-break
                best = min(range(len(self._queue)),
                           key=lambda i: (self._queue[i].prompt_len, i))
                self._queue.rotate(-best)
                req = self._queue.popleft()
                self._queue.rotate(best)
            else:
                req = self._queue.popleft()
            slot = self._free.pop(0)
            req.slot, req.state, req.filled = slot, "prefill", 0
            self._slot_req[slot] = req
            self._pos[slot] = 0

    def _prefill_slots(self) -> List[int]:
        return [s for s in range(self.slots)
                if self._slot_req[s] is not None
                and self._slot_req[s].state == "prefill"]

    def _prefill_work(self) -> bool:
        """Spend the per-step prefill budget (``prefill_chunks_per_step``
        chunks), round-robin across slots so concurrent prefills share it.
        Consecutive chunks of one prompt are fused into a single call —
        same math (chunk attention is position-masked), ≤ budget distinct
        jit shapes, far fewer kernel launches."""
        budget = self.prefill_chunks_per_step
        did = False
        while budget > 0:
            pending = self._prefill_slots()
            if not pending:
                break
            slot = pending[self._rr % len(pending)]
            self._rr += 1
            req = self._slot_req[slot]
            C = self.chunk_tokens
            rem = -(-(req.prompt_len - req.filled) // C)
            k = min(budget, rem)
            self._prefill_chunks(req, k)
            budget -= k
            did = True
        return did

    def _prefill_chunks(self, req: GenRequest, k: int) -> None:
        C = k * self.chunk_tokens
        off = req.filled
        chunk = req.tokens[off:off + C]
        n = len(chunk)
        if n < C:                       # right-pad the final chunk; padded
            chunk = np.pad(chunk, (0, C - n))  # K/V is never attended
        tr = self.tracer
        with obs.span("engine.prefill.launch", tr, rid=req.rid, chunks=k):
            logits, self.cache["k"], self.cache["v"] = self.core._chunk(
                self.core.params, jnp.asarray(chunk[None]),
                self.cache["k"], self.cache["v"],
                jnp.asarray(req.slot, jnp.int32), jnp.asarray(off, jnp.int32))
        self.n_prefill_chunks += k
        req.filled = off + n
        # park the slot's decode position at the *next* write offset: a
        # ride-along decode write lands exactly where the next real write
        # (chunk or first decode token) will overwrite it
        self._pos[req.slot] = req.filled
        if req.filled >= req.prompt_len:
            # final chunk: the last real token's logits give the first token
            with obs.span("engine.prefill.first_token", tr, rid=req.rid):
                first = int(np.asarray(
                    jnp.argmax(logits[0, req.prompt_len - 1 - off])))
            req.out.append(first)
            req.t_first = time.perf_counter()
            req.state = "decode"
            self._cur[req.slot] = first
            self._pos[req.slot] = req.prompt_len
            if len(req.out) >= req.max_new:
                self._retire(req)

    def _decode_slots(self) -> List[int]:
        return [s for s in range(self.slots)
                if self._slot_req[s] is not None
                and self._slot_req[s].state == "decode"]

    def _decode_work(self) -> bool:
        """One batched decode step across every slot in decode state.

        Idle / prefilling slots ride along for jit shape stability, parked at
        their next write offset — their garbage writes sit exactly where the
        next real write will land, so they are overwritten before they ever
        become attendable.
        """
        active = self._decode_slots()
        if not active:
            return False
        tr = self.tracer
        with obs.span("engine.decode.upload", tr):
            self.cache["pos"] = jnp.asarray(self._pos)
            batch = {"tokens": jnp.asarray(self._cur[:, None])}
        with obs.span("engine.decode.launch", tr):
            logits, self.cache = self.core._decode(
                self.core.params, batch=batch, cache=self.cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        with obs.span("engine.decode.sync", tr):
            nxt = np.asarray(nxt)
        with obs.span("engine.decode.bookkeep", tr):
            now = time.perf_counter()
            self.n_decode_steps += 1
            for s in active:
                req = self._slot_req[s]
                req.out.append(int(nxt[s]))
                self._cur[s] = int(nxt[s])
                self._pos[s] += 1
                if len(req.out) >= req.max_new:
                    req.t_done = now
                    self._retire(req)
        return True

    def _retire(self, req: GenRequest) -> None:
        if req.t_done == 0.0:
            req.t_done = time.perf_counter()
        req.state = "done"
        self.stats.record(req.ttft_s, req.tpot_s, len(req.out))
        self._slot_req[req.slot] = None
        self._free.append(req.slot)
        self._free.sort()

    # -- batch drive --------------------------------------------------------

    def run(self, prompts: Sequence[str]) -> List[str]:
        """Submit every prompt now, step to completion, return the decoded
        answer strings in submission order."""
        return self.run_timed(prompts)[0]

    def run_timed(self, prompts: Sequence[str]
                  ) -> Tuple[List[str], List[float]]:
        """``run``, and each prompt's first-token time (``perf_counter``).
        Batch mode owns its records: they are popped after rendering so a
        long-running serving loop of ``generate`` calls holds no
        per-request state (service-mode callers driving ``submit``/``step``
        pop ``records[rid]`` themselves)."""
        t0 = time.perf_counter()
        rids = [self.submit(p, t_arrive=t0) for p in prompts]
        while self.busy():
            self.step()
        reqs = [self.records.pop(r) for r in rids]
        return ([render_tokens(r.out) for r in reqs],
                [r.t_first for r in reqs])


class EngineLLM(BaseLLM):
    """``BaseLLM`` drop-in over ``GenEngine`` — the ``model_engine`` registry
    component.  ``generate`` batches through the slot pool; serving paths
    that want per-request arrival anchoring drive ``engine`` directly."""

    def __init__(self, cfg: Optional[ModelConfig] = None, slots: int = 4,
                 chunk_tokens: int = 32, prefill_chunks_per_step: int = 1,
                 admission: str = "fcfs", max_prompt: int = 256,
                 max_new: int = 16, seed: int = 0,
                 engine: Optional[GenEngine] = None):
        self.engine = engine if engine is not None else GenEngine(
            cfg, slots=slots, chunk_tokens=chunk_tokens,
            prefill_chunks_per_step=prefill_chunks_per_step,
            admission=admission, max_prompt=max_prompt, max_new=max_new,
            seed=seed)
        self.cfg = self.engine.cfg

    @property
    def stats(self) -> GenStats:
        return self.engine.stats

    @property
    def max_new(self) -> int:
        return self.engine.max_new

    def set_max_new(self, n: int) -> int:
        return self.engine.set_max_new(n)

    def clone(self) -> "EngineLLM":
        """Warm-pool replica: own slot pool, shared params/jit/stats."""
        return EngineLLM(engine=self.engine.clone())

    def generate(self, prompts: Sequence[str],
                 contexts: Sequence[Sequence[Chunk]]) -> List[str]:
        return self.generate_timed(prompts, contexts)[0]

    def generate_timed(self, prompts: Sequence[str],
                       contexts: Sequence[Sequence[Chunk]]
                       ) -> Tuple[List[str], List[float]]:
        texts = [build_prompt(p, c) for p, c in zip(prompts, contexts)]
        return self.engine.run_timed(texts)


def engine_from_model_llm(llm: ModelLLM, **kw) -> GenEngine:
    """Build an engine sharing a lock-step ``ModelLLM``'s params (and stats)
    — the apples-to-apples comparison the equivalence benchmark uses."""
    core = _EngineCore(llm.cfg, params=llm.params, model=llm.model)
    kw.setdefault("max_prompt", llm.max_prompt)
    kw.setdefault("max_new", llm.max_new)
    return GenEngine(core=core, **kw)


@register("llm", "model_engine")
def _engine_llm(arch: str = "", smoke: bool = True, slots: int = 4,
                chunk_tokens: int = 32, prefill_chunks_per_step: int = 1,
                admission: str = "fcfs", max_prompt: int = 256,
                max_new: int = 16, seed: int = 0,
                cfg: Optional[ModelConfig] = None) -> EngineLLM:
    """Spec-friendly continuous-batching LLM factory (mirrors ``model``)."""
    if cfg is None:
        assert arch, "llm 'model_engine' needs an 'arch' option or a cfg"
        from repro import configs as arch_configs
        cfg = (arch_configs.get_smoke(arch) if smoke
               else arch_configs.get_config(arch))
    return EngineLLM(cfg, slots=slots, chunk_tokens=chunk_tokens,
                     prefill_chunks_per_step=prefill_chunks_per_step,
                     admission=admission, max_prompt=max_prompt,
                     max_new=max_new, seed=seed)
