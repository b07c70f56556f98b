"""Generation stage (paper §3.3.4): a JAX serving engine behind ``BaseLLM``.

``ModelLLM`` is the lock-step baseline: batched prefill fills the KV cache,
then a jit'd greedy decode loop emits tokens.  TTFT / TPOT are recorded
**per request** (the paper reads the same two metrics off vLLM's endpoint) —
jit-padding rows added for shape stability are never counted.  On
transformer families the decode runs with *per-row* positions, so a row's
output depends only on its own unpadded prompt; that makes lock-step output
identical to the token-level continuous-batching engine
(``repro.serving.genengine``) for the same admission order.  Any architecture
in the zoo plugs in via its ModelConfig — the RAG pipeline is model-agnostic,
which is the paper's point.

``ExtractiveLLM`` is the deterministic quality oracle: it answers from the
retrieved context with template matching.  Random-weight models cannot produce
graded answers, so accuracy benchmarks (paper Fig. 8/9) use this backend while
performance benchmarks use ``ModelLLM`` (DESIGN.md §2).
"""
from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.interfaces import BaseLLM, Chunk
from repro.core.registry import register
from repro.core.tokenizer import HashTokenizer
from repro.models import api
from repro.models.config import ModelConfig

PROMPT_TEMPLATE = ("answer the question using the context\n"
                   "context: {context}\nquestion: {question}\nanswer:")

# families whose serving path runs through repro.models.transformer and
# supports per-row decode positions (vector ``cache["pos"]``)
PER_ROW_POS_FAMILIES = ("dense", "moe", "vlm")


def build_prompt(question: str, contexts: Sequence[Chunk]) -> str:
    ctx = " ".join(c.text for c in contexts)
    return PROMPT_TEMPLATE.format(context=ctx, question=question)


def render_tokens(ids: Sequence[int]) -> str:
    """The shared id->text rendering for random-weight generation output
    (the hash tokenizer has no decoder).  Lock-step, engine and benchmark
    outputs must render identically for equivalence checks to mean
    anything, so there is exactly one implementation."""
    return " ".join(f"tok{t}" for t in ids)


@dataclass
class GenStats:
    """Per-request generation metrics, safe under concurrent recording.

    Replicated generate-stage workers (``ElasticExecutor`` warm pools) share
    one ``GenStats``: every mutation happens under the internal lock, so no
    sample is lost when two engines retire requests simultaneously.  Only
    *real* requests are recorded — jit-padding rows never reach ``record``.
    """

    ttft_s: List[float] = field(default_factory=list)   # guarded-by: _lock
    tpot_s: List[float] = field(default_factory=list)   # guarded-by: _lock
    tokens_out: int = 0                                 # guarded-by: _lock
    n_requests: int = 0                                 # guarded-by: _lock
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def record(self, ttft_s: float, tpot_s: float, tokens: int) -> None:
        """Record one completed request (thread-safe)."""
        with self._lock:
            self.ttft_s.append(float(ttft_s))
            self.tpot_s.append(float(tpot_s))
            self.tokens_out += int(tokens)
            self.n_requests += 1

    def reset(self) -> None:
        """Drop every sample (a serving run's warm-up is not a request)."""
        with self._lock:
            self.ttft_s, self.tpot_s = [], []
            self.tokens_out = self.n_requests = 0

    def merge(self, other: "GenStats") -> None:
        """Fold another stats object in (per-engine stats at summary time)."""
        with other._lock:
            ttft, tpot = list(other.ttft_s), list(other.tpot_s)
            tokens, n = other.tokens_out, other.n_requests
        with self._lock:
            self.ttft_s.extend(ttft)
            self.tpot_s.extend(tpot)
            self.tokens_out += tokens
            self.n_requests += n

    def summary(self) -> Dict[str, float]:
        with self._lock:
            ttft, tpot = list(self.ttft_s), list(self.tpot_s)
            tokens, n = self.tokens_out, self.n_requests
        return {
            "ttft_mean_s": float(np.mean(ttft)) if ttft else 0.0,
            "tpot_mean_s": float(np.mean(tpot)) if tpot else 0.0,
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else 0.0,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else 0.0,
            "tpot_p50_s": float(np.percentile(tpot, 50)) if tpot else 0.0,
            "tpot_p95_s": float(np.percentile(tpot, 95)) if tpot else 0.0,
            "tokens_out": float(tokens),
            "n_requests": float(n),
        }


class ModelLLM(BaseLLM):
    """Batched prefill + KV-cache greedy decode over any zoo architecture."""

    def __init__(self, cfg: ModelConfig, max_prompt: int = 256,
                 max_new: int = 16, batch_size: int = 8, seed: int = 0,
                 stats: Optional[GenStats] = None):
        self.cfg = cfg
        self.model = api.get_model(cfg)
        self.max_prompt = max_prompt
        self.max_new = max_new
        self._max_new_cap = max_new
        self.batch_size = batch_size
        self.tok = HashTokenizer(cfg.vocab_size)
        self.params = self.model.init(jax.random.PRNGKey(seed), cfg)
        self.stats = stats if stats is not None else GenStats()
        # transformer families decode with per-row positions, so right-padded
        # prompt rows generate exactly as they would unpadded
        self._per_row_pos = cfg.family in PER_ROW_POS_FAMILIES
        self._prefill = jax.jit(partial(self.model.prefill, cfg=cfg))
        self._decode = jax.jit(partial(self.model.decode_step, cfg=cfg))

    def clone(self) -> "ModelLLM":
        """A replica view for warm-pool workers: shares params, jit caches
        and the (thread-safe) stats; per-call state is already local."""
        twin = object.__new__(ModelLLM)
        twin.__dict__.update(self.__dict__)
        return twin

    def set_max_new(self, n: int) -> int:
        """Autoscale knob: clamp decode length to [1, configured max]."""
        self.max_new = max(1, min(int(n), self._max_new_cap))
        return self.max_new

    def _make_batch(self, tokens: np.ndarray) -> Dict:
        batch = {"tokens": jnp.asarray(tokens)}
        if self.cfg.family == "vlm":
            # backbone-only: pretend patch embeddings for the token ids
            B, S = tokens.shape
            batch = {"embeds": jnp.zeros((B, S, self.cfg.d_model),
                                         jnp.dtype(self.cfg.dtype))}
        if self.cfg.family == "audio":
            B = tokens.shape[0]
            batch["frames"] = jnp.zeros(
                (B, self.cfg.encoder_seq, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        return batch

    def generate(self, prompts: Sequence[str],
                 contexts: Sequence[Sequence[Chunk]]) -> List[str]:
        out: List[str] = []
        bs = self.batch_size
        for lo in range(0, len(prompts), bs):
            chunk_p = prompts[lo:lo + bs]
            chunk_c = contexts[lo:lo + bs]
            texts = [build_prompt(p, c) for p, c in zip(chunk_p, chunk_c)]
            tokens = self.tok.encode_batch(texts, self.max_prompt)
            if len(texts) < bs:   # pad batch dim for jit shape stability
                tokens = np.pad(tokens, ((0, bs - len(texts)), (0, 0)))
            out.extend(self._generate_batch(tokens, n_real=len(texts)))
        return out

    def _generate_batch(self, tokens: np.ndarray, n_real: int) -> List[str]:
        """Generate for one padded batch; only the first ``n_real`` rows are
        real requests — they alone are timed, counted and returned."""
        B = tokens.shape[0]
        max_new = self.max_new
        max_len = self.max_prompt + max_new
        cache = self.model.init_cache(self.cfg, B, max_len)
        t0 = time.perf_counter()
        batch = self._make_batch(tokens)
        if self._per_row_pos:
            # per-row true prompt lengths (pad_id == 0 never appears in real
            # content); an all-pad row still reads one position
            lengths = np.maximum((tokens != 0).sum(axis=1), 1).astype(np.int32)
            logits, cache = self._prefill(self.params, batch=batch,
                                          cache=cache,
                                          lengths=jnp.asarray(lengths))
        else:
            logits, cache = self._prefill(self.params, batch=batch,
                                          cache=cache)
        first = np.asarray(jnp.argmax(logits, axis=-1))
        jax.block_until_ready(first)
        ttft = time.perf_counter() - t0
        toks = [first]
        cur = jnp.asarray(first[:, None].astype(np.int32))
        t1 = time.perf_counter()
        for _ in range(max_new - 1):
            step = {"tokens": cur}
            if self.cfg.family == "vlm":
                step = {"embeds": jnp.zeros(
                    (B, 1, self.cfg.d_model), jnp.dtype(self.cfg.dtype))}
            logits, cache = self._decode(self.params, batch=step, cache=cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            cur = nxt[:, None]
            toks.append(np.asarray(nxt))
        jax.block_until_ready(cur)
        n_steps = max(max_new - 1, 1)
        tpot = (time.perf_counter() - t1) / n_steps
        # lock-step semantics: every real request in the batch saw its first
        # token after the shared prefill and decoded at the shared cadence
        for _ in range(n_real):
            self.stats.record(ttft, tpot, max_new)
        ids = np.stack(toks, axis=1)[:n_real]          # [n_real, max_new]
        return [render_tokens(row) for row in ids]


_FACT = re.compile(r"the (\w+) of ([\w\-]+) is ([\w\-]+)")
_Q = re.compile(r"what is the (\w+) of ([\w\-]+)")


@register("llm", "extractive")
class ExtractiveLLM(BaseLLM):
    """Deterministic reader: extracts `the <attr> of <subj> is <val>` facts
    from the retrieved context.  Highest-version chunk wins (freshness)."""

    def generate(self, prompts: Sequence[str],
                 contexts: Sequence[Sequence[Chunk]]) -> List[str]:
        out = []
        for q, ctx in zip(prompts, contexts):
            m = _Q.search(q.lower())
            answer = ""
            if m:
                attr, subj = m.group(1), m.group(2)
                best_ver = -1
                for c in ctx:
                    for fm in _FACT.finditer(c.text.lower()):
                        if fm.group(1) == attr and fm.group(2) == subj \
                                and c.version >= best_ver:
                            best_ver = c.version
                            answer = fm.group(3)
            out.append(answer)
        return out


@register("llm", "model")
def _model_llm(arch: str = "", smoke: bool = True, max_prompt: int = 256,
               max_new: int = 16, batch_size: int = 8, seed: int = 0,
               cfg: Optional[ModelConfig] = None) -> ModelLLM:
    """Spec-friendly ModelLLM factory: resolves the architecture id to its
    (smoke or published) ModelConfig unless one is passed directly."""
    if cfg is None:
        assert arch, "llm 'model' needs an 'arch' option or a cfg"
        from repro import configs as arch_configs
        cfg = (arch_configs.get_smoke(arch) if smoke
               else arch_configs.get_config(arch))
    return ModelLLM(cfg, max_prompt=max_prompt, max_new=max_new,
                    batch_size=batch_size, seed=seed)


def make_llm(kind: str = "extractive", cfg: Optional[ModelConfig] = None,
             **kw) -> BaseLLM:
    from repro.core import registry
    if cfg is not None:
        kw["cfg"] = cfg
    return registry.create("llm", kind, **kw)
