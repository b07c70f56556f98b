"""Operations and bytes the algorithm needs, from shapes alone.

Each count is the least the work requires, so a share of a roofline or of
a peak built on it cannot pass 100% unless the time leaves part of the work
out:

* a dense GQA transformer (SwiGLU MLP, untied head; ``from_config``, the
  default ``model_costs`` of a configuration): FLOPs per token, with
  attention over the keys the token sees, and the head only for tokens
  whose logits are used (the last prompt token and each decoded token);
  bytes per decode step: every weight once, the gathered embedding rows,
  the keys and values each active sequence attends to, and the one
  position it writes;
* a flat exact top-k launch: the corpus once, the queries and the k
  results (``repro.roofline.retrieve``'s ``_corpus_bytes`` + ``_io_bytes``
  for ``flat``/``none``, copied).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable

F32 = 4
I32 = 4
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class Dense:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int
    dtype_bytes: int

    @classmethod
    def from_config(cls, m: Dict) -> "Dense":
        hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
        return cls(m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
                   m["d_ff"], m["vocab_size"], hd,
                   DTYPE_BYTES[m.get("dtype", "bfloat16")])

    @property
    def layer_params(self) -> int:
        d, h, kv, hd, f = (self.d_model, self.n_heads, self.n_kv_heads,
                           self.head_dim, self.d_ff)
        return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab_size

    @property
    def norm_params(self) -> int:
        return (2 * self.n_layers + 1) * self.d_model

    def token_flops(self, context: int, logits: bool) -> float:
        """One token that attends to ``context`` keys (itself included)."""
        f = 2.0 * self.n_layers * self.layer_params
        f += 4.0 * self.n_layers * context * self.n_heads * self.head_dim
        if logits:
            f += 2.0 * self.head_params
        return f

    def kv_bytes_per_position(self) -> int:
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim \
            * self.dtype_bytes

    def decode_step_bytes(self, positions: Iterable[int]) -> float:
        """Least HBM bytes of one decode step whose active sequences write
        at ``positions`` (each reads the keys and values 0..p-1 from the
        cache and writes position p)."""
        pos = list(positions)
        w = (self.n_layers * self.layer_params + self.head_params
             + self.norm_params) * self.dtype_bytes
        w += len(pos) * self.d_model * self.dtype_bytes      # embedding rows
        kv = self.kv_bytes_per_position()
        return float(w + (sum(pos) + len(pos)) * kv)


def from_config(m: Dict) -> Dense:
    """The count of a configuration's ``model`` block."""
    return Dense.from_config(m)


def flat_launch_bytes(n: int, d: int, nq: int, k: int) -> float:
    """Least HBM bytes of one exact flat f32 top-k launch."""
    return float(n * d * F32 + nq * d * F32 + nq * k * (F32 + I32))
