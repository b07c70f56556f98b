"""Operations and bytes of a top-k mixture-of-experts transformer, for the
benchmark's own tests: a configuration names it as its ``model_costs``.

As the dense count (``costs.Dense``) with each layer's MLP replaced by a
router (``d_model x num_experts``) and the ``top_k`` experts a token uses:
a token runs ``top_k`` experts, and a decode step reads at least ``top_k``
experts of each layer, so both counts stay the least the work needs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

from benchlib.costs import DTYPE_BYTES, Dense


@dataclass(frozen=True)
class MoE(Dense):
    num_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0

    @property
    def layer_params(self) -> int:
        d, h, kv, hd = self.d_model, self.n_heads, self.n_kv_heads, \
            self.head_dim
        return (2 * d * h * hd + 2 * d * kv * hd + d * self.num_experts
                + 3 * d * self.expert_d_ff * self.top_k)


def from_config(m: Dict) -> MoE:
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    e = m["moe"]
    return MoE(m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
               m["d_ff"], m["vocab_size"], hd,
               DTYPE_BYTES[m.get("dtype", "bfloat16")], e["num_experts"],
               e["top_k"], e["expert_d_ff"])
