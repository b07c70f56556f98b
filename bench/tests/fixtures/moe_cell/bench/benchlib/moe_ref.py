"""Plain float32 reference of a top-k mixture-of-experts transformer, for
the benchmark's own tests: a configuration names it as its
``model_reference`` to hold a ``moe``-family generator.

The architecture (the program's ``moe`` family, written down from its
equations): the dense reference's attention (``ref_model``: RMS norms
scaled by ``1 + w``, full rotary, grouped-query causal attention), and in
place of its MLP a softmax router over ``moe.num_experts`` experts whose
``moe.top_k`` largest gates, renormalised to sum to one, weight the
SwiGLU experts they pick.  Every expert runs on every token, so no token
is ever dropped; that is exact only where the program's expert capacity
drops none either (``capacity_factor`` at least ``num_experts / top_k``).

Weights: every leaf of the parameter tree, in sorted path order, gets one
key of ``split(PRNGKey(seed), n_leaves)``; norms are zero, the embedding is
``N(0, 1) x 0.02``, every other matrix a normal truncated to [-2, 2] times
``1 / sqrt(fan_in)``; the router in float32, the rest in the served dtype.
The forward runs layer by layer in float32 at ``precision=HIGHEST``.
``fp8=True`` rounds every weight matrix to float8 (e4m3) first.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.ref_model import (HIGHEST, _embed, _fp8, _head, _normal, _rms,
                                _rope, _trunc)

LAYER_LEAVES = ("wk", "wo", "wq", "wv", "attn_norm", "mlp_norm", "router",
                "w_down", "w_gate", "w_up")


def leaf_shapes(m: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(name, shape, dtype) of every parameter leaf, in sorted path order."""
    L, d, h, kv, v = (m["n_layers"], m["d_model"], m["n_heads"],
                      m["n_kv_heads"], m["vocab_size"])
    hd = m.get("head_dim") or d // h
    e, f = m["moe"]["num_experts"], m["moe"]["expert_d_ff"]
    dt, rt = m.get("dtype", "bfloat16"), m["moe"].get("router_dtype",
                                                       "float32")
    return [("embed", (v, d), dt), ("final_norm", (d,), dt),
            ("wk", (L, d, kv * hd), dt), ("wo", (L, h * hd, d), dt),
            ("wq", (L, d, h * hd), dt), ("wv", (L, d, kv * hd), dt),
            ("attn_norm", (L, d), dt), ("mlp_norm", (L, d), dt),
            ("router", (L, d, e), rt), ("w_down", (L, e, f, d), dt),
            ("w_gate", (L, e, d, f), dt), ("w_up", (L, e, d, f), dt),
            ("lm_head", (d, v), dt)]


def make_weights(m: Dict, seed: int) -> Dict[str, jax.Array]:
    """The served weights, made again from the seed."""
    shapes = leaf_shapes(m)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for (name, shape, dtype), k in zip(shapes, keys):
        dtype = jnp.dtype(dtype)
        if "norm" in name:
            out[name] = jnp.zeros(shape, dtype)
        elif name == "embed":
            out[name] = _normal(k, shape, dtype)
        else:
            out[name] = _trunc(k, shape, dtype)
    return out


@partial(jax.jit, static_argnames=("m", "fp8"))
def _layer(x, w: Dict, layer, m: Tuple, fp8: bool):
    L, d, h, kv, hd, eps, theta, top_k = m
    lw = {}
    for name in LAYER_LEAVES:
        a = jax.lax.dynamic_index_in_dim(w[name], layer, 0, keepdims=False)
        a = a.astype(jnp.float32)
        lw[name] = _fp8(a) if (fp8 and a.ndim >= 2) else a
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    y = _rms(x, lw["attn_norm"], eps)
    q = jnp.matmul(y, lw["wq"], precision=HIGHEST).reshape(B, S, h, hd)
    k = jnp.matmul(y, lw["wk"], precision=HIGHEST).reshape(B, S, kv, hd)
    v = jnp.matmul(y, lw["wv"], precision=HIGHEST).reshape(B, S, kv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(B, S, kv, h // kv, hd)
    s = jnp.einsum("bqkrd,bmkd->bkrqm", q, k,
                   precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkrqm,bmkd->bqkrd", p, v, precision=HIGHEST)
    x = x + jnp.matmul(o.reshape(B, S, h * hd), lw["wo"], precision=HIGHEST)
    y = _rms(x, lw["mlp_norm"], eps)
    gates = jax.nn.softmax(jnp.matmul(y, lw["router"], precision=HIGHEST),
                           axis=-1)                               # [B,S,E]
    top, idx = jax.lax.top_k(gates, top_k)
    top = top / top.sum(-1, keepdims=True)
    weight = jnp.sum(top[..., None] * jax.nn.one_hot(idx, gates.shape[-1]),
                     axis=-2)                                     # [B,S,E]
    g = jnp.einsum("bsd,edf->bsef", y, lw["w_gate"], precision=HIGHEST)
    u = jnp.einsum("bsd,edf->bsef", y, lw["w_up"], precision=HIGHEST)
    out = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * u, lw["w_down"],
                     precision=HIGHEST)
    return x + jnp.einsum("bsed,bse->bsd", out, weight, precision=HIGHEST)


def logits_at(m: Dict, w: Dict, seqs: Sequence[np.ndarray],
              at: Sequence[Sequence[int]], fp8: bool = False) -> np.ndarray:
    """Float32 logits of each sequence at the positions ``at[i]``."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    mt = (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], hd,
          float(m.get("norm_eps", 1e-5)), float(m["rope_theta"]),
          int(m["moe"]["top_k"]))
    S = -(-max(len(s) for s in seqs) // 128) * 128
    tok = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        tok[i, :len(s)] = s
    x = _embed(w["embed"], jnp.asarray(tok), fp8)
    layer_w = {n: w[n] for n in LAYER_LEAVES}
    for layer in range(m["n_layers"]):
        x = _layer(x, layer_w, jnp.int32(layer), mt, fp8)
    rows = np.concatenate([np.full(len(a), i) for i, a in enumerate(at)])
    cols = np.concatenate([np.asarray(a) for a in at])
    return np.asarray(_head(x, w["final_norm"], w["lm_head"],
                            jnp.asarray(rows), jnp.asarray(cols), mt[5], fp8))
