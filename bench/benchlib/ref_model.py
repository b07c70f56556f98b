"""Plain float32 reference of a dense GQA transformer, and its control.

The architecture (as served by ``repro.models.transformer`` for the
``dense`` family, written down here from its equations and imported from
nowhere): token embedding; per layer ``x += Wo . attn(rope(Wq h),
rope(Wk h), Wv h)`` and ``x += Wd (silu(Wg h) * Wu h)``, each ``h`` an RMS
norm of ``x`` scaled by ``1 + w``; a final RMS norm and an untied head.
RoPE rotates the two halves of every head (full rotary), grouped-query
attention shares each key/value head among ``n_heads / n_kv_heads`` query
heads, and attention is causal.

Weights are made again from the seed by the configuration's stated scheme
(``weights``: every leaf of the parameter tree, in sorted path order, gets
one key of ``split(PRNGKey(seed), n_leaves)``; norms are zero; the
embedding is ``N(0, 1) x 0.02``; every other matrix is a normal truncated
to [-2, 2] times ``1 / sqrt(fan_in)``; all cast to the served dtype).  The
forward runs layer by layer in float32 at ``precision=HIGHEST`` over the
served dtype's values, so it fits beside nothing but its own weights.

The control is the same forward with every weight rounded to float8
(e4m3, one scale per output column): the step below bfloat16 that would
tempt a later change.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("wk", "wo", "wq", "wv", "attn_norm", "w_down", "w_gate",
                "w_up", "mlp_norm")


def leaf_shapes(m: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """(name, shape) of every parameter leaf, in the tree's sorted order."""
    L, d, h, kv, f, v = (m["n_layers"], m["d_model"], m["n_heads"],
                         m["n_kv_heads"], m["d_ff"], m["vocab_size"])
    hd = m.get("head_dim") or d // h
    return [("embed", (v, d)), ("final_norm", (d,)),
            ("wk", (L, d, kv * hd)), ("wo", (L, h * hd, d)),
            ("wq", (L, d, h * hd)), ("wv", (L, d, kv * hd)),
            ("attn_norm", (L, d)), ("w_down", (L, f, d)),
            ("w_gate", (L, d, f)), ("w_up", (L, d, f)),
            ("mlp_norm", (L, d)), ("lm_head", (d, v))]


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _trunc(key, shape, dtype):
    std = 1.0 / math.sqrt(shape[-2])
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def make_weights(m: Dict, seed: int) -> Dict[str, jax.Array]:
    """The served weights, made again from the seed on the device."""
    dtype = jnp.dtype(m.get("dtype", "bfloat16"))
    shapes = leaf_shapes(m)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for (name, shape), k in zip(shapes, keys):
        if "norm" in name:
            out[name] = jnp.zeros(shape, dtype)
        elif name == "embed":
            out[name] = _normal(k, shape, dtype)
        else:
            out[name] = _trunc(k, shape, dtype)
    return out


def _fp8(w):
    """Round to float8 e4m3 with one scale per output column."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs          # [B,S,half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("m", "fp8"))
def _layer(x, w: Dict, layer, m: Tuple, fp8: bool):
    L, d, h, kv, hd, eps, theta = m
    lw = {}
    for name in LAYER_LEAVES:
        a = jax.lax.dynamic_index_in_dim(w[name], layer, 0, keepdims=False)
        a = a.astype(jnp.float32)
        lw[name] = _fp8(a) if (fp8 and a.ndim == 2) else a
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    y = _rms(x, lw["attn_norm"], eps)
    q = jnp.matmul(y, lw["wq"], precision=HIGHEST).reshape(B, S, h, hd)
    k = jnp.matmul(y, lw["wk"], precision=HIGHEST).reshape(B, S, kv, hd)
    v = jnp.matmul(y, lw["wv"], precision=HIGHEST).reshape(B, S, kv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(B, S, kv, h // kv, hd)
    s = jnp.einsum("bqkrd,bmkd->bkrqm", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkrqm,bmkd->bqkrd", p, v, precision=HIGHEST)
    x = x + jnp.matmul(o.reshape(B, S, h * hd), lw["wo"], precision=HIGHEST)
    y = _rms(x, lw["mlp_norm"], eps)
    g = jnp.matmul(y, lw["w_gate"], precision=HIGHEST)
    u = jnp.matmul(y, lw["w_up"], precision=HIGHEST)
    return x + jnp.matmul(jax.nn.silu(g) * u, lw["w_down"], precision=HIGHEST)


@partial(jax.jit, static_argnames=("fp8",))
def _embed(emb, tokens, fp8: bool):
    t = emb.astype(jnp.float32)
    if fp8:
        t = _fp8(t.T).T                # one scale per embedding row
    return jnp.take(t, tokens, axis=0)


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, norm, head, rows, cols, eps: float, fp8: bool):
    hw = head.astype(jnp.float32)
    if fp8:
        hw = _fp8(hw)
    y = _rms(x[rows, cols], norm.astype(jnp.float32), eps)      # [n, d]
    return jnp.matmul(y, hw, precision=HIGHEST)                 # [n, V]


def logits_at(m: Dict, w: Dict, seqs: Sequence[np.ndarray],
              at: Sequence[Sequence[int]], fp8: bool = False) -> np.ndarray:
    """Float32 logits of each sequence at the positions ``at[i]``.

    Sequences are right-padded to one length, so one program serves every
    run of the cell; padding never reaches an earlier position (causal).
    """
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    mt = (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], hd,
          float(m.get("norm_eps", 1e-5)), float(m["rope_theta"]))
    S = max(len(s) for s in seqs)
    S = -(-S // 128) * 128
    tok = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        tok[i, :len(s)] = s
    x = _embed(w["embed"], jnp.asarray(tok), fp8)
    layer_w = {n: w[n] for n in LAYER_LEAVES}
    for layer in range(m["n_layers"]):
        x = _layer(x, layer_w, jnp.int32(layer), mt, fp8)
    rows = np.concatenate([np.full(len(a), i) for i, a in enumerate(at)])
    cols = np.concatenate([np.asarray(a) for a in at])
    out = _head(x, w["final_norm"], w["lm_head"], jnp.asarray(rows),
                jnp.asarray(cols), mt[5], fp8)
    return np.asarray(out)
