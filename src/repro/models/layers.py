"""Shared neural-net layers: norms, RoPE/M-RoPE, GQA attention, MLP variants.

All layers are pure functions over explicit parameter pytrees; there is no
module framework.  Parameter *shapes* are produced by the ``*_shape`` twins so
the dry-run can build ShapeDtypeStruct trees without touching device memory.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


@partial(jax.jit, static_argnames=("shape", "dtype", "scale"))
def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init, compiled as one program so the f32
    draw never sits on the device beside its scaled copy (a full-width
    weight stack is several GB in f32)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def shape_of(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def rmsnorm(x, weight, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layernorm(x, weight, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2] (fp32)."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions: broadcastable to [..., S] int32."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)              # [half]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_3d, theta: float, sections):
    """Multimodal RoPE (Qwen2-VL).

    x: [B, S, H, D]; positions_3d: [3, B, S] (temporal, height, width).
    ``sections`` partitions the half-dim into (t, h, w) frequency bands; for
    pure text all three position streams are equal and this reduces to RoPE.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)              # [half]
    # angle per stream: [3, B, S, half]
    ang = positions_3d[..., None].astype(jnp.float32) * freqs
    # pick the stream for each frequency band
    idx = jnp.concatenate([
        jnp.full((sections[i],), i, dtype=jnp.int32) for i in range(3)
    ])                                                   # [half]
    onehot = jax.nn.one_hot(idx, 3, dtype=jnp.float32)   # [half, 3]
    ang = jnp.einsum("tbsh,ht->bsh", ang, onehot)        # select stream per band
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, dim: int) -> jnp.ndarray:
    pos = np.arange(seq_len)[:, None]
    div = np.exp(np.arange(0, dim, 2) * (-math.log(10000.0) / dim))
    pe = np.zeros((seq_len, dim), dtype=np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(pe)


# ---------------------------------------------------------------------------
# activations / MLP
# ---------------------------------------------------------------------------


def activation_fn(name: str):
    if name == "swiglu":
        raise ValueError("swiglu is a gated MLP, not a pointwise activation")
    if name == "sq_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return partial(jax.nn.gelu, approximate=True)
    if name == "silu":
        return jax.nn.silu
    raise ValueError(f"unknown activation {name}")


def mlp_params_shape(cfg: ModelConfig, d_ff: Optional[int] = None, prefix_dims=()):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.dtype
    if cfg.activation == "swiglu":
        return {
            "w_gate": shape_of((*prefix_dims, d, f), dt),
            "w_up": shape_of((*prefix_dims, d, f), dt),
            "w_down": shape_of((*prefix_dims, f, d), dt),
        }
    return {
        "w_up": shape_of((*prefix_dims, d, f), dt),
        "w_down": shape_of((*prefix_dims, f, d), dt),
    }


def mlp_params_init(key, cfg: ModelConfig, d_ff: Optional[int] = None, prefix_dims=()):
    shapes = mlp_params_shape(cfg, d_ff, prefix_dims)
    keys = jax.random.split(key, len(shapes))
    return {
        name: dense_init(k, s.shape, s.dtype)
        for (name, s), k in zip(sorted(shapes.items()), keys)
    }


def mlp_apply(params, x, activation: str):
    if activation == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = activation_fn(activation)(x @ params["w_up"])
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# attention (full-sequence and single-step cached)
# ---------------------------------------------------------------------------


def attn_params_shape(cfg: ModelConfig, prefix_dims=()):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    dt = cfg.dtype
    return {
        "wq": shape_of((*prefix_dims, d, cfg.n_heads * hd), dt),
        "wk": shape_of((*prefix_dims, d, cfg.n_kv_heads * hd), dt),
        "wv": shape_of((*prefix_dims, d, cfg.n_kv_heads * hd), dt),
        "wo": shape_of((*prefix_dims, cfg.n_heads * hd, d), dt),
    }


def attn_params_init(key, cfg: ModelConfig, prefix_dims=()):
    shapes = attn_params_shape(cfg, prefix_dims)
    keys = jax.random.split(key, len(shapes))
    return {
        name: dense_init(k, s.shape, s.dtype)
        for (name, s), k in zip(sorted(shapes.items()), keys)
    }


def _split_heads(x, n_heads, head_dim):
    return x.reshape(*x.shape[:-1], n_heads, head_dim)


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def attention_scores_mask(q_len, kv_len, window: int, causal: bool, offset=0):
    """[q_len, kv_len] additive mask (0 / -inf)."""
    qpos = jnp.arange(q_len)[:, None] + offset
    kpos = jnp.arange(kv_len)[None, :]
    ok = jnp.ones((q_len, kv_len), dtype=bool)
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def multihead_attention(
    params,
    x,
    positions,
    cfg: ModelConfig,
    *,
    causal: bool = True,
    kv_x=None,
    use_rope: bool = True,
    positions_3d=None,
    window: int = 0,
):
    """Full-sequence attention.  kv_x != None -> cross attention (no rope)."""
    hd = cfg.resolved_head_dim
    kv_in = x if kv_x is None else kv_x
    q = _split_heads(x @ params["wq"], cfg.n_heads, hd)
    k = _split_heads(kv_in @ params["wk"], cfg.n_kv_heads, hd)
    v = _split_heads(kv_in @ params["wv"], cfg.n_kv_heads, hd)
    if use_rope and kv_x is None:
        if cfg.rope_type == "mrope" and positions_3d is not None:
            q = apply_mrope(q, positions_3d, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions_3d, cfg.rope_theta, cfg.mrope_sections)
        elif cfg.rope_type in ("rope", "mrope"):
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    # grouped-query attention without materializing repeated K/V
    q = q.reshape(*q.shape[:-2], cfg.n_kv_heads, n_rep, hd)
    scores = jnp.einsum("bqkrd,bmkd->bkrqm", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    if causal or window > 0:
        mask = attention_scores_mask(scores.shape[-2], scores.shape[-1], window, causal)
        scores = scores + mask[None, None, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkrqm,bmkd->bqkrd", probs, v)
    out = out.reshape(*x.shape[:-1], cfg.n_heads * hd)
    return out @ params["wo"]


def cached_attention_step(
    params,
    x,            # [B, 1, D]
    cache_k,      # [B, max_len, n_kv, hd]
    cache_v,
    index,        # scalar int32 write position, or [B] per-row positions
    cfg: ModelConfig,
    *,
    window: int = 0,
    positions_3d=None,
):
    """One decode step with a KV cache; returns (out, cache_k, cache_v).

    ``index`` may be a scalar (lock-step decode: the whole batch sits at one
    position) or a ``[B]`` vector (continuous batching: every cache row is an
    independent sequence at its own decode position).
    """
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    per_row = jnp.ndim(index) == 1
    q = _split_heads(x @ params["wq"], cfg.n_heads, hd)          # [B,1,H,hd]
    k = _split_heads(x @ params["wk"], cfg.n_kv_heads, hd)
    v = _split_heads(x @ params["wv"], cfg.n_kv_heads, hd)
    if per_row:
        pos = index.astype(jnp.int32).reshape(B, 1)
    else:
        pos = jnp.full((B, 1), index, dtype=jnp.int32)
    if cfg.rope_type == "mrope" and positions_3d is not None:
        q = apply_mrope(q, positions_3d, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions_3d, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope_type in ("rope", "mrope"):
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    if per_row:
        rows = jnp.arange(B)
        cache_k = cache_k.at[rows, pos[:, 0]].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, pos[:, 0]].set(v[:, 0].astype(cache_v.dtype))
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), index, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), index, axis=1)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    # grouped-query decode: score directly against the packed KV cache
    q = q.reshape(B, 1, cfg.n_kv_heads, n_rep, hd)
    scores = jnp.einsum("bqkrd,bmkd->bkrqm", q, cache_k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    kpos = jnp.arange(cache_k.shape[1])
    ok = kpos[None, :] <= pos            # [B, M] (broadcasts on the scalar path)
    if window > 0:
        ok &= kpos[None, :] > pos - window
    scores = jnp.where(ok[:, None, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkrqm,bmkd->bqkrd", probs, cache_v)
    out = out.reshape(B, 1, cfg.n_heads * hd) @ params["wo"]
    return out, cache_k, cache_v


def cached_attention_chunk(
    params,
    x,            # [B, C, D]: one prompt chunk
    cache_k,      # [B, max_len, n_kv, hd]
    cache_v,
    offset,       # scalar int32: absolute position of the chunk's first token
    cfg: ModelConfig,
    *,
    window: int = 0,
):
    """Chunked-prefill attention: C prompt tokens at absolute positions
    [offset, offset+C) attend causally to earlier chunks already in the cache
    plus themselves.  Returns (out [B, C, D'], cache_k, cache_v).

    Cache contents at positions > the current query position are masked out,
    so stale K/V left behind by a slot's previous occupant is never attended.
    """
    hd = cfg.resolved_head_dim
    B, C = x.shape[:2]
    q = _split_heads(x @ params["wq"], cfg.n_heads, hd)          # [B,C,H,hd]
    k = _split_heads(x @ params["wk"], cfg.n_kv_heads, hd)
    v = _split_heads(x @ params["wv"], cfg.n_kv_heads, hd)
    pos = offset + jnp.arange(C, dtype=jnp.int32)                # [C]
    posb = jnp.broadcast_to(pos[None, :], (B, C))
    if cfg.rope_type in ("rope", "mrope"):
        q = apply_rope(q, posb, cfg.rope_theta)
        k = apply_rope(k, posb, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), offset, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), offset, axis=1)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    q = q.reshape(B, C, cfg.n_kv_heads, n_rep, hd)
    scores = jnp.einsum("bqkrd,bmkd->bkrqm", q, cache_k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    kpos = jnp.arange(cache_k.shape[1])
    ok = kpos[None, :] <= pos[:, None]                           # [C, M]
    if window > 0:
        ok &= kpos[None, :] > pos[:, None] - window
    scores = jnp.where(ok[None, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkrqm,bmkd->bqkrd", probs, cache_v)
    out = out.reshape(B, C, cfg.n_heads * hd) @ params["wo"]
    return out, cache_k, cache_v


def cached_cross_attention_step(params, x, cross_k, cross_v, cfg: ModelConfig):
    """Decode-time cross attention against precomputed encoder K/V."""
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    q = _split_heads(x @ params["wq"], cfg.n_heads, hd)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    kk, vv = _repeat_kv(cross_k, n_rep), _repeat_kv(cross_v, n_rep)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) / math.sqrt(hd)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
    return out.reshape(B, 1, cfg.n_heads * hd) @ params["wo"]
