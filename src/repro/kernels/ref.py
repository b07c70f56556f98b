"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = jnp.float32(-3.0e38)
# every retrieve score is an f32 dot product: a TPU's default-precision f32
# dot rounds its operands to bf16, which would reorder near-equal scores
HIGHEST = jax.lax.Precision.HIGHEST

# Cross-mode contract of every retrieve rung (off / op / fused) in every
# kernel mode (compiled Pallas, interpret, XLA): the same ids in the same
# order, no live id twice, and scores equal within SCORE_RTOL of the row's
# largest |score|.  Scores are not bit-equal because each lowering orders
# the f32 reductions (the d-term dot products, the m-term PQ sums)
# differently: that moves a score by ~sqrt(d) ulps, about 2e-6 of its size
# at d=768.
SCORE_RTOL = 1e-5


def topk_mismatch(s_a, i_a, s_b, i_b, rtol: float = SCORE_RTOL):
    """Check two top-k results ``([nq, k] scores, [nq, k] ids)`` against
    the cross-mode contract; returns ``None`` or the first violation."""
    s_a, i_a = np.asarray(s_a, np.float64), np.asarray(i_a)
    s_b, i_b = np.asarray(s_b, np.float64), np.asarray(i_b)
    for r in range(s_a.shape[0]):
        if not (i_a[r] == i_b[r]).all():
            return f"row {r}: ids differ: {i_a[r]} vs {i_b[r]}"
        live = i_a[r] >= 0
        if np.unique(i_a[r][live]).size != live.sum():
            return f"row {r}: an id appears twice: {i_a[r]}"
        gap = np.abs(s_a[r][live] - s_b[r][live])
        tol = rtol * max(np.abs(s_a[r][live]).max(initial=0.0),
                         np.abs(s_b[r][live]).max(initial=0.0))
        if (gap > tol).any():
            return (f"row {r}: scores differ by {gap.max():.3g} > {tol:.3g}: "
                    f"{s_a[r]} vs {s_b[r]}")
    return None


def topk_search(q, vecs, live, k: int):
    """Exact similarity top-k.  q:[nq,d] vecs:[N,d] live:[N] bool.

    Returns (scores [nq,k], idx [nq,k] int32).  Rows with fewer than ``k``
    live entries pad with ``(NEG, -1)`` — the same contract as
    ``topk_search_pallas`` (previously this oracle leaked the raw
    ``lax.top_k`` position of a masked row, so results were
    mode-dependent: id ``-1`` under pallas/interpret but a garbage dead
    slot under ``REPRO_KERNEL_MODE=xla``).
    """
    scores = jnp.dot(q, vecs.T, precision=HIGHEST)
    scores = jnp.where(live[None, :], scores, NEG)
    top, idx = jax.lax.top_k(scores, k)
    return top, jnp.where(top <= NEG / 2, -1, idx)


def quant_score(q, codes, scale):
    """SQ-int8 scoring.  q:[nq,d] f32, codes:[N,d] int8, scale:[d] f32.

    score[i,j] = sum_d q[i,d] * codes[j,d] * scale[d]
    """
    qs = q * scale[None, :]
    return jnp.dot(qs, codes.astype(jnp.float32).T, precision=HIGHEST)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None):
    """Reference attention.  q:[B,H,S,dh], k/v:[B,Hkv,S,dh] (GQA repeat).

    Returns [B,H,S,dh].
    """
    B, H, S, dh = q.shape
    hkv = k.shape[1]
    rep = H // hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = scale if scale is not None else 1.0 / math.sqrt(dh)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * s
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
