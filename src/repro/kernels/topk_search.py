"""Fused similarity × top-k retrieval kernel (the retrieval-stage hot loop).

The flat / temp-flat search computes ``q @ vecs.T`` and immediately reduces
it to k winners.  Materializing the full ``[nq, N]`` score matrix in HBM
costs 4·nq·N bytes of write+read traffic that the MXU result never needs.
The kernel streams corpus tiles HBM→VMEM, scores a ``[bq, bn]`` tile on the
MXU, and reduces it *in VMEM* to a per-tile top-k; only ``[nq, n_tiles, k]``
candidates (≪ [nq, N]) ever reach HBM.  A cheap ``lax.top_k`` merge outside
the kernel produces the global winners.

Tiling: bq rows of queries stay VMEM-resident across the whole sweep of a
corpus tile; corpus tiles are (bn, d) with bn a multiple of 128 (lane dim) so
the q·cᵀ contraction is MXU-aligned.  The corpus may be f32 vectors or int8
codes (upcast in VMEM; the SQ-int8 rung folds its scale into the query).

TPU block rules (Mosaic): the last two dims of every block are multiples of
(8, 128) or equal to the array's.  So liveness is a ``[1, N]`` row read in
``(1, bn)`` blocks, and each tile's k winners land in a lane-dense
``(bq, KP)`` output block (KP = k rounded up to 128 lanes), written once
per tile; round ``t`` of the selection fills lane ``t``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import HIGHEST

NEG = -3.0e38


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def lanes(k: int) -> int:
    """Candidate-row width: k rounded up to whole 128-lane vregs."""
    return round_up(k, 128)


def select_topk(scores, k: int, ids=None, base=0):
    """k rounds of (max, first argmax, mask) over ``scores`` [r, n] in VMEM.

    Returns lane-dense ``([r, KP] scores, [r, KP] ids)``: round ``t`` fills
    lane ``t``; lanes ``>= k`` hold ``(NEG, -1)``.  A winner's id is
    ``base + column``, or ``ids[0, column]`` when an ``ids`` row [1, n] is
    given.  Ties go to the lowest column, as in ``lax.top_k``; the id is
    picked by a masked reduction, never by a scalar read from a vector.
    """
    r, n = scores.shape
    kp = lanes(k)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, n), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, kp), 1)

    def body(t, carry):
        sc, out_s, out_i = carry
        m = jnp.max(sc, axis=1, keepdims=True)                   # [r, 1]
        am = jnp.min(jnp.where(sc == m, col, n), axis=1, keepdims=True)
        hit = col == am
        if ids is None:
            win = base + am
        else:
            win = jnp.max(jnp.where(hit, ids, -1), axis=1, keepdims=True)
        out_s = jnp.where(lane == t, m, out_s)
        out_i = jnp.where(lane == t, win, out_i)
        return jnp.where(hit, NEG, sc), out_s, out_i

    init = (scores, jnp.full((r, kp), NEG, jnp.float32),
            jnp.full((r, kp), -1, jnp.int32))
    _, out_s, out_i = jax.lax.fori_loop(0, k, body, init)
    return out_s, out_i


def _topk_tile_kernel(q_ref, vecs_ref, live_ref, out_s_ref, out_i_ref, *,
                      k: int, bn: int):
    """One grid step: score one (bq × bn) tile, emit its local top-k."""
    j = pl.program_id(1)                         # corpus-tile index
    q = q_ref[...]                               # [bq, d]   (VMEM)
    vt = vecs_ref[...].astype(jnp.float32)       # [bn, d]   (int8 codes upcast)
    scores = jax.lax.dot_general(
        q, vt, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)      # [bq, bn] on the MXU
    scores = jnp.where(live_ref[...] != 0, scores, NEG)   # live: [1, bn]
    out_s_ref[...], out_i_ref[...] = select_topk(scores, k, base=j * bn)


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret"))
def topk_search_pallas(q, vecs, live, k: int, *, bq: int = 128, bn: int = 1024,
                       interpret: bool = True):
    """q:[nq,d] vecs:[N,d] (f32, or int8 codes) live:[N]
    -> (scores [nq,k], idx [nq,k]) with (NEG, -1) padding."""
    nq, d = q.shape
    N = vecs.shape[0]
    bq = min(bq, round_up(nq, 8))
    bn = min(bn, round_up(N, 128))
    kp = lanes(k)
    # pad to tile multiples
    nq_p = round_up(nq, bq)
    n_p = round_up(N, bn)
    qp = jnp.pad(q, ((0, nq_p - nq), (0, 0)))
    vp = jnp.pad(vecs, ((0, n_p - N), (0, 0)))
    lp = jnp.pad(live.astype(jnp.int32), (0, n_p - N))[None, :]
    nt = n_p // bn

    out_s, out_i = pl.pallas_call(
        functools.partial(_topk_tile_kernel, k=k, bn=bn),
        grid=(nq_p // bq, nt),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, kp), lambda i, j: (i, j)),
            pl.BlockSpec((bq, kp), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq_p, nt * kp), jnp.float32),
            jax.ShapeDtypeStruct((nq_p, nt * kp), jnp.int32),
        ],
        interpret=interpret,
    )(qp, vp, lp)
    return merge_tiles(out_s[:nq], out_i[:nq], nt, k)


def merge_candidates(cand_s, cand_i, k: int):
    """Global top-k over per-tile/per-bucket candidates.

    ``cand_s``/``cand_i``: ``[nq, C]`` candidate scores/ids in tile-major,
    rank-minor order (ties therefore resolve exactly as a flat
    ``lax.top_k`` over the unfused score matrix would).  Pads with
    ``(NEG, -1)`` when ``C < k``.
    """
    nq, c = cand_s.shape
    if c < k:
        cand_s = jnp.pad(cand_s, ((0, 0), (0, k - c)), constant_values=NEG)
        cand_i = jnp.pad(cand_i, ((0, 0), (0, k - c)), constant_values=-1)
    top, pos = jax.lax.top_k(cand_s, k)
    idx = jnp.take_along_axis(cand_i, pos, axis=1)
    return top, jnp.where(top <= NEG / 2, -1, idx)


def merge_tiles(out_s, out_i, n_tiles: int, k: int):
    """``merge_candidates`` over the kernels' lane-dense candidate rows
    ``[nq, n_tiles*KP]``: the first k lanes of each tile's row."""
    nq = out_s.shape[0]
    kp = out_s.shape[1] // n_tiles
    return merge_candidates(
        out_s.reshape(nq, n_tiles, kp)[:, :, :k].reshape(nq, -1),
        out_i.reshape(nq, n_tiles, kp)[:, :, :k].reshape(nq, -1), k)
