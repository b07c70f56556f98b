"""Fused retrieve backend: probe → (dequant-)score → top-k in one kernel.

Motivation (ROADMAP item 2): the unfused ladder in ``repro.core.vectordb``
computes full candidate score matrices and reduces them afterwards —
``_sq8_flat_search`` runs ``quant_score`` over the whole corpus and hands a
``[nq, N]`` matrix to ``lax.top_k``, and ``_ivf_search``/``_pq_ivf_search``
gather ``[nq, nprobe, cap_b, d]`` candidate tensors before a flattened
top-k.  On a bandwidth-bound search those intermediate HBM round-trips are
the dominant cost: the corpus bytes must stream through HBM exactly once,
everything else is overhead (see ``repro.roofline.retrieve`` for the bytes
model the benchmark gate checks against).

The fused kernels keep every intermediate in VMEM:

* **flat / sq8** — corpus (or int8 code) tiles stream HBM→VMEM, are scored
  on the MXU against the resident query block (codes upcast int8→f32 in
  VMEM), and reduced *in VMEM* to a per-tile top-k by ``k`` rounds of
  (max, argmax, mask).  Only ``[nq, n_tiles, k]`` candidates (≪ ``[nq, N]``)
  reach HBM; a cheap ``lax.top_k`` merge outside the kernel produces the
  global winners.
* **ivf / pq** — the vector DB maintains a *bucket-contiguous packed
  mirror* of the corpus (built at ``build_index`` time: bucket ``b`` owns
  rows ``[b·cap_b, (b+1)·cap_b)``).  Centroid scoring + top-``nprobe``
  probe selection is a tiny ``[nq, nlist]`` XLA prologue whose winners feed
  the kernel as a *scalar-prefetch* operand: grid step ``(i, p)`` DMAs
  exactly the probed bucket's block into VMEM via the prefetched index map,
  scores it against query ``i`` (PQ: ADC lookup in the per-query LUT,
  resident in VMEM, as one-hot matmuls), and selects the bucket-local
  top-k.  Buckets hold a multiple of 128 rows, and the mirror keeps the
  kernels' layout (``[1, rows]`` slot ids, ``[m, rows]`` PQ codes) so no
  search converts it.  The ``[nq, nprobe, cap_b]`` candidate tensors of
  the unfused path never exist; ``[nq, nprobe, k]`` candidates merge
  outside.

Every kernel is batched over the query axis, so one coalesced retrieve
micro-batch from the elastic executor is a single kernel launch.

Modes: the ``pallas`` variants compile on TPU and validate under
``interpret=True`` on CPU; the ``*_xla`` fallbacks implement the *same
tiled algorithm* (per-tile score → local top-k → merge) with ``lax.scan``
carrying only tile-sized intermediates, so outputs agree across modes
under the contract in ``repro.kernels.ref`` (equal ids, scores equal up to
f32 reduction order) and the CPU benchmark path still avoids materializing
the full matrices.  Dispatch lives in ``repro.kernels.ops``.

Output contract (shared with ``topk_search_pallas``): rows with fewer than
``k`` live matches pad with ``(NEG, -1)`` — masked/dead candidates score
exactly ``NEG`` and any id whose score is ``<= NEG/2`` is replaced by
``-1``, so dead-slot ids never leak into the candidate set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import HIGHEST
from repro.kernels.topk_search import (NEG, lanes, merge_candidates,
                                       merge_tiles, select_topk,
                                       topk_search_pallas)


# ---------------------------------------------------------------------------
# flat / sq8: tile-streamed exact scan
# ---------------------------------------------------------------------------


def sq8_topk_pallas(q, codes, scale, live, k: int, *, bq: int = 128,
                    bn: int = 1024, interpret: bool = True):
    """q:[nq,d] f32, codes:[N,d] int8, scale:[d], live:[N]
    -> (scores [nq,k], idx [nq,k]) with (NEG, -1) padding.

    The flat tile kernel over int8 codes: the scale folds into the query,
    codes upcast int8→f32 in VMEM, so HBM only ever sees the 1-byte codes."""
    return topk_search_pallas(q * scale[None, :], codes, live, k, bq=bq,
                              bn=bn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "bn"))
def _tiled_topk_xla(qs, mat, live, k: int, bn: int):
    """XLA realization of the tile-streamed scan: ``lax.scan`` over corpus
    tiles, per-tile score + local top-k, tile-sized intermediates only."""
    nq = qs.shape[0]
    d = mat.shape[1]
    N = mat.shape[0]
    n_p = -(-N // bn) * bn
    mp = jnp.pad(mat, ((0, n_p - N), (0, 0)))
    lp = jnp.pad(live.astype(bool), (0, n_p - N))
    nt = n_p // bn
    kt = min(k, bn)

    def tile(carry, inp):
        c, l, base = inp
        s = jax.lax.dot_general(
            qs, c.astype(jnp.float32), (((1,), (1,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)  # [nq, bn]
        s = jnp.where(l[None, :], s, NEG)
        ts, tp = jax.lax.top_k(s, kt)
        return carry, (ts, (base + tp).astype(jnp.int32))

    _, (cs, ci) = jax.lax.scan(
        tile, 0,
        (mp.reshape(nt, bn, d), lp.reshape(nt, bn),
         jnp.arange(nt, dtype=jnp.int32) * bn))
    cand_s = jnp.moveaxis(cs, 0, 1).reshape(nq, nt * kt)
    cand_i = jnp.moveaxis(ci, 0, 1).reshape(nq, nt * kt)
    return merge_candidates(cand_s, cand_i, k)


def flat_topk_xla(q, vecs, live, k: int, *, bn: int = 1024):
    """Fused-equivalent exact scan (f32 corpus), XLA fallback."""
    return _tiled_topk_xla(q, vecs, live, k, bn)


def sq8_topk_xla(q, codes, scale, live, k: int, *, bn: int = 1024):
    """Fused-equivalent SQ-int8 scan, XLA fallback: int8 tiles upcast
    per-tile (cache-resident) instead of materializing the f32 corpus."""
    return _tiled_topk_xla(q * scale[None, :], codes, live, k, bn)


# ---------------------------------------------------------------------------
# ivf / pq: scalar-prefetched bucket probe over the packed mirror
# ---------------------------------------------------------------------------


def _ivf_bucket_kernel(probe_ref, q_ref, vecs_ref, ok_ref, slot_ref,
                       out_s_ref, out_i_ref, *, k: int):
    """Grid step (i, p): score query i against its p-th probed bucket and
    emit the bucket-local top-k as one lane-dense row."""
    del probe_ref                     # consumed by the index maps
    scores = jax.lax.dot_general(
        q_ref[...], vecs_ref[...], (((1,), (1,)), ((), ())),
        precision=HIGHEST,
        preferred_element_type=jnp.float32)            # [1, d]·[cap_b, d]ᵀ
    scores = jnp.where(ok_ref[...] != 0, scores, NEG)  # ok: [1, cap_b]
    out_s_ref[...], out_i_ref[...] = select_topk(scores, k, ids=slot_ref[...])


def _probe(q, cent, nprobe: int):
    """Tiny XLA prologue: centroid scores -> top-nprobe bucket ids.

    Identical arithmetic to the unfused ``_ivf_search`` probe, so the
    fused path scores exactly the same buckets."""
    _, probe = jax.lax.top_k(jnp.dot(q, cent.T, precision=HIGHEST), nprobe)
    return probe.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "interpret"))
def ivf_topk_pallas(q, cent, packed_vecs, packed_slot, packed_ok,
                    nprobe: int, k: int, *, interpret: bool = True):
    """IVF probe→score→select over the packed mirror, one launch.

    q:[nq,d]; cent:[nlist,d]; packed_vecs:[nlist*cap_b,d];
    packed_slot/packed_ok:[1, nlist*cap_b] int32 (slot id / liveness of
    each packed row, -1 / 0 for pads and tombstones); cap_b a multiple of
    128.

    Each grid step reads one query as a ``(1, d)`` block of ``[nq, 1, d]``:
    bucket membership differs per query, so the MXU tile is inherently
    narrow — the win is bandwidth.
    """
    nq, d = q.shape
    nlist = cent.shape[0]
    cap_b = packed_vecs.shape[0] // nlist
    kp = lanes(k)
    probe = _probe(q, cent, nprobe)
    row = lambda i, p, probe: (0, probe[i, p])           # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, nprobe),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda i, p, probe: (i, 0, 0)),
            pl.BlockSpec((cap_b, d), lambda i, p, probe: (probe[i, p], 0)),
            pl.BlockSpec((1, cap_b), row),
            pl.BlockSpec((1, cap_b), row),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, kp), lambda i, p, probe: (i, 0, p)),
            pl.BlockSpec((None, 1, kp), lambda i, p, probe: (i, 0, p)),
        ],
    )
    out_s, out_i = pl.pallas_call(
        functools.partial(_ivf_bucket_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1, nprobe * kp), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1, nprobe * kp), jnp.int32),
        ],
        interpret=interpret,
    )(probe, q[:, None, :], packed_vecs, packed_ok, packed_slot)
    return merge_tiles(out_s[:, 0], out_i[:, 0], nprobe, k)


@functools.partial(jax.jit, static_argnames=("nprobe", "k"))
def ivf_topk_xla(q, cent, packed_vecs, packed_slot, packed_ok,
                 nprobe: int, k: int):
    """XLA fallback: ``lax.scan`` over probes, per-probe bucket gather +
    local top-k — the [nq, nprobe, cap_b, d] tensor never exists."""
    nq, d = q.shape
    nlist = cent.shape[0]
    cap_b = packed_vecs.shape[0] // nlist
    probe = _probe(q, cent, nprobe)
    pv = packed_vecs.reshape(nlist, cap_b, d)
    ps = packed_slot.reshape(nlist, cap_b)
    po = packed_ok.reshape(nlist, cap_b)
    kt = min(k, cap_b)

    def per_probe(carry, p):
        b = probe[:, p]                                # [nq]
        s = jnp.einsum("qd,qbd->qb", q, pv[b], precision=HIGHEST)
        s = jnp.where(po[b] != 0, s, NEG)
        ts, tp = jax.lax.top_k(s, kt)
        return carry, (ts, jnp.take_along_axis(ps[b], tp, axis=1))

    _, (cs, ci) = jax.lax.scan(per_probe, 0,
                               jnp.arange(nprobe, dtype=jnp.int32))
    cand_s = jnp.moveaxis(cs, 0, 1).reshape(nq, nprobe * kt)
    cand_i = jnp.moveaxis(ci, 0, 1).reshape(nq, nprobe * kt)
    return merge_candidates(cand_s, cand_i, k)


def _pq_lut(q, codebook):
    """Per-query ADC lookup tables [nq, m, 256] (identical einsum to the
    unfused ``_pq_ivf_search``)."""
    m, _, dsub = codebook.shape
    nq = q.shape[0]
    return jnp.einsum("qms,mcs->qmc", q.reshape(nq, m, dsub), codebook,
                      precision=HIGHEST)


def _pq_bucket_kernel(probe_ref, lut_ref, codes_ref, ok_ref, slot_ref,
                      out_s_ref, out_i_ref, *, k: int):
    """Grid step (i, p): ADC-score query i's LUT against one bucket's codes.

    The [m, 256] LUT is VMEM-resident.  The lookup is a one-hot matmul per
    subspace — ``lut[j] · onehot(codes[j])`` picks exactly one table entry
    per row on the MXU — summed over subspaces."""
    del probe_ref
    codes = codes_ref[...]                             # [m, cap_b] int32
    m, cap_b = codes.shape
    entry = jax.lax.broadcasted_iota(jnp.int32, (256, cap_b), 0)
    scores = None
    for j in range(m):
        onehot = (entry == codes[j:j + 1, :]).astype(jnp.float32)
        s = jax.lax.dot_general(
            lut_ref[j:j + 1, :], onehot, (((1,), (0,)), ((), ())),
            precision=HIGHEST,
            preferred_element_type=jnp.float32)        # [1, cap_b]
        scores = s if scores is None else scores + s
    scores = jnp.where(ok_ref[...] != 0, scores, NEG)
    out_s_ref[...], out_i_ref[...] = select_topk(scores, k, ids=slot_ref[...])


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "interpret"))
def pq_topk_pallas(q, codebook, cent, packed_codes, packed_slot, packed_ok,
                   nprobe: int, k: int, *, interpret: bool = True):
    """PQ ADC probe→score→select over packed bucket codes, one launch.

    packed_codes:[m, nlist*cap_b] int32, subspace-major, so a probed
    bucket is one lane-dense ``(m, cap_b)`` block; packed_slot/packed_ok
    as in ``ivf_topk_pallas``."""
    nq = q.shape[0]
    m = codebook.shape[0]
    nlist = cent.shape[0]
    cap_b = packed_codes.shape[1] // nlist
    kp = lanes(k)
    lut = _pq_lut(q, codebook)
    probe = _probe(q, cent, nprobe)
    row = lambda i, p, probe: (0, probe[i, p])           # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, nprobe),
        in_specs=[
            pl.BlockSpec((None, m, 256), lambda i, p, probe: (i, 0, 0)),
            pl.BlockSpec((m, cap_b), row),
            pl.BlockSpec((1, cap_b), row),
            pl.BlockSpec((1, cap_b), row),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, kp), lambda i, p, probe: (i, 0, p)),
            pl.BlockSpec((None, 1, kp), lambda i, p, probe: (i, 0, p)),
        ],
    )
    out_s, out_i = pl.pallas_call(
        functools.partial(_pq_bucket_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1, nprobe * kp), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1, nprobe * kp), jnp.int32),
        ],
        interpret=interpret,
    )(probe, lut, packed_codes, packed_ok, packed_slot)
    return merge_tiles(out_s[:, 0], out_i[:, 0], nprobe, k)


@functools.partial(jax.jit, static_argnames=("nprobe", "k"))
def pq_topk_xla(q, codebook, cent, packed_codes, packed_slot, packed_ok,
                nprobe: int, k: int):
    """XLA fallback: scan over probes, per-probe code gather + ADC + local
    top-k — tile-sized intermediates only.

    The ADC lookup indexes a *flattened* per-query ``[m*256]`` table
    (``code + 256*subspace``): one single-axis take_along_axis, which XLA
    CPU lowers ~4x faster than the rank-3 broadcast gather while fetching
    the same values.
    """
    nq = q.shape[0]
    m = codebook.shape[0]
    nlist = cent.shape[0]
    cap_b = packed_codes.shape[1] // nlist
    flat_lut = _pq_lut(q, codebook).reshape(nq, m * 256)
    probe = _probe(q, cent, nprobe)
    pc = packed_codes.reshape(m, nlist, cap_b)
    ps = packed_slot.reshape(nlist, cap_b)
    po = packed_ok.reshape(nlist, cap_b)
    offs = (jnp.arange(m, dtype=packed_codes.dtype) * 256)[:, None, None]
    kt = min(k, cap_b)

    def per_probe(carry, p):
        b = probe[:, p]
        fidx = jnp.moveaxis(pc[:, b] + offs, 0, 1).reshape(nq, m * cap_b)
        gath = jnp.take_along_axis(flat_lut, fidx, axis=1)
        s = gath.reshape(nq, m, cap_b).sum(axis=1)     # [nq, cap_b]
        s = jnp.where(po[b] != 0, s, NEG)
        ts, tp = jax.lax.top_k(s, kt)
        return carry, (ts, jnp.take_along_axis(ps[b], tp, axis=1))

    _, (cs, ci) = jax.lax.scan(per_probe, 0,
                               jnp.arange(nprobe, dtype=jnp.int32))
    cand_s = jnp.moveaxis(cs, 0, 1).reshape(nq, nprobe * kt)
    cand_i = jnp.moveaxis(ci, 0, 1).reshape(nq, nprobe * kt)
    return merge_candidates(cand_s, cand_i, k)
