"""Scalar-quantized (int8) scoring kernel (paper §3.3.2 SQ path).

The SQ index stores the corpus as int8 codes + a per-dimension fp32 scale —
4× less HBM traffic than fp32 vectors, which is the whole point of SQ on a
bandwidth-bound search.  The kernel folds the dequantization into the query:
``score = (q ⊙ scale) · codesᵀ`` — codes are upcast int8→f32 *in VMEM* right
before the MXU contraction, so HBM only ever sees the 1-byte codes.

Tiling matches topk_search: query rows stay resident, corpus code tiles
(bn × d, int8 = bn·d bytes) stream through VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import HIGHEST


def _quant_score_kernel(qs_ref, codes_ref, out_ref):
    qs = qs_ref[...]                                   # [bq, d] f32 (prescaled)
    codes = codes_ref[...].astype(jnp.float32)         # [bn, d] int8 -> f32
    # HIGHEST: Mosaic's default f32 contraction is not full f32 on TPU
    out_ref[...] = jax.lax.dot_general(
        qs, codes, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)            # [bq, bn]


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def quant_score_pallas(q, codes, scale, *, bq: int = 128, bn: int = 1024,
                       interpret: bool = True):
    """q:[nq,d] f32, codes:[N,d] int8, scale:[d] -> scores [nq,N] f32."""
    nq, d = q.shape
    N = codes.shape[0]
    qs = q * scale[None, :]
    nq_p = -(-nq // bq) * bq
    n_p = -(-N // bn) * bn
    qp = jnp.pad(qs, ((0, nq_p - nq), (0, 0)))
    cp = jnp.pad(codes, ((0, n_p - N), (0, 0)))
    out = pl.pallas_call(
        _quant_score_kernel,
        grid=(nq_p // bq, n_p // bn),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq_p, n_p), jnp.float32),
        interpret=interpret,
    )(qp, cp)
    return out[:nq, :N]
