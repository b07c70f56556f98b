"""Public jit'd wrappers for the Pallas kernels.

Each op dispatches on the runtime platform:
  * TPU      — compiled Pallas kernel (the target path);
  * CPU      — ``interpret=True`` Pallas (correctness validation), or the
               pure-XLA fallback when ``REPRO_KERNEL_MODE=xla`` (fast for
               large benchmark runs, identical semantics).

The dry-run always lowers the XLA fallback: host-CPU placeholder devices
cannot lower real Mosaic kernels, and the roofline terms come from HLO cost
analysis which the fallback represents faithfully.

Dispatch contract (the "kernel-dispatch" invariants pinned by
``tests/test_kernels.py``):

* ``REPRO_KERNEL_MODE`` must be one of ``pallas`` / ``interpret`` / ``xla``;
  anything else raises immediately instead of silently falling back to the
  slowest (interpret) path.
* Every op accepts an explicit ``mode=`` override.  Callers that embed an op
  inside their own ``jax.jit`` (the vector DB search primitives) MUST resolve
  ``kernel_mode()`` *outside* the traced function and pass it through as a
  static argument — an environment read at trace time would be baked into the
  jit cache and a later ``REPRO_KERNEL_MODE`` change would silently not take
  effect for already-traced shapes.
* All modes of one op meet the cross-mode contract of
  ``repro.kernels.ref.topk_mismatch`` (equal ids, scores equal up to f32
  reduction order), including the documented ``(NEG, -1)`` padding for
  rows with fewer than ``k`` live matches.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_retrieve as _fr
from repro.kernels import quant_score as _qs
from repro.kernels import ref
from repro.kernels import topk_search as _ts

KERNEL_MODES = ("pallas", "interpret", "xla")


def kernel_mode() -> str:
    """Resolve the active kernel mode (validated).

    ``REPRO_KERNEL_MODE`` wins when set; otherwise ``pallas`` on TPU and
    ``interpret`` elsewhere.  Unrecognized values (e.g. ``XLA``, a typo) used
    to be treated as interpret mode — the slowest path — with no warning;
    now they raise naming the allowed values.
    """
    env = os.environ.get("REPRO_KERNEL_MODE")
    if env:
        if env not in KERNEL_MODES:
            raise ValueError(
                f"invalid REPRO_KERNEL_MODE={env!r}; allowed values: "
                f"{', '.join(KERNEL_MODES)}")
        return env
    platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "interpret"


# back-compat alias (pre-validation name)
_mode = kernel_mode


def _resolve(mode) -> str:
    if mode is None:
        return kernel_mode()
    if mode not in KERNEL_MODES:
        raise ValueError(f"invalid kernel mode {mode!r}; allowed values: "
                         f"{', '.join(KERNEL_MODES)}")
    return mode


def topk_search(q, vecs, live, k: int, *, mode: str | None = None):
    mode = _resolve(mode)
    if mode == "xla":
        return ref.topk_search(q, vecs, live, k)
    return _ts.topk_search_pallas(q, vecs, live, k,
                                  interpret=(mode != "pallas"))


def quant_score(q, codes, scale, *, mode: str | None = None):
    mode = _resolve(mode)
    if mode == "xla":
        return ref.quant_score(q, codes, scale)
    return _qs.quant_score_pallas(q, codes, scale,
                                  interpret=(mode != "pallas"))


def flash_attention(q, k, v, *, causal: bool = True, mode: str | None = None):
    mode = _resolve(mode)
    if mode == "xla":
        return ref.flash_attention(q, k, v, causal=causal)
    return _fa.flash_attention_pallas(q, k, v, causal=causal,
                                      interpret=(mode != "pallas"))


# -- fused retrieve backend (probe -> score -> select, one launch) ----------


def fused_flat_topk(q, vecs, live, k: int, *, mode: str | None = None):
    """Fused exact scan: one launch per query micro-batch, candidate score
    matrices never materialized in HBM."""
    mode = _resolve(mode)
    if mode == "xla":
        return _fr.flat_topk_xla(q, vecs, live, k)
    return _ts.topk_search_pallas(q, vecs, live, k,
                                  interpret=(mode != "pallas"))


def fused_sq8_topk(q, codes, scale, live, k: int, *, mode: str | None = None):
    """Fused SQ-int8 scan: dequant-score + select in VMEM (codes stream
    through HBM once; the ``[nq, N]`` score matrix never exists)."""
    mode = _resolve(mode)
    if mode == "xla":
        return _fr.sq8_topk_xla(q, codes, scale, live, k)
    return _fr.sq8_topk_pallas(q, codes, scale, live, k,
                               interpret=(mode != "pallas"))


def fused_ivf_topk(q, cent, packed_vecs, packed_slot, packed_ok,
                   nprobe: int, k: int, *, mode: str | None = None):
    """Fused IVF probe -> bucket score -> select over the packed
    (bucket-contiguous) corpus mirror."""
    mode = _resolve(mode)
    if mode == "xla":
        return _fr.ivf_topk_xla(q, cent, packed_vecs, packed_slot, packed_ok,
                                nprobe, k)
    return _fr.ivf_topk_pallas(q, cent, packed_vecs, packed_slot, packed_ok,
                               nprobe, k, interpret=(mode != "pallas"))


def fused_pq_topk(q, codebook, cent, packed_codes, packed_slot, packed_ok,
                  nprobe: int, k: int, *, mode: str | None = None):
    """Fused PQ-ADC probe -> LUT score -> select over packed bucket codes."""
    mode = _resolve(mode)
    if mode == "xla":
        return _fr.pq_topk_xla(q, codebook, cent, packed_codes, packed_slot,
                               packed_ok, nprobe, k)
    return _fr.pq_topk_pallas(q, codebook, cent, packed_codes, packed_slot,
                              packed_ok, nprobe, k,
                              interpret=(mode != "pallas"))
