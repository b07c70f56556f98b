"""Logical-axis sharding: partition rules for params, activations, caches.

Models are written against *logical* axis names ("batch", "seq", "heads",
"ff", "experts", "vocab", ...).  A ``ShardingRules`` context maps logical
names to physical mesh axes; ``constrain`` applies
``jax.lax.with_sharding_constraint`` only when a mesh is active and every
requested dimension is divisible by its mesh-axis size — so the same model
code runs unsharded on one CPU device and fully sharded on a 512-chip mesh.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# default logical -> physical mapping.  "pod" is folded into the batch axes
# when present (multi-pod meshes extend data parallelism across pods).
DEFAULT_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": "model",          # sequence-parallel residuals (SP)
    "embed": None,           # residual feature dim replicated
    "heads": "model",        # TP over attention heads
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",           # TP over MLP hidden
    "experts": "model",      # expert parallelism
    "expert_ff": None,
    "vocab": "model",
    "zero": ("pod", "data"),  # ZeRO-1 optimizer-state sharding axis
    "kv_seq": "model",       # decode-time KV cache sequence sharding
    "corpus": ("pod", "data"),  # vector-db corpus sharding
}


class _State(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Dict[str, Axis] = dict(DEFAULT_RULES)


_STATE = _State()


@contextmanager
def sharding_rules(mesh: Optional[Mesh], rules: Optional[Dict[str, Axis]] = None):
    """Activate a mesh + logical-rule mapping for model code."""
    prev = (_STATE.mesh, _STATE.rules)
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    _STATE.mesh, _STATE.rules = mesh, merged
    try:
        yield
    finally:
        _STATE.mesh, _STATE.rules = prev


def _axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape.get(axis, 1)
    n = 1
    for a in axis:
        n *= mesh.shape.get(a, 1)
    return n


def _filter_axes(mesh: Mesh, axis: Axis) -> Axis:
    """Drop mesh axes that don't exist on this mesh (e.g. 'pod' single-pod)."""
    if axis is None:
        return None
    if isinstance(axis, str):
        return axis if axis in mesh.shape else None
    kept = tuple(a for a in axis if a in mesh.shape)
    return kept if kept else None


def logical_spec(shape: Sequence[int], logical: Sequence[Optional[str]],
                 mesh: Optional[Mesh] = None,
                 rules: Optional[Dict[str, Axis]] = None) -> P:
    """Build a PartitionSpec from logical axis names with divisibility checks."""
    mesh = mesh or _STATE.mesh
    rules = rules or _STATE.rules
    if mesh is None:
        return P(*([None] * len(shape)))
    out = []
    used: set = set()
    for dim, name in zip(shape, logical):
        axis = _filter_axes(mesh, rules.get(name)) if name else None
        if axis is None:
            out.append(None)
            continue
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        axes = tuple(a for a in axes if a not in used)
        size = _axis_size(mesh, axes)
        if size > 1 and dim % size == 0:
            out.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            # try progressively smaller prefixes of the axis tuple
            ok = None
            for k in range(len(axes) - 1, 0, -1):
                sub = axes[:k]
                s = _axis_size(mesh, sub)
                if s > 1 and dim % s == 0:
                    ok = sub if len(sub) > 1 else sub[0]
                    used.update(sub)
                    break
            out.append(ok)
    return P(*out)


def constrain(x, *logical: Optional[str]):
    """with_sharding_constraint on logical axes; no-op without a mesh."""
    mesh = _STATE.mesh
    if mesh is None or np.prod([d for d in mesh.devices.shape]) == 1:
        return x
    spec = logical_spec(x.shape, logical, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))
