"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod = 16×16 (256 chips, TPU v5e pod slice);
multi-pod adds a leading "pod" axis (2×16×16 = 512 chips).  DP spans
("pod","data") so scaling to N pods grows only the pod axis; TP stays
intra-pod where ICI bandwidth is (DESIGN.md §6).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Single-device mesh for CPU smoke runs."""
    return make_mesh((1, 1), ("data", "model"))
