"""Compile-only checks for the chip: the four retrieve kernels at real widths
and one full-width phi4-mini decode step, compiled for a described TPU v5e
(no chip attached; nothing runs).  Interpret mode cannot show what the TPU
compiler refuses — block shapes off the (8, 128) tiling, scalar reads from
vectors, lowerings Mosaic lacks, programs larger than the chip's memory.

The topology is described inside a fixture (never at import): only one
process may hold the TPU library, and every test worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import fused_retrieve as fr
from repro.kernels.topk_search import topk_search_pallas
from repro.models import api

D, N, NQ, K = 768, 1 << 20, 64, 10
NLIST, CAP_B, NPROBE, PQ_M = 1024, 1024, 16, 8
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be cached but not read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    return compiled


def test_topk_search_compiles_for_v5e(shape):
    _compile_kernel(
        functools.partial(topk_search_pallas, k=K, interpret=False),
        shape((NQ, D), jnp.float32), shape((N, D), jnp.float32),
        shape((N,), jnp.bool_))


def test_sq8_topk_compiles_for_v5e(shape):
    _compile_kernel(
        functools.partial(fr.sq8_topk_pallas, k=K, interpret=False),
        shape((NQ, D), jnp.float32), shape((N, D), jnp.int8),
        shape((D,), jnp.float32), shape((N,), jnp.bool_))


def test_ivf_topk_compiles_for_v5e(shape):
    rows = NLIST * CAP_B
    _compile_kernel(
        functools.partial(fr.ivf_topk_pallas, nprobe=NPROBE, k=K,
                          interpret=False),
        shape((NQ, D), jnp.float32), shape((NLIST, D), jnp.float32),
        shape((rows, D), jnp.float32), shape((1, rows), jnp.int32),
        shape((1, rows), jnp.int32))


def test_pq_topk_compiles_for_v5e(shape):
    rows = NLIST * CAP_B
    _compile_kernel(
        functools.partial(fr.pq_topk_pallas, nprobe=NPROBE, k=K,
                          interpret=False),
        shape((NQ, D), jnp.float32),
        shape((PQ_M, 256, D // PQ_M), jnp.float32),
        shape((NLIST, D), jnp.float32), shape((PQ_M, rows), jnp.int32),
        shape((1, rows), jnp.int32), shape((1, rows), jnp.int32))


def test_phi4_mini_decode_step_fits_one_v5e(shape):
    """The engine's decode step at full width (4 slots, 1,024-token prompts
    + 32 new tokens) compiles for one chip and fits its memory."""
    cfg = configs.get_config("phi4_mini_3_8b")
    model = api.get_model(cfg)
    on_chip = lambda tree: jax.tree.map(                   # noqa: E731
        lambda s: shape(s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(cfg, 4, 1056)))
    cache["pos"] = shape((4,), jnp.int32)
    compiled = jax.jit(functools.partial(model.decode_step, cfg=cfg)).lower(
        params, batch={"tokens": shape((4, 1), jnp.int32)},
        cache=cache).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
