"""Compile the main-path Pallas kernels for a TPU v5e, without the chip.

Interpret mode accepts kernels the TPU compiler (Mosaic) refuses, so the
CPU differential suites cannot show that a kernel lowers.  These tests
compile each kernel of the served path with ``interpret=False`` against a
described ``v5e:2x2`` topology, at bench widths, and check that the
compiled program holds the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described in a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine_dense as ed
from repro.kernels.fused_check.ops import (fused_check_gathered_prefix2,
                                           fused_check_packed)
from repro.kernels.fused_select.ops import (fused_select_gathered_prefix,
                                            fused_select_packed)
from repro.kernels.resident_pool.ops import resident_pool_segment
from repro.kernels.resident_step.ops import resident_segment

# (rows, words): bench-suite buckets, the large suite, and the cumbe-16k
# adjacency (row-tiled by plan_blocks)
STEP_SHAPES = [(256, 16), (512, 48), (1024, 128), (2048, 64), (16384, 512)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile is written to the persistent cache but cannot be read
    # back without a chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_spec(sharding, tree, lead=()):
    return jax.tree.map(
        lambda leaf: _spec(sharding, lead + leaf.shape, leaf.dtype), tree)


@pytest.mark.parametrize("n,w", STEP_SHAPES)
def test_fused_select_packed_compiles(one_chip, n, w):
    _compile(lambda a, m, p: fused_select_packed(
        a, m, p, impl="pallas", interpret=False),
        _spec(one_chip, (n, w), jnp.uint32),
        _spec(one_chip, (w,), jnp.uint32),
        _spec(one_chip, (n // 32,), jnp.uint32))


@pytest.mark.parametrize("n,w", STEP_SHAPES)
def test_fused_check_packed_compiles(one_chip, n, w):
    _compile(lambda a, m, k, q, p: fused_check_packed(
        a, m, k, q, p, impl="pallas", interpret=False, with_counts=True),
        _spec(one_chip, (n, w), jnp.uint32),
        _spec(one_chip, (w,), jnp.uint32),
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (n // 32,), jnp.uint32),
        _spec(one_chip, (n // 32,), jnp.uint32))


@pytest.mark.parametrize("n,w", STEP_SHAPES)
def test_fused_select_gathered_prefix_compiles(one_chip, n, w):
    _compile(lambda a, i, m, p: fused_select_gathered_prefix(
        a, i, m, p, impl="pallas", interpret=False),
        _spec(one_chip, (n, w), jnp.uint32),
        _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, (w,), jnp.uint32),
        _spec(one_chip, (), jnp.int32))


@pytest.mark.parametrize("n,w", STEP_SHAPES)
def test_fused_check_gathered_prefix2_compiles(one_chip, n, w):
    _compile(lambda a, i, m, k, q, p: fused_check_gathered_prefix2(
        a, i, m, k, q, p, impl="pallas", interpret=False),
        _spec(one_chip, (n, w), jnp.uint32),
        _spec(one_chip, (2 * n,), jnp.int32),
        _spec(one_chip, (w,), jnp.uint32),
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (), jnp.int32))


def _resident_case(n_u, n_v, order_mode="deg"):
    cfg = ed.EngineConfig(n_u=n_u, n_v=n_v, m_real=n_u, depth=n_u + 2,
                          order_mode=order_mode, kernel_impl="pallas")
    ctx = jax.eval_shape(lambda: ed.GraphContext(
        adj=jnp.zeros((cfg.n_u, cfg.wv), jnp.uint32),
        order=jnp.zeros((cfg.n_u,), jnp.int32),
        rank=jnp.zeros((cfg.n_u,), jnp.int32),
        l_root=jnp.zeros((cfg.wv,), jnp.uint32),
        root_counts=jnp.zeros((cfg.n_u,), jnp.int32)))
    state = jax.eval_shape(
        lambda: ed.init_state(cfg, np.arange(cfg.n_u, dtype=np.int32)))
    return cfg, ctx, state


# marvel-like's pow2 bucket in every order mode, plus the large suite's
# bucket — the largest the residency gate admits on the single-lane path
@pytest.mark.parametrize("n_u,n_v,order_mode", [
    (256, 512, "deg"), (256, 512, "deg_nocache"), (256, 512, "input"),
    (1024, 4096, "deg")])
def test_resident_segment_compiles(one_chip, n_u, n_v, order_mode):
    cfg, ctx, state = _resident_case(n_u, n_v, order_mode)
    assert cfg.resident_active
    _compile(lambda g, s: resident_segment(
        g, cfg, s, start=0, budget=1 << 30, steps_per_call=8,
        interpret=False),
        _tree_spec(one_chip, ctx), _tree_spec(one_chip, state))


# marvel-like x 4 lanes, and the largest bucket the pool gate admits
@pytest.mark.parametrize("n_u,n_v", [(256, 512), (512, 2048)])
@pytest.mark.parametrize("ctx_batched", [False, True])
def test_resident_pool_segment_compiles(one_chip, n_u, n_v, ctx_batched):
    lanes = 4
    cfg, ctx, state = _resident_case(n_u, n_v)
    assert ed.pool_lanes(cfg, lanes) == lanes
    _compile(lambda g, s: resident_pool_segment(
        g, cfg, s, start=0, budget=1 << 30, steps_per_call=8,
        ctx_batched=ctx_batched, interpret=False),
        _tree_spec(one_chip, ctx, (lanes,) if ctx_batched else ()),
        _tree_spec(one_chip, state, (lanes,)))
