"""The LUT kernels compile for a TPU v5e at granite_8b widths.

Each test lowers one kernel for a described (not attached) ``v5e:2x2``
topology and compiles it with the TPU compiler installed beside JAX, so a
block shape or an in-kernel op that Mosaic refuses fails here, on the CPU,
instead of on the chip.  Nothing runs: these are compiles, not chip runs.
The topology is described inside a fixture only (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lut_affine.ops import (
    lut_affine,
    lut_affine_experts,
    lut_affine_grouped,
)
from repro.kernels.lut_tl1.ops import lut_tl1, lut_tl1_grouped

D, FF = 4096, 14336  # granite_8b d_model, d_ff
CHUNK, ENTRIES, PLANES = 4, 16, 8  # 8-bit fixed-point bitplane, 4-element chunks
DECODE = 8  # decode rows per dispatch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """Described-chip compiles cannot be read back from the persistent
    cache without a chip; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [DECODE, 64])
def test_lut_affine_compiles(one_chip, no_compile_cache, rows):
    k = D // CHUNK
    _assert_kernel(
        _compile(
            lambda c, t, s: lut_affine(c, t, s, interpret=False),
            one_chip,
            ((rows, PLANES, k), jnp.int32),
            ((k, ENTRIES, D), jnp.int8),
            ((PLANES,), jnp.float32),
        )
    )


@pytest.mark.parametrize("kernel", ["lut_affine", "lut_tl1"])
def test_narrow_chunk_axis_compiles(one_chip, no_compile_cache, kernel):
    """A chunk axis under 128 lanes is one whole-axis tile: its codes are
    padded to a 128-lane window, the only width a lane rotate lowers at."""
    if kernel == "lut_affine":
        k = 100
        fn, shapes = lambda c, t, s: lut_affine(c, t, s, interpret=False), (
            ((DECODE, PLANES, k), jnp.int32),
            ((k, ENTRIES, D), jnp.int8),
            ((PLANES,), jnp.float32),
        )
    else:
        q = 400  # 100 packed bytes
        fn, shapes = lambda a, t, s: lut_tl1(a, t, s, interpret=False), (
            ((DECODE, q), jnp.int32),
            ((q // 4, D), jnp.uint8),
            ((DECODE, 1), jnp.float32),
        )
    _assert_kernel(_compile(fn, one_chip, *shapes))


def test_lut_affine_grouped_compiles(one_chip, no_compile_cache):
    k = D // CHUNK  # gate/up: two 4096 -> 14336 members
    _assert_kernel(
        _compile(
            lambda c, t, s: lut_affine_grouped(c, t, s, interpret=False),
            one_chip,
            ((DECODE, PLANES, k), jnp.int32),
            ((2, k, ENTRIES, FF), jnp.int8),
            ((PLANES,), jnp.float32),
        )
    )


def test_lut_affine_experts_compiles(one_chip, no_compile_cache):
    k, experts = D // CHUNK, 4
    _assert_kernel(
        _compile(
            lambda c, t, s, g: lut_affine_experts(c, t, s, g, interpret=False),
            one_chip,
            ((DECODE, PLANES, k), jnp.int32),
            ((experts, 2, k, ENTRIES, 1024), jnp.int8),
            ((PLANES,), jnp.float32),
            ((experts,), jnp.int32),
        )
    )


def test_lut_affine_shift_bits_compiles(one_chip, no_compile_cache):
    planes = 12  # bitplane_shift, radix 1: one element per chunk, 4 entries
    _assert_kernel(
        _compile(
            lambda c, t, s: lut_affine(c, t, s, shift_bits=2, interpret=False),
            one_chip,
            ((DECODE, planes, D), jnp.int32),
            ((D, 4, D), jnp.int8),
            ((planes,), jnp.float32),
        )
    )


@pytest.mark.parametrize("acts_dtype", [jnp.int32, jnp.float32])
def test_lut_tl1_compiles(one_chip, no_compile_cache, acts_dtype):
    _assert_kernel(
        _compile(
            lambda a, t, s: lut_tl1(a, t, s, interpret=False),
            one_chip,
            ((DECODE, D), acts_dtype),
            ((D // 4, D), jnp.uint8),
            ((DECODE, 1), jnp.float32),
        )
    )


def test_lut_tl1_grouped_compiles(one_chip, no_compile_cache):
    _assert_kernel(
        _compile(
            lambda a, t, s: lut_tl1_grouped(a, t, s, interpret=False),
            one_chip,
            ((DECODE, D), jnp.int32),
            ((2, D // 4, FF), jnp.uint8),
            ((DECODE, 1), jnp.float32),
        )
    )
