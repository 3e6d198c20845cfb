"""Compile the decode kernels for a described TPU v5e (no chip needed).

The TPU compiler refuses what the Pallas interpreter accepts: block shapes
off the (8, 128) tiling, or more SMEM/VMEM than a kernel may hold.  These
cases compile the paged and contiguous flash-decode kernels at the widths
the engine serves, so such a refusal fails here rather than on the chip.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU compiler, and every worker collects the same
tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quant
from repro.kernels.flash_decode import (
    flash_decode_kernel,
    flash_decode_paged_kernel,
)
from repro.kernels.sparse_decode import DEC_FIELDS

BLOCK = 128
SLOTS = 8
ITEMS = 1024
MAX_SEQ = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_paged(sharding, *, num_blocks, heads, kv_heads, head_dim,
                   block, kv_dtype, items=ITEMS):
    pool_dtype = quant.kv_cache_dtype(kv_dtype, jnp.bfloat16)
    s = lambda shape, dt: _shape(sharding, shape, dt)
    pool = s((num_blocks, kv_heads, block, head_dim), pool_dtype)
    kw = {}
    if quant.is_quantized(kv_dtype):
        kw = dict(k_scales=s((num_blocks, kv_heads), jnp.float32),
                  v_scales=s((num_blocks, kv_heads), jnp.float32))
    fn = jax.jit(lambda *a, **k: flash_decode_paged_kernel(
        *a, block_kv=block, **k))
    return fn.lower(
        s((SLOTS, kv_heads, heads // kv_heads, head_dim), jnp.bfloat16),
        pool, pool, s((items, DEC_FIELDS), jnp.int32),
        s((SLOTS, MAX_SEQ // block), jnp.int32), s((SLOTS,), jnp.int32),
        **kw).compile()


# SmolLM-135M: 9 heads / 3 kv heads, head_dim 64; Yi-6B: 32 / 4, 128
@pytest.mark.parametrize("heads,kv_heads,head_dim,block,num_blocks", [
    (9, 3, 64, 128, 4096),
    (9, 3, 64, 64, 8192),
    (32, 4, 128, 128, 2048),
], ids=["smollm-blk128", "smollm-blk64", "yi6b-blk128"])
def test_paged_bf16_compiles(one_chip, heads, kv_heads, head_dim, block,
                             num_blocks):
    c = _compile_paged(one_chip, num_blocks=num_blocks, heads=heads,
                       kv_heads=kv_heads, head_dim=head_dim, block=block,
                       kv_dtype="bf16")
    assert "tpu_custom_call" in c.as_text()


# 16384 blocks is past what 16 GB holds at SmolLM width (int8: ~11k) and
# still inside the 1 MiB of SMEM the scale tables live in
@pytest.mark.parametrize("kv_dtype,num_blocks", [
    ("int8", 4096), ("fp8", 4096), ("int8", 16384),
])
def test_paged_quantized_compiles(one_chip, kv_dtype, num_blocks):
    c = _compile_paged(one_chip, num_blocks=num_blocks, heads=9,
                       kv_heads=3, head_dim=64, block=BLOCK,
                       kv_dtype=kv_dtype)
    assert "tpu_custom_call" in c.as_text()


def test_paged_long_item_table_compiles(one_chip):
    """A tick's item table of 8192 rows: 2D in SMEM it would take 4 MiB
    (rows padded to 128 words), flat it takes 192 KiB."""
    c = _compile_paged(one_chip, num_blocks=4096, heads=9, kv_heads=3,
                       head_dim=64, block=BLOCK, kv_dtype="int8",
                       items=8192)
    assert "tpu_custom_call" in c.as_text()


def test_contiguous_int8_compiles(one_chip):
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    cache = s((SLOTS, 3, MAX_SEQ, 64), jnp.int8)
    scales = s((SLOTS, 3, MAX_SEQ // BLOCK), jnp.float32)
    fn = jax.jit(lambda *a, **k: flash_decode_kernel(*a, block_kv=BLOCK,
                                                     **k))
    c = fn.lower(s((SLOTS, 3, 3, 64), jnp.float32), cache, cache,
                 s((ITEMS, DEC_FIELDS), jnp.int32), s((SLOTS,), jnp.int32),
                 k_scales=scales, v_scales=scales).compile()
    assert "tpu_custom_call" in c.as_text()
