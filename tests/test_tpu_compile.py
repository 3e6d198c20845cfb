"""Compile the decode kernels for a described TPU v5e (no chip needed).

The TPU compiler refuses what the Pallas interpreter accepts: block shapes
off the (8, 128) tiling, or more SMEM/VMEM than a kernel may hold.  These
cases compile the paged and contiguous flash-decode kernels at the widths
the engine serves, so such a refusal fails here rather than on the chip.

The paged decode and chunk steps are compiled whole, with the pool
donated, to hold them to an in-place pool: no temporary or copy the size
of the pool.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU compiler, and every worker collects the same
tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quant
from repro.kernels import ops
from repro.kernels.flash_decode import (
    flash_decode_kernel,
    flash_decode_paged_kernel,
)
from repro.kernels.sparse_decode import DEC_FIELDS
from repro.models import transformer as tfm

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


# The steps at two widths, each with a 1024-block pool plus the trash
# block: SmolLM-135M at 4 layers (head_dim 64, the kernel's padded path)
# and Yi-6B at 2 layers (head_dim 128 unpadded, 32 query heads over 4 KV
# heads, untied head), where XLA may lay the pool out either way round
STEP_CFGS = {
    "smollm": tfm.TransformerConfig(num_layers=4, d_model=576, num_heads=9,
                                    num_kv_heads=3, head_dim=64, d_ff=1536,
                                    vocab_size=49152, block_q=BLOCK,
                                    block_kv=BLOCK, layer_loop="scan"),
    "yi6b": tfm.TransformerConfig(num_layers=2, d_model=4096, num_heads=32,
                                  num_kv_heads=4, head_dim=128, d_ff=11008,
                                  vocab_size=64000, tie_embeddings=False,
                                  block_q=BLOCK, block_kv=BLOCK,
                                  layer_loop="scan"),
}
STEP_BLOCKS = 1025


@pytest.fixture(scope="module")
def paged_step(one_chip):
    """``compile(width, step, chunk=None) -> (pool shape, layer bytes,
    compiled)``: the packed decode step (``step="decode"``) or the sparse
    chunk step of ``chunk`` tokens, compiled with the pool donated."""
    def compile_step(width, step, chunk=None):
        cfg = STEP_CFGS[width]
        s = lambda shape, dt: _shape(one_chip, shape, dt)
        params = jax.tree.map(lambda a: s(a.shape, a.dtype), jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)))
        shape = (cfg.num_layers, 2, STEP_BLOCKS, cfg.num_kv_heads, BLOCK,
                 cfg.head_dim_)
        pool = s(shape, jnp.bfloat16)
        i32 = lambda *shape: s(shape, jnp.int32)
        T = MAX_SEQ // BLOCK
        on_tpu = ops._on_tpu
        ops._on_tpu = lambda: True       # the dispatch sees the CPU here
        try:
            if step == "decode":
                compiled = jax.jit(lambda p, c, tok, pos, tbl, items, act:
                                   tfm.decode_step_paged(
                                       p, c, tok, pos, tbl, cfg,
                                       packed_items=items,
                                       cache_len=pos + 1, active=act),
                                   donate_argnums=(1,)).lower(
                    params, pool, i32(SLOTS), i32(SLOTS), i32(SLOTS, T),
                    i32(cfg.num_layers, ITEMS, DEC_FIELDS),
                    s((SLOTS,), jnp.bool_)).compile()
            else:
                compiled = jax.jit(lambda p, c, tok, tbl, off, kv_len, last,
                                   items: tfm.prefill_chunk_paged(
                                       p, c, tok, tbl, off, cfg,
                                       kv_len=kv_len, sparse_items=items,
                                       last_index=last),
                                   donate_argnums=(1,)).lower(
                    params, pool, i32(1, chunk), i32(T), i32(), i32(), i32(),
                    i32(cfg.num_layers, 512, 7)).compile()
        finally:
            ops._on_tpu = on_tpu
        layer_bytes = (2 * STEP_BLOCKS * cfg.num_kv_heads * BLOCK
                       * cfg.head_dim_ * 2)
        return shape, layer_bytes, compiled
    return compile_step


def _pool_copies(compiled, shape):
    """HLO instructions that copy a value of the whole pool's shape."""
    dims = ",".join(map(str, shape))
    return [ln.strip() for ln in compiled.as_text().splitlines()
            if re.search(rf"= bf16\[{dims}\]\S* copy(-start)?\(", ln)]


@pytest.mark.parametrize("width", list(STEP_CFGS))
def test_paged_decode_step_updates_pool_in_place(paged_step, width):
    """The pool is the layer loop's carry: the step holds at most about
    two layers of temporaries, not a second pool, and never copies the
    pool whole.  Two layers is what head_dim 64 needs: its kernel still
    takes layer ``l``'s K and V sliced out of the pool and padded to 128
    lanes."""
    shape, layer_bytes, decode = paged_step(width, "decode")
    ma = decode.memory_analysis()
    assert ma.alias_size_in_bytes >= layer_bytes * shape[0]
    assert ma.temp_size_in_bytes < 2.5 * layer_bytes, ma.temp_size_in_bytes
    assert "tpu_custom_call" in decode.as_text()
    assert not _pool_copies(decode, shape)


def test_paged_decode_step_reads_layer_in_place(paged_step):
    """At head_dim 128 the kernel copies its tiles from the carried pool
    at the layer index: the step slices no layer's K or V out of the pool
    and holds well under one layer of temporaries."""
    shape, layer_bytes, decode = paged_step("yi6b", "decode")
    temp = decode.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes / 64, temp
    layer_of_pool = rf"= bf16\[1,1,{shape[2]},[^\]]*\]\S* dynamic-slice\("
    assert not [ln for ln in decode.as_text().splitlines()
                if re.search(layer_of_pool, ln)]


@pytest.mark.parametrize("width", list(STEP_CFGS))
@pytest.mark.parametrize("chunk", [128, 256])
def test_paged_chunk_step_updates_pool_in_place(paged_step, width, chunk):
    """Every chunk bucket writes the pool in place at both widths: at
    head_dim 128 a one-block chunk once made XLA lay the carried pool out
    to suit the chunk's K, copying the whole pool into and out of the
    layer loop."""
    shape, layer_bytes, chunk_step = paged_step(width, "chunk", chunk)
    ma = chunk_step.memory_analysis()
    assert ma.alias_size_in_bytes >= layer_bytes * shape[0]
    assert ma.temp_size_in_bytes < layer_bytes, ma.temp_size_in_bytes
    assert not _pool_copies(chunk_step, shape)
