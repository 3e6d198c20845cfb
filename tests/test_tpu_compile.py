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
                   block, kv_dtype, items=ITEMS, layers=None):
    """The paged kernel over one layer's K and V pools, or with ``layers``
    over a whole ``[layers, 2, N, Hkv, block, Dh]`` pool at a traced
    layer index."""
    pool_dtype = quant.kv_cache_dtype(kv_dtype, jnp.bfloat16)
    s = lambda shape, dt: _shape(sharding, shape, dt)
    pool = s((num_blocks, kv_heads, block, head_dim), pool_dtype)
    pools, kw = (pool, pool), {}
    if layers is not None:
        pools = (s((layers, 2) + pool.shape, pool_dtype), None)
        kw["layer"] = s((), jnp.int32)
    if quant.is_quantized(kv_dtype):
        kw.update(k_scales=s((num_blocks, kv_heads), jnp.float32),
                  v_scales=s((num_blocks, kv_heads), jnp.float32))
    fn = jax.jit(lambda *a, **k: flash_decode_paged_kernel(
        *a, block_kv=block, **k))
    return fn.lower(
        s((SLOTS, kv_heads, heads // kv_heads, head_dim), jnp.bfloat16),
        *pools, s((items, DEC_FIELDS), jnp.int32),
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


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "fp8"])
def test_paged_whole_pool_transposed_tiles_compile(one_chip, kv_dtype):
    """At SmolLM widths (head_dim 64, 128-token blocks) the kernel reads
    the whole pool's ``[Dh, block]`` tiles: the TPU stores the pool token
    on lanes, so the transposed view it takes is a bitcast, never a
    relayout or a lane pad of the pool."""
    c = _compile_paged(one_chip, num_blocks=1025, heads=9, kv_heads=3,
                       head_dim=64, block=BLOCK, kv_dtype=kv_dtype,
                       layers=4)
    assert "tpu_custom_call" in c.as_text()
    assert re.search(r"\[4,2,1025,3,64,128\]\S* bitcast\(", c.as_text())
    assert not _pool_tile_ops(c, 1025, 3, "copy", "copy-start", "transpose",
                              "pad", "dynamic-slice")
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20


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
# block: SmolLM-135M at 4 layers (head_dim 64, the kernel's token-on-lanes
# tiles) and Yi-6B at 2 layers (head_dim 128 unpadded, 32 query heads over 4 KV
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


def _pool_tile_ops(compiled, num_blocks, kv_heads, *ops):
    """HLO instructions of the kinds ``ops`` whose output is the pool, a
    layer of it or its K or V (any dtype, either tile orientation, padded
    or not): shapes ending in ``num_blocks, kv_heads, a, b``."""
    kinds = "|".join(map(re.escape, ops))
    pat = (rf"= \w+\[(\d+,)*{num_blocks},{kv_heads},\d+,\d+\]\S* "
           rf"({kinds})\(")
    return [ln.strip()[:200] for ln in compiled.as_text().splitlines()
            if re.search(pat, ln)]


@pytest.mark.parametrize("width", list(STEP_CFGS))
def test_paged_decode_step_updates_pool_in_place(paged_step, width):
    """The pool is the layer loop's carry: the step holds well under two
    layers of temporaries, not a second pool, and never copies the pool
    whole.  At both widths the kernel reads layer ``l``'s tiles from the
    carried pool itself (``test_paged_decode_step_reads_layer_in_place``
    holds it to that)."""
    shape, layer_bytes, decode = paged_step(width, "decode")
    ma = decode.memory_analysis()
    assert ma.alias_size_in_bytes >= layer_bytes * shape[0]
    assert ma.temp_size_in_bytes < 2.5 * layer_bytes, ma.temp_size_in_bytes
    assert "tpu_custom_call" in decode.as_text()
    assert not _pool_copies(decode, shape)


def test_paged_decode_step_reads_layer_in_place(paged_step):
    """At both widths the kernel copies its tiles from the carried pool at
    the layer index (``[block, Dh]`` rows at head_dim 128, the pool's own
    token-on-lanes ``[Dh, block]`` tiles at 64): the step slices no
    layer's K or V out of the pool, pads none to 128 lanes, re-lays out
    neither pool nor layer, and holds well under one layer of
    temporaries.  A tied head's embedding table is copied row-major for
    the token gather in every step, whatever the pool, and is counted
    apart."""
    for width, cfg in STEP_CFGS.items():
        shape, layer_bytes, decode = paged_step(width, "decode")
        n, hkv = shape[2], shape[3]
        assert not _pool_tile_ops(decode, n, hkv, "dynamic-slice"), width
        assert not _pool_tile_ops(decode, n, hkv, "pad"), width
        assert not _pool_tile_ops(decode, n, hkv, "copy", "copy-start",
                                  "transpose"), width
        head_copy = (cfg.vocab_size * -(-cfg.d_model // 128) * 128 * 2
                     if cfg.tie_embeddings else 0)
        temp = decode.memory_analysis().temp_size_in_bytes
        assert temp < layer_bytes / 64 + head_copy, (width, temp)


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
