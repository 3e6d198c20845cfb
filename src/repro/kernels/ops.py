"""Public jit'd entry points for the Pallas kernels.

Selects the execution backend: real Pallas lowering on TPU, ``interpret=True``
elsewhere (this container is CPU-only; interpret mode executes the kernel
body in Python and is the validation target).  Models and the serving engine
call through this module, never the kernels directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.attention.worklist_jnp import (
    packed_decode_attention as _packed_decode_ref,
    packed_decode_attention_paged as _packed_decode_paged_ref,
)
from repro.kernels.flash_attn import flash_attention as _flash
from repro.kernels.flash_decode import (
    decode_items_from_ids,
    flash_decode_kernel as _flash_decode_kernel,
    flash_decode_paged_kernel as _flash_decode_paged_kernel,
    flash_decode_paged_reference as _flash_decode_paged_ref,
    flash_decode_reference as _flash_decode_ref,
    merge_partials,
)
from repro.kernels.sparse_prefill import sparse_prefill_attention as _sparse_prefill
from repro.kernels.sparse_decode import (
    DecodeWorkList,
    build_decode_worklist,
    sparse_decode_attention as _sparse_decode,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, block_q=128, block_kv=128,
                    scale=None, interpret=None):
    if interpret is None:
        interpret = not _on_tpu()
    return _flash(q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
                  scale=scale, interpret=interpret)


def sparse_prefill(q, k, v, items, *, block_q=128, block_kv=128, scale=None,
                   interpret=None):
    if interpret is None:
        interpret = not _on_tpu()
    return _sparse_prefill(q, k, v, jnp.asarray(items), block_q=block_q,
                           block_kv=block_kv, scale=scale,
                           interpret=interpret)


def sparse_decode(q, k_cache, v_cache, items, *, cache_len, block_kv=128,
                  scale=None, interpret=None):
    if interpret is None:
        interpret = not _on_tpu()
    return _sparse_decode(q, k_cache, v_cache, jnp.asarray(items),
                          cache_len=cache_len, block_kv=block_kv, scale=scale,
                          interpret=interpret)


def flash_decode(q, k_cache, v_cache, block_ids, pos, *, block_kv=128,
                 scale=None, window=None, partials=False, use_kernel=None,
                 interpret=None, k_scales=None, v_scales=None):
    """Fused budgeted flash-decode: stream only the selected KV blocks.

    q ``[B, H, 1, D]`` (serving layout — GQA grouping happens here);
    caches ``[B, Hkv, Smax, D]``; ``block_ids [B, Hkv, nb]`` int32 selected
    cache blocks (-1 pad, trailing); ``pos [B]`` per-slot last position.
    With a quantized cache (DESIGN.md §2.12) pass ``k_scales``/``v_scales``
    ``[B, Hkv, Smax/block_kv]`` f32 — dequantization fuses into the
    executor (post-dot rescale), no f32 cache copy is ever materialized.

    ``partials=True`` returns ``(out [B,H,1,D], m, l [B,Hkv,G])`` for the
    flash-decoding cross-shard merge; otherwise just ``out``.  On TPU the
    Pallas kernel runs; elsewhere the jnp reference executes the same
    zero-copy access pattern (scan + dynamic_slice, no dense gather).
    """
    B, H, _, dh = q.shape
    hkv = k_cache.shape[1]
    G = H // hkv
    qg = q.reshape(B, hkv, G, dh)
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel:
        if interpret is None:
            interpret = not _on_tpu()
        items = decode_items_from_ids(jnp.asarray(block_ids))
        out, m, l = _flash_decode_kernel(
            qg, k_cache, v_cache, items, jnp.asarray(pos),
            block_kv=block_kv, scale=scale, window=window,
            interpret=interpret, k_scales=k_scales, v_scales=v_scales)
    else:
        out, m, l = _flash_decode_ref(
            qg, k_cache, v_cache, jnp.asarray(block_ids), jnp.asarray(pos),
            block_kv=block_kv, scale=scale, window=window,
            k_scales=k_scales, v_scales=v_scales)
    out = out.reshape(B, H, 1, dh)
    if partials:
        return out, m, l        # out is f32 — merge-able without requantizing
    return out.astype(q.dtype)


def _layer_pools(k_pool, v_pool, layer):
    """The reference executors' per-layer K and V pools: with ``layer``,
    ``k_pool`` is the whole ``[L, 2, N, Hkv, block, D]`` pool."""
    if layer is None:
        return k_pool, v_pool
    return k_pool[layer, 0], k_pool[layer, 1]


def flash_decode_paged(q, k_pool, v_pool, block_ids, table, pos, *,
                       block_kv=128, scale=None, window=None, partials=False,
                       use_kernel=None, interpret=None, k_scales=None,
                       v_scales=None, layer=None):
    """Paged fused flash-decode: stream selected blocks from the pool.

    q ``[B, H, 1, D]`` (serving layout — GQA grouping happens here);
    pools ``[N, Hkv, block_kv, D]``, or with ``layer`` the whole ``[L, 2,
    N, Hkv, block_kv, D]`` pool as ``k_pool`` and ``v_pool`` None (the
    kernel then reads layer ``layer``'s tiles from it in place; head_dim
    or block_kv a multiple of 128); ``block_ids [B, Hkv, nb]`` int32
    LOGICAL selected blocks (-1 pad, trailing); ``table [B, T]`` int32
    logical -> pool-global translation (-1 = unmapped, masked); ``pos [B]``
    per-slot last position.  With a quantized pool pass ``k_scales``/
    ``v_scales`` ``[N, Hkv]`` f32 (PHYSICAL block index — the scale travels
    with its pool block through the same table indirection).  Same
    returns/partials contract as :func:`flash_decode`; on TPU the
    scalar-prefetch table-indirection kernel runs, elsewhere the jnp
    reference with the identical zero-copy access pattern.
    """
    B, H, _, dh = q.shape
    hkv = k_pool.shape[-3]
    G = H // hkv
    qg = q.reshape(B, hkv, G, dh)
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel:
        if interpret is None:
            interpret = not _on_tpu()
        items = decode_items_from_ids(jnp.asarray(block_ids))
        out, m, l = _flash_decode_paged_kernel(
            qg, k_pool, v_pool, items, jnp.asarray(table), jnp.asarray(pos),
            block_kv=block_kv, scale=scale, window=window,
            interpret=interpret, k_scales=k_scales, v_scales=v_scales,
            layer=layer)
    else:
        k_pool, v_pool = _layer_pools(k_pool, v_pool, layer)
        out, m, l = _flash_decode_paged_ref(
            qg, k_pool, v_pool, jnp.asarray(block_ids), jnp.asarray(table),
            jnp.asarray(pos), block_kv=block_kv, scale=scale, window=window,
            k_scales=k_scales, v_scales=v_scales)
    out = out.reshape(B, H, 1, dh)
    if partials:
        return out, m, l        # out is f32 — merge-able without requantizing
    return out.astype(q.dtype)


def flash_decode_packed(q, k_cache, v_cache, items, pos, *, block_kv=128,
                        scale=None, window=None, partials=False,
                        use_kernel=None, interpret=None, k_scales=None,
                        v_scales=None):
    """Cost-packed ragged flash-decode (DESIGN.md §2.8).

    q ``[B, H, 1, D]`` (serving layout — GQA grouping happens here);
    caches ``[B, Hkv, Smax, D]``; ``items [L, DEC_FIELDS]`` int32 packed
    decode worklist (one (row, kv_head, kv_block) tile per row, runs
    contiguous, replicate-last padding at valid=0); ``pos [B]`` per-slot
    last position.  The grid/scan length is the PACKED item count — decode
    cost scales with ``mean_h b_h`` instead of ``Hkv x max_h b_h x B``.
    On TPU the Pallas kernel consumes the table directly; elsewhere the
    bitwise jnp twin executes the same ragged grid.  Same returns/partials
    contract as :func:`flash_decode`.
    """
    B, H, _, dh = q.shape
    hkv = k_cache.shape[1]
    G = H // hkv
    qg = q.reshape(B, hkv, G, dh)
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel:
        if interpret is None:
            interpret = not _on_tpu()
        out, m, l = _flash_decode_kernel(
            qg, k_cache, v_cache, jnp.asarray(items), jnp.asarray(pos),
            block_kv=block_kv, scale=scale, window=window,
            interpret=interpret, k_scales=k_scales, v_scales=v_scales)
    else:
        out, m, l = _packed_decode_ref(
            qg, k_cache, v_cache, jnp.asarray(items), jnp.asarray(pos),
            block_kv=block_kv, scale=scale, window=window,
            k_scales=k_scales, v_scales=v_scales)
    out = out.reshape(B, H, 1, dh)
    if partials:
        return out, m, l
    return out.astype(q.dtype)


def flash_decode_packed_paged(q, k_pool, v_pool, items, table, pos, *,
                              block_kv=128, scale=None, window=None,
                              partials=False, use_kernel=None,
                              interpret=None, k_scales=None, v_scales=None,
                              layer=None):
    """Paged twin of :func:`flash_decode_packed`: the packed items' LOGICAL
    kv blocks translate to pool blocks through ``table [B, T]`` (-1 =
    unmapped, masked); pool operands and ``layer`` as
    :func:`flash_decode_paged`; same contract otherwise."""
    B, H, _, dh = q.shape
    hkv = k_pool.shape[-3]
    G = H // hkv
    qg = q.reshape(B, hkv, G, dh)
    if use_kernel is None:
        use_kernel = _on_tpu()
    if use_kernel:
        if interpret is None:
            interpret = not _on_tpu()
        out, m, l = _flash_decode_paged_kernel(
            qg, k_pool, v_pool, jnp.asarray(items), jnp.asarray(table),
            jnp.asarray(pos), block_kv=block_kv, scale=scale, window=window,
            interpret=interpret, k_scales=k_scales, v_scales=v_scales,
            layer=layer)
    else:
        k_pool, v_pool = _layer_pools(k_pool, v_pool, layer)
        out, m, l = _packed_decode_paged_ref(
            qg, k_pool, v_pool, jnp.asarray(items), jnp.asarray(table),
            jnp.asarray(pos), block_kv=block_kv, scale=scale, window=window,
            k_scales=k_scales, v_scales=v_scales)
    out = out.reshape(B, H, 1, dh)
    if partials:
        return out, m, l
    return out.astype(q.dtype)


__all__ = [
    "flash_attention",
    "sparse_prefill",
    "sparse_decode",
    "flash_decode",
    "flash_decode_paged",
    "flash_decode_packed",
    "flash_decode_packed_paged",
    "merge_partials",
    "DecodeWorkList",
    "build_decode_worklist",
]
