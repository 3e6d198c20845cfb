"""Pure-jnp work-list attention — the portable twin of the Pallas kernels.

The models and the dry-run path cannot lower Mosaic TPU kernels on the CPU
container, so the same flattened work-list execution model (DESIGN.md §2.2)
is provided as a ``lax.scan`` over items with dynamic slices.  Properties:

- HLO size is O(1) in sequence length (a while loop over the item list) —
  a 500k-context program lowers as compactly as a 4k one;
- FLOPs are EXACT: only selected (head, q_blk, kv_blk) tiles are computed —
  ``cost_analysis`` of the lowered step reflects the true sparse compute,
  which is what the roofline analysis reads;
- it is differentiable (scan + dynamic_update_slice), so the same path
  serves training with causal work-lists;
- semantics match ``kernels.sparse_prefill`` bit-for-bit in f32.

``causal_items`` builds the dense-causal work-list (used for baseline/
training attention); sparse lists come from ``repro.core.worklist``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.worklist import (
    D_BATCH,
    D_FIRST,
    D_KVBLK,
    D_KVHEAD,
    D_LAST,
    D_VALID,
    F_FIRST,
    F_HEAD,
    F_KVBLK,
    F_KVHEAD,
    F_LAST,
    F_QBLK,
    F_VALID,
    ITEM_FIELDS,
)

NEG_INF = -1e30


def causal_items(num_heads: int, nq: int, kv_of_head: np.ndarray | None = None,
                 ) -> np.ndarray:
    """Full-causal work-list: every (h, qb, kb <= qb) tile.  [L, 7] int32."""
    if kv_of_head is None:
        kv_of_head = np.arange(num_heads)
    rows = []
    for h in range(num_heads):
        for qb in range(nq):
            for kb in range(qb + 1):
                rows.append((h, qb, kb, int(kb == 0), int(kb == qb), 1,
                             int(kv_of_head[h])))
    return np.asarray(rows, dtype=np.int32)


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "scale"))
def worklist_attention(
    q: jnp.ndarray,       # [H, Sq, D]
    k: jnp.ndarray,       # [Hkv, Skv, D]
    v: jnp.ndarray,
    items: jnp.ndarray,   # [L, ITEM_FIELDS] int32
    *,
    block_q: int = 128,
    block_kv: int = 128,
    scale: float | None = None,
    q_offset: jnp.ndarray | int | None = None,
    kv_len: jnp.ndarray | int | None = None,
):
    """Execute a work-list with a single lax.scan (one device's list).

    Mirrors ``kernels.sparse_prefill.sparse_prefill_attention``; (head, q_blk)
    tiles with no items yield zero rows.

    ``q_offset`` / ``kv_len`` support chunked prefill: queries live at global
    positions ``q_offset + i`` (item q_blk stays chunk-local) and attend kv
    positions ``< kv_len`` of a cache longer than the chunk.  Both are traced
    scalars — one compile serves every chunk offset.  ``None`` (the default)
    is the classic whole-sequence behavior (offset 0, kv_len = Skv).
    """
    hq, sq, dh = q.shape
    hkv, skv, _ = k.shape
    scale_v = (dh ** -0.5) if scale is None else scale
    pad_q = (-sq) % block_q
    pad_kv = (-skv) % block_kv
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))).astype(jnp.float32)
    kp = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0))).astype(jnp.float32)
    vp = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0))).astype(jnp.float32)
    sqp = qp.shape[1]

    out0 = jnp.zeros((hq, sqp, dh), jnp.float32)
    acc0 = jnp.zeros((block_q, dh), jnp.float32)
    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)

    def step(carry, it):
        out, acc, m, l = carry
        head, qblk, kvblk = it[F_HEAD], it[F_QBLK], it[F_KVBLK]
        kvh = it[F_KVHEAD]
        first = it[F_FIRST] == 1
        last = it[F_LAST] == 1
        valid = it[F_VALID] == 1

        acc = jnp.where(first, jnp.zeros_like(acc), acc)
        m = jnp.where(first, jnp.full_like(m, -jnp.inf), m)
        l = jnp.where(first, jnp.zeros_like(l), l)

        qt = jax.lax.dynamic_slice(
            qp, (head, qblk * block_q, 0), (1, block_q, dh))[0]
        kt = jax.lax.dynamic_slice(
            kp, (kvh, kvblk * block_kv, 0), (1, block_kv, dh))[0]
        vt = jax.lax.dynamic_slice(
            vp, (kvh, kvblk * block_kv, 0), (1, block_kv, dh))[0]
        s = (qt @ kt.T) * scale_v
        qpos = qblk * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kvblk * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos_g = qpos if q_offset is None else qpos + q_offset
        klim = skv if kv_len is None else jnp.minimum(
            jnp.asarray(kv_len, jnp.int32), skv)
        mask = (kpos <= qpos_g) & (kpos < klim) & (qpos < sq) & valid
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + p @ vt
        # no-op the accumulator update on invalid (padding) items
        acc = jnp.where(valid, acc_new, acc)
        l = jnp.where(valid, l_new, l)
        m = jnp.where(valid, m_new, m)

        write = valid & last
        norm = acc / jnp.maximum(l, 1e-30)
        norm = jnp.where(l > 0.0, norm, 0.0)
        cur = jax.lax.dynamic_slice(
            out, (head, qblk * block_q, 0), (1, block_q, dh))[0]
        tile = jnp.where(write, norm, cur)
        out = jax.lax.dynamic_update_slice(
            out, tile[None], (head, qblk * block_q, 0))
        return (out, acc, m, l), None

    (out, _, _, _), _ = jax.lax.scan(step, (out0, acc0, m0, l0), items)
    return out[:, :sq, :].astype(q.dtype)


def batched_worklist_attention(q, k, v, items, **kw):
    """vmap over a leading batch dim; items shared across the batch."""
    fn = functools.partial(worklist_attention, **kw)
    return jax.vmap(lambda qq, kk, vv: fn(qq, kk, vv, items))(q, k, v)


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "scale"))
def worklist_attention_paged(
    q: jnp.ndarray,       # [H, Sq, D]
    pool: jnp.ndarray,    # [L, 2, N, Hkv, block_kv, D]  paged K/V cache
    items: jnp.ndarray,   # [P, ITEM_FIELDS] int32 (kv_blk LOGICAL)
    table: jnp.ndarray,   # [T] int32 logical kv block -> pool block (-1)
    layer: jnp.ndarray | int,
    *,
    block_q: int = 128,
    block_kv: int = 128,
    scale: float | None = None,
    q_offset: jnp.ndarray | int | None = None,
    kv_len: jnp.ndarray | int | None = None,
    k_scales: jnp.ndarray | None = None,   # [N, Hkv] f32, PHYSICAL index
    v_scales: jnp.ndarray | None = None,
):
    """Paged twin of :func:`worklist_attention` (DESIGN.md §2.7): the K/V
    tiles come from layer ``layer`` of the device block POOL through the
    sequence's block table instead of a contiguous per-sequence cache.
    Item ``kv_blk`` stays in the LOGICAL namespace (positions and masks
    derive from it); only the tile ADDRESS is table-indirected, so tile
    values, masks, and the accumulation order — hence the bit pattern of
    the output — match the contiguous executor on equal cache contents.
    ``kv_len`` masks positions past the resident prefix, which also
    guarantees every contributing logical block is mapped; unmapped (-1)
    entries are clamped to pool block 0 and masked out.  With a quantized
    pool (§2.12) pass ``k_scales``/``v_scales [N, Hkv]`` f32 (layer
    ``layer``'s) — the chunked prefill's reads of PAST resident blocks
    dequantize post-dot, same as the decode executors.

    Every item's K and V tile is gathered from the whole pool before the
    scan, an O(items) temporary: no layer of the pool is sliced out.
    """
    hq, sq, dh = q.shape
    assert pool.shape[4] == block_kv, "pool block size != block_kv"
    scale_v = (dh ** -0.5) if scale is None else scale
    quantized = k_scales is not None
    pad_q = (-sq) % block_q
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))).astype(jnp.float32)
    sqp = qp.shape[1]
    tbl = table.astype(jnp.int32)
    klim_default = tbl.shape[0] * block_kv
    if quantized:
        ksf = k_scales.astype(jnp.float32)
        vsf = v_scales.astype(jnp.float32)

    # every item's pool block; an unmapped one reads block 0, masked below
    phys_all = tbl[jnp.maximum(items[:, F_KVBLK], 0)]
    safe_all = jnp.maximum(phys_all, 0)
    kvh_all = items[:, F_KVHEAD]
    k_tiles = pool[layer, 0, safe_all, kvh_all]
    v_tiles = pool[layer, 1, safe_all, kvh_all]

    out0 = jnp.zeros((hq, sqp, dh), jnp.float32)
    acc0 = jnp.zeros((block_q, dh), jnp.float32)
    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)

    def step(carry, scan_in):
        it, phys, kt, vt = scan_in
        out, acc, m, l = carry
        head, qblk, kvblk = it[F_HEAD], it[F_QBLK], it[F_KVBLK]
        kvh = it[F_KVHEAD]
        first = it[F_FIRST] == 1
        last = it[F_LAST] == 1
        valid = it[F_VALID] == 1

        acc = jnp.where(first, jnp.zeros_like(acc), acc)
        m = jnp.where(first, jnp.full_like(m, -jnp.inf), m)
        l = jnp.where(first, jnp.zeros_like(l), l)

        mapped = phys >= 0
        safe = jnp.maximum(phys, 0)
        qt = jax.lax.dynamic_slice(
            qp, (head, qblk * block_q, 0), (1, block_q, dh))[0]
        if not quantized:
            kt = kt.astype(jnp.float32)
            vt = vt.astype(jnp.float32)
        # mixed f32 x codes dot on the quantized path; the raw code tile
        # feeds the dot (no convert to hoist), scale applied to the logits
        s = jax.lax.dot_general(
            qt, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale_v
        if quantized:
            s = s * ksf[safe, kvh]
        qpos = qblk * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kvblk * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos_g = qpos if q_offset is None else qpos + q_offset
        klim = klim_default if kv_len is None else jnp.minimum(
            jnp.asarray(kv_len, jnp.int32), klim_default)
        mask = ((kpos <= qpos_g) & (kpos < klim) & (qpos < sq)
                & valid & mapped)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quantized:
            pv = pv * vsf[safe, kvh]
        acc_new = acc * alpha + pv
        # no-op the accumulator update on invalid (padding) items
        acc = jnp.where(valid, acc_new, acc)
        l = jnp.where(valid, l_new, l)
        m = jnp.where(valid, m_new, m)

        write = valid & last
        norm = acc / jnp.maximum(l, 1e-30)
        norm = jnp.where(l > 0.0, norm, 0.0)
        cur = jax.lax.dynamic_slice(
            out, (head, qblk * block_q, 0), (1, block_q, dh))[0]
        tile = jnp.where(write, norm, cur)
        out = jax.lax.dynamic_update_slice(
            out, tile[None], (head, qblk * block_q, 0))
        return (out, acc, m, l), None

    (out, _, _, _), _ = jax.lax.scan(
        step, (out0, acc0, m0, l0), (items, phys_all, k_tiles, v_tiles))
    return out[:, :sq, :].astype(q.dtype)


# ---------------------------------------------------------------------------
# Cost-packed ragged decode executors (DESIGN.md §2.8)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_kv", "scale", "window"))
def packed_decode_attention(
    q: jnp.ndarray,          # [B, Hkv, G, D]  (GQA-grouped query rows)
    k_cache: jnp.ndarray,    # [B, Hkv, Smax, D]
    v_cache: jnp.ndarray,
    items: jnp.ndarray,      # [L, DEC_FIELDS] int32 packed decode worklist
    pos: jnp.ndarray,        # [B] int32 per-slot last position (inclusive)
    *,
    block_kv: int = 128,
    scale: float | None = None,
    window: int | None = None,
    k_scales: jnp.ndarray | None = None,   # [B, Hkv, Smax/block_kv] f32
    v_scales: jnp.ndarray | None = None,
):
    """Execute a cost-packed decode worklist with one ``lax.scan``.

    The portable twin of running ``kernels.flash_decode_kernel`` over a
    packed item table: grid length == the PACKED list length (total real
    items rounded to the compile bucket), not ``B x Hkv x max-budget``.
    Per (row, kv head) run the block-update arithmetic replicates
    :func:`repro.kernels.flash_decode.flash_decode_reference` op for op —
    same tiles, same accumulation order — so the two paths produce
    BITWISE-identical outputs (hence identical greedy tokens) on equal
    selections.  Returns the same ``(out f32, m, l)`` partials contract.
    ``k_scales``/``v_scales`` enable the quantized-cache path (§2.12):
    per-(slot, kv-head, block) dequant scales applied AFTER the dots.
    """
    B, hkv, G, dh = q.shape
    smax = k_cache.shape[2]
    scale_v = float(dh ** -0.5) if scale is None else float(scale)
    quantized = k_scales is not None
    pad_s = (-smax) % block_kv
    kp = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    if quantized:
        pad_b = (smax + pad_s) // block_kv - k_scales.shape[2]
        ksp = jnp.pad(k_scales.astype(jnp.float32),
                      ((0, 0), (0, 0), (0, pad_b)))
        vsp = jnp.pad(v_scales.astype(jnp.float32),
                      ((0, 0), (0, 0), (0, pad_b)))
    qc = q.astype(jnp.float32 if quantized else k_cache.dtype)
    pos_i = jnp.asarray(pos, jnp.int32)

    out0 = jnp.zeros((B, hkv, G, dh), jnp.float32)
    m_out0 = jnp.full((B, hkv, G), NEG_INF, jnp.float32)
    l_out0 = jnp.zeros((B, hkv, G), jnp.float32)
    acc0 = jnp.zeros((G, dh), jnp.float32)
    m0 = jnp.full((G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G, 1), jnp.float32)

    def step(carry, it):
        out, m_out, l_out, acc, m, l = carry
        b, h, blk = it[D_BATCH], it[D_KVHEAD], it[D_KVBLK]
        first = it[D_FIRST] == 1
        last = it[D_LAST] == 1
        ok = it[D_VALID] == 1

        acc = jnp.where(first, jnp.zeros_like(acc), acc)
        m = jnp.where(first, jnp.full_like(m, NEG_INF), m)
        l = jnp.where(first, jnp.zeros_like(l), l)

        qh = jax.lax.dynamic_slice(qc, (b, h, 0, 0), (1, 1, G, dh))[0, 0]
        kt = jax.lax.dynamic_slice(
            kp, (b, h, blk * block_kv, 0), (1, 1, block_kv, dh))[0, 0]
        vt = jax.lax.dynamic_slice(
            vp, (b, h, blk * block_kv, 0), (1, 1, block_kv, dh))[0, 0]
        p = pos_i[b]
        # block-update arithmetic == flash_decode_reference, verbatim
        s = jax.lax.dot_general(
            qh, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale_v   # [G, blk]
        if quantized:
            s = s * ksp[b, h, blk]
        kpos = blk * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = (kpos <= p) & ok
        if window is not None:
            mask &= kpos > p - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + pr.sum(axis=-1, keepdims=True)
        # f32 p.V dot (see flash_decode_reference): keeps the striped-merge
        # path bit-compatible with single-pass math
        if quantized:
            # mixed f32 x codes dot, post-dot V dequant — no vt convert
            pv = jax.lax.dot_general(
                pr, vt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * vsp[b, h, blk]
        else:
            pv = jax.lax.dot_general(
                pr, vt.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc_new = acc * alpha + pv
        acc = jnp.where(ok, acc_new, acc)
        m = jnp.where(ok, m_new, m)
        l = jnp.where(ok, l_new, l)

        # finalize on `last` alone (matching the Pallas kernel's
        # @pl.when(last)): the PADDED table sets is_last on the run's final
        # stride row even when that row is invalid padding; packed tables
        # only carry last=1 on real items, so both layouts write correctly
        write = last
        norm = acc / jnp.maximum(l, 1e-30)
        norm = jnp.where(l > 0.0, norm, 0.0)
        cur = jax.lax.dynamic_slice(out, (b, h, 0, 0), (1, 1, G, dh))[0, 0]
        out = jax.lax.dynamic_update_slice(
            out, jnp.where(write, norm, cur)[None, None], (b, h, 0, 0))
        cur_m = jax.lax.dynamic_slice(m_out, (b, h, 0), (1, 1, G))[0, 0]
        m_out = jax.lax.dynamic_update_slice(
            m_out, jnp.where(write, m[:, 0], cur_m)[None, None], (b, h, 0))
        cur_l = jax.lax.dynamic_slice(l_out, (b, h, 0), (1, 1, G))[0, 0]
        l_out = jax.lax.dynamic_update_slice(
            l_out, jnp.where(write, l[:, 0], cur_l)[None, None], (b, h, 0))
        return (out, m_out, l_out, acc, m, l), None

    (out, m_out, l_out, _, _, _), _ = jax.lax.scan(
        step, (out0, m_out0, l_out0, acc0, m0, l0), items)
    return out, m_out, l_out


@functools.partial(jax.jit, static_argnames=("block_kv", "scale", "window"))
def packed_decode_attention_paged(
    q: jnp.ndarray,          # [B, Hkv, G, D]
    k_pool: jnp.ndarray,     # [N, Hkv, block_kv, D]  device block pool
    v_pool: jnp.ndarray,
    items: jnp.ndarray,      # [L, DEC_FIELDS] int32, D_KVBLK LOGICAL
    table: jnp.ndarray,      # [B, T] int32 logical -> pool block (-1)
    pos: jnp.ndarray,        # [B] int32 per-slot last position (inclusive)
    *,
    block_kv: int = 128,
    scale: float | None = None,
    window: int | None = None,
    k_scales: jnp.ndarray | None = None,   # [N, Hkv] f32, PHYSICAL index
    v_scales: jnp.ndarray | None = None,
):
    """Paged twin of :func:`packed_decode_attention`: tiles come from the
    block POOL through the per-slot table; item kv blocks stay LOGICAL
    (positions/masks derive from them), only the slice address is
    indirected; unmapped entries are masked.  Per-run arithmetic replicates
    ``flash_decode_paged_reference`` op for op (bitwise on equal
    selections); same ``(out f32, m, l)`` returns.  ``k_scales``/
    ``v_scales [N, Hkv]`` f32 (physical-block-indexed) enable the
    quantized-pool path (§2.12): post-dot dequant, no f32 pool copy.
    """
    B, hkv, G, dh = q.shape
    assert k_pool.shape[2] == block_kv, "pool block size != block_kv"
    scale_v = float(dh ** -0.5) if scale is None else float(scale)
    quantized = k_scales is not None
    tbl = jnp.asarray(table, jnp.int32)
    if quantized:
        ksf = k_scales.astype(jnp.float32)
        vsf = v_scales.astype(jnp.float32)
    qc = q.astype(jnp.float32 if quantized else k_pool.dtype)
    pos_i = jnp.asarray(pos, jnp.int32)

    out0 = jnp.zeros((B, hkv, G, dh), jnp.float32)
    m_out0 = jnp.full((B, hkv, G), NEG_INF, jnp.float32)
    l_out0 = jnp.zeros((B, hkv, G), jnp.float32)
    acc0 = jnp.zeros((G, dh), jnp.float32)
    m0 = jnp.full((G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G, 1), jnp.float32)

    def step(carry, it):
        out, m_out, l_out, acc, m, l = carry
        b, h, blk = it[D_BATCH], it[D_KVHEAD], it[D_KVBLK]
        first = it[D_FIRST] == 1
        last = it[D_LAST] == 1
        valid = it[D_VALID] == 1

        acc = jnp.where(first, jnp.zeros_like(acc), acc)
        m = jnp.where(first, jnp.full_like(m, NEG_INF), m)
        l = jnp.where(first, jnp.zeros_like(l), l)

        phys = tbl[b, blk]
        ok = valid & (phys >= 0)
        safe = jnp.maximum(phys, 0)
        qh = jax.lax.dynamic_slice(qc, (b, h, 0, 0), (1, 1, G, dh))[0, 0]
        kt = jax.lax.dynamic_slice(
            k_pool, (safe, h, 0, 0), (1, 1, block_kv, dh))[0, 0]
        vt = jax.lax.dynamic_slice(
            v_pool, (safe, h, 0, 0), (1, 1, block_kv, dh))[0, 0]
        p = pos_i[b]
        s = jax.lax.dot_general(
            qh, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale_v
        if quantized:
            s = s * ksf[safe, h]
        kpos = blk * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = (kpos <= p) & ok
        if window is not None:
            mask &= kpos > p - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + pr.sum(axis=-1, keepdims=True)
        # f32 p.V dot (see flash_decode_reference): keeps the striped-merge
        # path bit-compatible with single-pass math
        if quantized:
            pv = jax.lax.dot_general(
                pr, vt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * vsf[safe, h]
        else:
            pv = jax.lax.dot_general(
                pr, vt.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc_new = acc * alpha + pv
        acc = jnp.where(ok, acc_new, acc)
        m = jnp.where(ok, m_new, m)
        l = jnp.where(ok, l_new, l)

        write = last  # kernel-parity: finalize on `last` alone (see above)
        norm = acc / jnp.maximum(l, 1e-30)
        norm = jnp.where(l > 0.0, norm, 0.0)
        cur = jax.lax.dynamic_slice(out, (b, h, 0, 0), (1, 1, G, dh))[0, 0]
        out = jax.lax.dynamic_update_slice(
            out, jnp.where(write, norm, cur)[None, None], (b, h, 0, 0))
        cur_m = jax.lax.dynamic_slice(m_out, (b, h, 0), (1, 1, G))[0, 0]
        m_out = jax.lax.dynamic_update_slice(
            m_out, jnp.where(write, m[:, 0], cur_m)[None, None], (b, h, 0))
        cur_l = jax.lax.dynamic_slice(l_out, (b, h, 0), (1, 1, G))[0, 0]
        l_out = jax.lax.dynamic_update_slice(
            l_out, jnp.where(write, l[:, 0], cur_l)[None, None], (b, h, 0))
        return (out, m_out, l_out, acc, m, l), None

    (out, m_out, l_out, _, _, _), _ = jax.lax.scan(
        step, (out0, m_out0, l_out0, acc0, m0, l0), items)
    return out, m_out, l_out
