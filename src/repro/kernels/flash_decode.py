"""Fused budgeted flash-decode kernel (DESIGN.md §2.3).

The serving decode hot path previously gathered each head's selected KV
blocks into a dense ``[B, Hkv, nb*blk, D]`` buffer and ran a dense einsum
over it — touching every selected byte TWICE (gather write + einsum read)
and allocating a second cache-sized buffer.  Decode attention is memory
bound, so that doubling erases the sparsity advantage the HPLB planner
balanced for.  This kernel streams the selected blocks straight from the
slot cache:

    one work item = one (slot, kv_head, kv_block) matvec tile,
    grid = (L,);  item metadata + per-slot positions ride in SMEM via
    scalar prefetch;  BlockSpec index maps address the cache IN PLACE.

Exactly ``budget_blocks x block_kv x D`` bytes of K/V move HBM->VMEM per
(slot, kv head) — the roofline the paper claims.  GQA query heads are
grouped so one K/V tile serves all ``G`` rows of its group; the online
softmax carries ``(acc, m, l)`` across the contiguous items of one
(slot, kv head) run.

Work-item layout (int32, shared with ``sparse_decode`` / the HPLB decode
work-lists so balanced per-device lists drop in unchanged):

    [:, 0] slot (batch)   [:, 3] is_first   -> reset accumulator
    [:, 1] kv_head        [:, 4] is_last    -> finalize + write
    [:, 2] kv_block       [:, 5] valid      -> 0 = padding (skip compute)

Positions are PER SLOT and dynamic (continuous batching: every slot sits at
a different length): token ``kpos`` contributes iff ``kpos <= pos[slot]``.
Because both the item table and ``pos`` are data (not trace constants),
re-selecting blocks at block boundaries never recompiles.

The item table may be either the PADDED fixed-stride layout
(:func:`decode_items_from_ids` — grid ``B x Hkv x max-budget``, the
step-invariant baseline) or a COST-PACKED ragged list
(``core.worklist.pack_decode_items`` — grid = total selected blocks rounded
to a pow2 compile bucket, DESIGN.md §2.8).  The kernel is agnostic: it
executes whatever (first..last, valid) runs the table encodes, so the
packed grid drops the ``max_h b_h`` padding for free.  Under a packed
table, a (slot, kv head) with no run keeps an UNWRITTEN out tile — packed
builders must cover every pair the caller reads (the engine's selections
always include the newest block, so coverage is structural).

The kernel emits flash-decoding partials ``(out, m, l)`` so a sequence-
sharded cache can merge shard-local results with the standard
``exp(m - max m)`` rescale (``serving.sharded_attention``); single-shard
callers just take ``out``.

``flash_decode_reference`` is the pure-jnp twin for CPU: a ``lax.scan``
over the block list with ``dynamic_slice`` — the same "no dense gather"
access pattern, validated by jaxpr inspection in the tests and benchmark.

PAGED variants (DESIGN.md §2.7): the cache is a device block pool
``[N, Hkv, block, D]`` and the selection's LOGICAL block ids translate to
pool-global physical blocks through a per-slot block table ``[B, T]``
(-1 = unmapped).  The selection, positions, and masks stay in the logical
namespace — ``kpos = logical_blk * block + lane`` — only the ADDRESS is
indirected, so the budget allocator's ids flow unchanged down to the grid:

- :func:`flash_decode_paged_kernel` — the table rides in SMEM as a third
  scalar-prefetch operand; the body dereferences it
  (``table[slot, logical_blk]``) and copies the pool blocks in place into
  a double-buffered VMEM tile, one item ahead;
- :func:`flash_decode_paged_reference` — the jnp twin: ``lax.scan`` over
  the logical list, ``dynamic_slice`` at the table-translated pool index.

QUANTIZED pool (DESIGN.md §2.12): every executor takes optional
``k_scales`` / ``v_scales`` — one f32 scale per (block, kv-head) tile
(contiguous: ``[B, Hkv, Smax/block]``; paged: ``[N, Hkv]``, indexed by
PHYSICAL block).  Dequantization happens INSIDE the kernel, after the
dots: ``(q·k_codes) * s == q·(k_codes*s)`` up to f32 rounding because the
dequant is linear, so the int8/fp8 tiles stream HBM->VMEM as-is and no
f32 copy of the pool ever exists.  The jnp references feed the code
tiles to mixed-dtype ``lax.dot_general`` (f32 x int8/fp8, f32
accumulate) — deliberately no tile convert, which XLA could hoist into a
full-pool dequantized copy.  ``k_scales=None`` (the default) leaves the
pre-§2.12 bf16/f32 paths bitwise-untouched.  Scales ride to the Pallas
kernels as flat f32 tables held whole in SMEM; the body reads one scalar
per grid step at the (table-indirected) index of its K/V block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_decode import (
    DEC_FIELDS,
    D_BATCH,
    D_FIRST,
    D_KVBLK,
    D_KVHEAD,
    D_LAST,
    D_VALID,
)

NEG_INF = -1e30


def _field(items_ref, i, f):
    """Field ``f`` of work item ``i`` in the flat ``[L * DEC_FIELDS]``
    SMEM table.  Scalar-prefetch operands are passed 1D: SMEM pads a 2D
    int32 array's rows to 128 words, which at a few thousand items
    (``[L, 6]``) outgrows the 1 MiB SMEM of a v5e."""
    return items_ref[i * DEC_FIELDS + f]


# ---------------------------------------------------------------------------
# Work-item table from per-slot block ids (inside jit — ids are data)
# ---------------------------------------------------------------------------

def decode_items_from_ids(block_ids: jnp.ndarray) -> jnp.ndarray:
    """``block_ids [B, Hkv, nb]`` (-1 pad, pads trailing) -> items
    ``[B*Hkv*nb, DEC_FIELDS]``.

    This is the PADDED baseline grid (every head at the max-budget width;
    ``core.worklist.padded_decode_items`` is the host/numpy twin, and
    ``pack_decode_items`` builds the cost-packed ragged alternative).
    Fixed-stride layout: row ``(b, h, j)`` at index ``(b*Hkv + h)*nb + j``.
    ``is_first``/``is_last`` are set at ``j == 0`` / ``j == nb-1``
    UNCONDITIONALLY so every (slot, kv head) tile is initialized and
    finalized even when its selection is empty (the finalize writes zeros /
    ``m = NEG_INF`` / ``l = 0`` — the identity of the cross-shard merge).
    All ops are jnp: the table is rebuilt on-device each step from the
    runtime selection without recompiling.
    """
    B, hkv, nb = block_ids.shape
    flat = block_ids.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    j = idx % nb
    bh = idx // nb
    items = jnp.stack([
        bh // hkv,                                   # D_BATCH
        bh % hkv,                                    # D_KVHEAD
        jnp.maximum(flat, 0),                        # D_KVBLK (clipped)
        (j == 0).astype(jnp.int32),                  # D_FIRST
        (j == nb - 1).astype(jnp.int32),             # D_LAST
        (flat >= 0).astype(jnp.int32),               # D_VALID
    ], axis=1)
    return items


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _flash_decode_kernel(
    items_ref, pos_ref,          # SMEM (scalar prefetch)
    q_ref, k_ref, v_ref,         # VMEM tiles via index maps
    *rest,                       # [ks_ref, vs_ref,] outs, scratch
    scale: float,
    block_kv: int,
    window: int | None,
    quantized: bool = False,
    num_kv_heads: int = 0,
    num_scale_blocks: int = 0,
):
    if quantized:
        (ks_ref, vs_ref, o_ref, m_out_ref, l_out_ref,
         acc_ref, m_ref, l_ref) = rest
    else:
        o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = rest
    i = pl.program_id(0)
    valid = _field(items_ref, i, D_VALID) == 1
    first = _field(items_ref, i, D_FIRST) == 1
    last = _field(items_ref, i, D_LAST) == 1
    kvblk = _field(items_ref, i, D_KVBLK)
    slot = _field(items_ref, i, D_BATCH)
    pos = pos_ref[slot]
    if quantized:
        # flat SMEM index of this tile's (slot, kv head, block) scale
        sidx = ((slot * num_kv_heads + _field(items_ref, i, D_KVHEAD))
                * num_scale_blocks + kvblk)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(valid)
    def _compute():
        qt = q_ref[0, 0].astype(jnp.float32)   # [G, d]
        kt = k_ref[0, 0].astype(jnp.float32)   # [block_kv, d]
        vt = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qt, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, block_kv]
        if quantized:
            # post-dot dequant: the codes->values scale is linear, so it
            # commutes with the dot; the int8/fp8 tile streamed as-is
            s = s * ks_ref[sidx]
        kpos = kvblk * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kpos <= pos
        if window is not None:
            mask &= kpos > pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quantized:
            pv = pv * vs_ref[sidx]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(last)
    def _finalize():
        l = l_ref[...]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0.0, out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        m_out_ref[0, 0] = jnp.broadcast_to(m_ref[...], m_out_ref.shape[2:])
        l_out_ref[0, 0] = jnp.broadcast_to(l, l_out_ref.shape[2:])


@functools.partial(
    jax.jit,
    static_argnames=("block_kv", "scale", "window", "interpret"),
)
def flash_decode_kernel(
    q: jnp.ndarray,        # [B, Hkv, G, D]  (GQA-grouped query rows)
    k_cache: jnp.ndarray,  # [B, Hkv, Smax, D]
    v_cache: jnp.ndarray,
    items: jnp.ndarray,    # [L, DEC_FIELDS] int32 work-item table
    pos: jnp.ndarray,      # [B] int32 per-slot last position (inclusive)
    *,
    block_kv: int = 128,
    scale: float | None = None,
    window: int | None = None,
    interpret: bool = False,
    k_scales: jnp.ndarray | None = None,   # [B, Hkv, Smax/block_kv] f32
    v_scales: jnp.ndarray | None = None,
):
    """Execute a decode work-list against the slot cache in place.

    Returns flash-decoding partials ``(out, m, l)``: ``out [B, Hkv, G, D]``
    f32, normalized within this cache shard, ``m``/``l [B, Hkv, G]`` f32
    softmax statistics for cross-shard merging.  Every (slot, kv head) must be
    covered by a first..last item run (``decode_items_from_ids`` guarantees
    it; HPLB work-lists cover every head by construction — the sink block).
    """
    B, hkv, G, dh = q.shape
    smax = k_cache.shape[2]
    scale_v = float(dh ** -0.5) if scale is None else float(scale)
    quantized = k_scales is not None

    pad_g = (-G) % 8        # sublane alignment
    dh_pad = (-dh) % 128    # lane alignment
    pad_s = (-smax) % block_kv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_g), (0, dh_pad)))
    kp = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad_s), (0, dh_pad)))
    vp = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad_s), (0, dh_pad)))
    Gp, dp = G + pad_g, dh + dh_pad
    L = items.shape[0]

    nbs = (smax + pad_s) // block_kv
    kernel = functools.partial(
        _flash_decode_kernel, scale=scale_v, block_kv=block_kv,
        window=window, quantized=quantized, num_kv_heads=hkv,
        num_scale_blocks=nbs)

    def bh_index(i, it, p):
        return (_field(it, i, D_BATCH), _field(it, i, D_KVHEAD), 0, 0)

    def tile_index(i, it, p):
        return (_field(it, i, D_BATCH), _field(it, i, D_KVHEAD),
                _field(it, i, D_KVBLK), 0)

    in_specs = [
        pl.BlockSpec((1, 1, Gp, dp), bh_index),
        pl.BlockSpec((1, 1, block_kv, dp), tile_index),
        pl.BlockSpec((1, 1, block_kv, dp), tile_index),
    ]
    operands = [qp, kp, vp]
    if quantized:
        # one f32 scale per (slot, kv-head, block), the whole flat table
        # resident in SMEM and read as a scalar in the body: a (1, 1, 1)
        # VMEM tile per grid step is not a legal TPU block shape
        for s_arr in (k_scales, v_scales):
            pad_b = nbs - s_arr.shape[2]
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.append(jnp.pad(
                s_arr.astype(jnp.float32),
                ((0, 0), (0, 0), (0, pad_b))).reshape(-1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, Gp, dp), bh_index),
            pl.BlockSpec((1, 1, Gp, 128), bh_index),
            pl.BlockSpec((1, 1, Gp, 128), bh_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((Gp, dp), jnp.float32),
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((Gp, 1), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            # f32 out: these are merge-able partials (see reference)
            jax.ShapeDtypeStruct((B, hkv, Gp, dp), jnp.float32),
            jax.ShapeDtypeStruct((B, hkv, Gp, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, hkv, Gp, 128), jnp.float32),
        ],
        interpret=interpret,
    )(items.reshape(-1), pos.astype(jnp.int32), *operands)
    return (out[:, :, :G, :dh], m[:, :, :G, 0], l[:, :, :G, 0])


# ---------------------------------------------------------------------------
# Pure-jnp reference executor (CPU serving path)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("block_kv", "scale", "window"))
def flash_decode_reference(
    q: jnp.ndarray,          # [B, Hkv, G, D]
    k_cache: jnp.ndarray,    # [B, Hkv, Smax, D]
    v_cache: jnp.ndarray,
    block_ids: jnp.ndarray,  # [B, Hkv, nb] int32, -1 pad
    pos: jnp.ndarray,        # [B] int32 last position (inclusive)
    *,
    block_kv: int = 128,
    scale: float | None = None,
    window: int | None = None,
    k_scales: jnp.ndarray | None = None,   # [B, Hkv, Smax/block_kv] f32
    v_scales: jnp.ndarray | None = None,
):
    """jnp twin of :func:`flash_decode_kernel` — identical contract and
    returns, zero-copy access pattern (``lax.scan`` over the block list
    with per-block ``dynamic_slice``; no ``[B, Hkv, nb*blk, D]`` gather
    materializes in the jaxpr)."""
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

    def one_head(qh, kh, vh, ids, p, ksh=None, vsh=None):
        # qh [G, D]; kh/vh [Smax_pad, D]; ids [nb]; p scalar;
        # ksh/vsh [Smax_pad/block_kv] f32 per-block dequant scales

        def step(carry, blk_id):
            acc, m, l = carry
            ok = blk_id >= 0
            safe = jnp.maximum(blk_id, 0)
            kt = jax.lax.dynamic_slice(
                kh, (safe * block_kv, 0), (block_kv, dh))
            vt = jax.lax.dynamic_slice(
                vh, (safe * block_kv, 0), (block_kv, dh))
            # mixed-precision QK dot (f32 accumulate) WITHOUT an explicit
            # tile convert: a convert-of-slice is loop-invariant-hoistable
            # into a full-cache f32 copy, which would silently reintroduce
            # the memory traffic this path exists to avoid.  The same
            # holds for the quantized path: the int8/fp8 tile feeds the
            # dot raw and the scale multiplies the LOGITS after (linear
            # dequant commutes with the dot).
            s = jax.lax.dot_general(
                qh, kt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale_v  # [G, blk]
            if quantized:
                s = s * ksh[safe]
            kpos = safe * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            mask = (kpos <= p) & ok
            if window is not None:
                mask &= kpos > p - window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + pr.sum(axis=-1, keepdims=True)
            # the p.V dot stays f32 like the Pallas kernel: quantizing
            # ``pr`` to the cache dtype would put it on a grid that depends
            # on the RUNNING max, which differs between a single pass and
            # per-stripe partial passes — the striped merge (§2.11) would
            # then diverge from the 1D path by ~cache-dtype eps, not ulps
            if quantized:
                # mixed f32 x codes dot, post-dot V dequant — no vt convert
                pv = jax.lax.dot_general(
                    pr, vt, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * vsh[safe]
            else:
                pv = jax.lax.dot_general(
                    pr, vt.astype(jnp.float32), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc_new = acc * alpha + pv
            acc = jnp.where(ok, acc_new, acc)
            m = jnp.where(ok, m_new, m)
            l = jnp.where(ok, l_new, l)
            return (acc, m, l), None

        acc0 = jnp.zeros((G, dh), jnp.float32)
        m0 = jnp.full((G, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((G, 1), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), ids,
                                      unroll=True)
        out = acc / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0.0, out, 0.0)
        # out stays f32: cross-shard merges re-weight these partials, and a
        # bf16 round-trip here would quantize every merged element.  The
        # single-shard caller (ops.flash_decode) downcasts once at the end.
        return out, m[:, 0], l[:, 0]

    # vmap over kv heads then slots
    if quantized:
        per_head = jax.vmap(one_head, in_axes=(0, 0, 0, 0, None, 0, 0))
        out, m, l = jax.vmap(per_head)(q.astype(jnp.float32), kp, vp,
                                       block_ids.astype(jnp.int32),
                                       pos.astype(jnp.int32), ksp, vsp)
    else:
        per_head = jax.vmap(one_head, in_axes=(0, 0, 0, 0, None))
        out, m, l = jax.vmap(per_head)(q.astype(k_cache.dtype), kp, vp,
                                       block_ids.astype(jnp.int32),
                                       pos.astype(jnp.int32))
    return out, m, l


# ---------------------------------------------------------------------------
# Paged variants: block-table indirection into a device block pool
# ---------------------------------------------------------------------------

def _flash_decode_paged_kernel(
    items_ref, tbl_ref, pos_ref,   # SMEM (scalar prefetch)
    *rest,                         # [layer_ref,] q_ref, pool(s) in HBM,
                                   # [ks_ref, vs_ref,] outs, scratch
    scale: float,
    block_kv: int,
    window: int | None,
    quantized: bool = False,
    num_kv_heads: int = 0,
    table_width: int = 0,
    layered: bool = False,
    transposed: bool = False,
):
    """Same online-softmax body as :func:`_flash_decode_kernel`, but the
    K/V tiles come from the block POOL, copied by hand at the
    table-translated index into two VMEM buffers: item ``i + 1``'s tiles
    stream in while item ``i`` computes.  An item is additionally invalid
    when its table entry is unmapped (``table[slot, logical] < 0`` — e.g. a
    shard that does not own the block under a block-sharded pool); invalid
    items copy nothing.  ``layered``: one whole ``[L, 2, N, Hkv, block,
    Dh]`` pool in HBM, and the tiles are copied from layer
    ``layer_ref[0]`` of it (a fourth scalar-prefetch argument).
    ``transposed``: the pool's tiles are ``[Dh, block]`` (token on lanes),
    and the two dots contract their other dimension."""
    if layered:
        layer_ref, q_ref, pool_hbm, *rest = rest
        kv_src = lambda phys, head: [pool_hbm.at[layer_ref[0], c, phys, head]
                                     for c in (0, 1)]
    else:
        q_ref, k_hbm, v_hbm, *rest = rest
        kv_src = lambda phys, head: [k_hbm.at[phys, head],
                                     v_hbm.at[phys, head]]
    if quantized:
        (ks_ref, vs_ref, o_ref, m_out_ref, l_out_ref,
         acc_ref, m_ref, l_ref, k_buf, v_buf, sem) = rest
    else:
        (o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref,
         k_buf, v_buf, sem) = rest
    i = pl.program_id(0)

    def tile(j):
        """Item ``j``: (is streamed, physical block, K/V copies into
        buffer ``j % 2``)."""
        slot_j = _field(items_ref, j, D_BATCH)
        phys_j = tbl_ref[slot_j * table_width + _field(items_ref, j, D_KVBLK)]
        head_j = _field(items_ref, j, D_KVHEAD)
        buf = j % 2
        copies = [pltpu.make_async_copy(src, dst.at[buf], sem.at[c, buf])
                  for c, (src, dst) in enumerate(zip(
                      kv_src(jnp.maximum(phys_j, 0), head_j),
                      (k_buf, v_buf)))]
        ok = (_field(items_ref, j, D_VALID) == 1) & (phys_j >= 0)
        return ok, phys_j, copies

    def start(j):
        ok, _, copies = tile(j)

        @pl.when(ok)
        def _():
            for c in copies:
                c.start()

    @pl.when(i == 0)
    def _prime():
        start(0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _prefetch():
        start(i + 1)

    valid, phys, copies = tile(i)
    kvblk = _field(items_ref, i, D_KVBLK)
    slot = _field(items_ref, i, D_BATCH)
    first = _field(items_ref, i, D_FIRST) == 1
    last = _field(items_ref, i, D_LAST) == 1
    pos = pos_ref[slot]
    if quantized:
        # flat SMEM index of this tile's (physical block, kv head) scale
        sidx = (jnp.maximum(phys, 0) * num_kv_heads
                + _field(items_ref, i, D_KVHEAD))

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(valid)
    def _compute():
        for c in copies:
            c.wait()
        qt = q_ref[0, 0].astype(jnp.float32)   # [G, d]
        kt = k_buf[i % 2].astype(jnp.float32)  # [block_kv, d] / [d, block_kv]
        vt = v_buf[i % 2].astype(jnp.float32)
        d_of_tile = 0 if transposed else 1     # the tile's head_dim axis
        s = jax.lax.dot_general(
            qt, kt, (((1,), (d_of_tile,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, block_kv]
        if quantized:
            # post-dot dequant: per-(physical block, kv head) scale,
            # table-indirected exactly like the K tile it belongs to
            s = s * ks_ref[sidx]
        # positions come from the LOGICAL block id — the physical pool
        # index carries no position information
        kpos = kvblk * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kpos <= pos
        if window is not None:
            mask &= kpos > pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, vt, (((1,), (1 - d_of_tile,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quantized:
            pv = pv * vs_ref[sidx]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(last)
    def _finalize():
        l = l_ref[...]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0.0, out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        m_out_ref[0, 0] = jnp.broadcast_to(m_ref[...], m_out_ref.shape[2:])
        l_out_ref[0, 0] = jnp.broadcast_to(l, l_out_ref.shape[2:])


@functools.partial(
    jax.jit,
    static_argnames=("block_kv", "scale", "window", "interpret"),
)
def flash_decode_paged_kernel(
    q: jnp.ndarray,        # [B, Hkv, G, D]  (GQA-grouped query rows)
    k_pool: jnp.ndarray,   # [N, Hkv, block_kv, D], or the whole
                           # [L, 2, N, Hkv, block_kv, D] pool with ``layer``
    v_pool: jnp.ndarray | None,   # [N, Hkv, block_kv, D]; None with layer
    items: jnp.ndarray,    # [L, DEC_FIELDS] int32, D_KVBLK LOGICAL
    table: jnp.ndarray,    # [B, T] int32 logical -> pool block (-1 unmapped)
    pos: jnp.ndarray,      # [B] int32 per-slot last position (inclusive)
    *,
    block_kv: int = 128,
    scale: float | None = None,
    window: int | None = None,
    interpret: bool = False,
    k_scales: jnp.ndarray | None = None,   # [N, Hkv] f32, PHYSICAL index
    v_scales: jnp.ndarray | None = None,
    layer: jnp.ndarray | int | None = None,
):
    """Paged twin of :func:`flash_decode_kernel`: one (slot, kv_head,
    logical_block) matvec tile per grid step, the body dereferences the
    block table in SMEM (scalar prefetch) and copies the tile from the pool
    itself, one item ahead, so exactly the selected pool blocks move
    HBM->VMEM — same roofline as the
    contiguous kernel, token-granular memory.

    Three operand forms, picked by shape:

    - without ``layer``: one layer's K and V pools ``[N, Hkv, block_kv,
      D]``, head_dim padded to 128 lanes here (the sharded islands, the
      head-parallel path);
    - with ``layer`` (an int32 scalar, traced or not) ``k_pool`` is the
      whole ``[L, 2, N, Hkv, block_kv, D]`` pool, ``v_pool`` None, and the
      tiles are copied from ``k_pool[layer, 0 / 1]`` as it lies: no
      per-layer slice of the pool is made.  At ``D % 128 == 0`` the tiles
      are ``[block_kv, D]`` rows;
    - otherwise (``D`` 64, say) the TPU stores the pool's tiles token on
      lanes, ``[D, block_kv]``, so the kernel reads the pool's
      ``[..., D, block_kv]`` transposed view, which is the same bytes,
      and needs ``block_kv % 128 == 0``.  Neither pool nor query is
      padded.

    The scales stay one layer's ``[N, Hkv]``."""
    B, hkv, G, dh = q.shape
    assert k_pool.shape[-2] == block_kv, "pool block size != block_kv"
    scale_v = float(dh ** -0.5) if scale is None else float(scale)
    quantized = k_scales is not None
    layered = layer is not None
    transposed = layered and dh % 128 != 0

    pad_g = (-G) % 8        # sublane alignment
    dh_pad = 0 if transposed else (-dh) % 128    # lane alignment
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_g), (0, dh_pad)))
    if layered:
        assert v_pool is None and k_pool.ndim == 6, "whole pool, V in it"
        assert not transposed or block_kv % 128 == 0, \
            "the whole-pool form needs head_dim or block_kv % 128 == 0"
        pools = [jnp.swapaxes(k_pool, -1, -2) if transposed else k_pool]
    else:
        pools = [jnp.pad(p, ((0, 0), (0, 0), (0, 0), (0, dh_pad)))
                 for p in (k_pool, v_pool)]
    Gp, dp = G + pad_g, dh + dh_pad
    L = items.shape[0]
    T = table.shape[1]
    tile_shape = (dp, block_kv) if transposed else (block_kv, dp)

    kernel = functools.partial(
        _flash_decode_paged_kernel, scale=scale_v, block_kv=block_kv,
        window=window, quantized=quantized, num_kv_heads=hkv,
        table_width=T, layered=layered, transposed=transposed)

    def bh_index(i, it, *_):
        return (_field(it, i, D_BATCH), _field(it, i, D_KVHEAD), 0, 0)

    # the pools stay in HBM and the body copies its tiles itself: a
    # table-indirected BlockSpec on a pool of a few thousand bf16 blocks
    # halts a v5e at run time, though it compiles
    in_specs = ([pl.BlockSpec((1, 1, Gp, dp), bh_index)]
                + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools))
    operands = [qp, *pools]
    if quantized:
        # per-(physical block, kv head) scales: the flat [N * Hkv] table
        # sits whole in SMEM (4 B per pool tile) and the body reads the
        # entry at the table-translated index — a (1, 1) VMEM tile per grid
        # step is not a legal TPU block shape.  v5e SMEM is 1 MiB, so the
        # two tables bound N * Hkv below ~120k tiles: at SmolLM-135M widths
        # that is a ~40k-block int8 pool, more than 16 GB of HBM can hold.
        for s_arr in (k_scales, v_scales):
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.append(s_arr.astype(jnp.float32).reshape(-1))

    prefetch = [items.reshape(-1), table.astype(jnp.int32).reshape(-1),
                pos.astype(jnp.int32)]
    if layered:
        prefetch.append(jnp.asarray(layer, jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(L,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, Gp, dp), bh_index),
            pl.BlockSpec((1, 1, Gp, 128), bh_index),
            pl.BlockSpec((1, 1, Gp, 128), bh_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((Gp, dp), jnp.float32),
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((2, *tile_shape), pools[0].dtype),    # K buffers
            pltpu.VMEM((2, *tile_shape), pools[-1].dtype),   # V buffers
            pltpu.SemaphoreType.DMA((2, 2)),           # [K/V, buffer]
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # the prefetch of item i + 1 runs under item i: the grid is serial
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=[
            jax.ShapeDtypeStruct((B, hkv, Gp, dp), jnp.float32),
            jax.ShapeDtypeStruct((B, hkv, Gp, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, hkv, Gp, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*prefetch, *operands)
    return (out[:, :, :G, :dh], m[:, :, :G, 0], l[:, :, :G, 0])


@functools.partial(
    jax.jit, static_argnames=("block_kv", "scale", "window"))
def flash_decode_paged_reference(
    q: jnp.ndarray,          # [B, Hkv, G, D]
    k_pool: jnp.ndarray,     # [N, Hkv, block_kv, D]
    v_pool: jnp.ndarray,
    block_ids: jnp.ndarray,  # [B, Hkv, nb] int32 LOGICAL ids, -1 pad
    table: jnp.ndarray,      # [B, T] int32 logical -> pool block (-1)
    pos: jnp.ndarray,        # [B] int32 last position (inclusive)
    *,
    block_kv: int = 128,
    scale: float | None = None,
    window: int | None = None,
    k_scales: jnp.ndarray | None = None,   # [N, Hkv] f32, PHYSICAL index
    v_scales: jnp.ndarray | None = None,
):
    """jnp twin of :func:`flash_decode_paged_kernel` — identical contract
    and returns.  ``lax.scan`` over the logical block list with a per-block
    ``dynamic_slice`` at the table-translated pool index: no gather of the
    sequence's blocks into a contiguous view ever materializes, and the
    accumulation order (hence bit pattern) matches the contiguous
    :func:`flash_decode_reference` on equal cache contents."""
    B, hkv, G, dh = q.shape
    assert k_pool.shape[2] == block_kv, "pool block size != block_kv"
    scale_v = float(dh ** -0.5) if scale is None else float(scale)
    quantized = k_scales is not None
    tbl = table.astype(jnp.int32)
    if quantized:
        ksf = k_scales.astype(jnp.float32)
        vsf = v_scales.astype(jnp.float32)

    def one_slot(qb, ids_b, tbl_b, p):
        # qb [Hkv, G, D]; ids_b [Hkv, nb]; tbl_b [T]; p scalar

        def one_head(qh, ids, h_idx):

            def step(carry, blk_id):
                acc, m, l = carry
                safe_logical = jnp.maximum(blk_id, 0)
                phys = tbl_b[safe_logical]
                ok = (blk_id >= 0) & (phys >= 0)
                safe = jnp.maximum(phys, 0)
                kt = jax.lax.dynamic_slice(
                    k_pool, (safe, h_idx, 0, 0), (1, 1, block_kv, dh))[0, 0]
                vt = jax.lax.dynamic_slice(
                    v_pool, (safe, h_idx, 0, 0), (1, 1, block_kv, dh))[0, 0]
                s = jax.lax.dot_general(
                    qh, kt, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale_v
                if quantized:
                    # post-dot dequant at the PHYSICAL scale entry — the
                    # codes tile streamed raw, no convert to hoist
                    s = s * ksf[safe, h_idx]
                kpos = safe_logical * block_kv + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                mask = (kpos <= p) & ok
                if window is not None:
                    mask &= kpos > p - window
                s = jnp.where(mask, s, NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + pr.sum(axis=-1, keepdims=True)
                # f32 p.V dot (see flash_decode_reference): keeps the
                # striped-merge path bit-compatible with single-pass math
                if quantized:
                    pv = jax.lax.dot_general(
                        pr, vt, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32
                    ) * vsf[safe, h_idx]
                else:
                    pv = jax.lax.dot_general(
                        pr, vt.astype(jnp.float32), (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                acc_new = acc * alpha + pv
                acc = jnp.where(ok, acc_new, acc)
                m = jnp.where(ok, m_new, m)
                l = jnp.where(ok, l_new, l)
                return (acc, m, l), None

            acc0 = jnp.zeros((G, dh), jnp.float32)
            m0 = jnp.full((G, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((G, 1), jnp.float32)
            (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), ids,
                                          unroll=True)
            out = acc / jnp.maximum(l, 1e-30)
            out = jnp.where(l > 0.0, out, 0.0)
            return out, m[:, 0], l[:, 0]

        return jax.vmap(one_head)(qb, ids_b,
                                  jnp.arange(hkv, dtype=jnp.int32))

    q_in = q.astype(jnp.float32) if quantized else q.astype(k_pool.dtype)
    out, m, l = jax.vmap(one_slot)(q_in,
                                   block_ids.astype(jnp.int32), tbl,
                                   pos.astype(jnp.int32))
    return out, m, l


def merge_partials(outs, ms, ls):
    """Flash-decoding combine of per-shard partials along a leading axis.

    ``outs [S, ..., D]`` shard-normalized outputs, ``ms``/``ls [S, ...]``.
    Returns the exact global softmax output (used by tests and the
    sequence-striped decode path; the shard_map island does the same
    algebra with psum/pmax collectives).

    A fully-masked shard — ``m == NEG_INF`` (or ``-inf``), ``l == 0``, the
    identity the executors emit when a shard's stripe holds none of a
    row's blocks — must merge as the EXACT identity: its weight is forced
    to zero (a ``-inf`` max would otherwise turn ``exp(m - gm)`` into
    ``exp(nan)``), the max is taken over contributing shards only, and a
    row with exactly one contributing shard returns that shard's output
    bitwise (no ``x * l / l`` renormalization ulp).  All shards masked
    returns zeros, never ``0/0`` NaN.
    """
    outs32 = outs.astype(jnp.float32)
    real = ls > 0.0                                    # [S, ...]
    nreal = real.sum(axis=0)                           # [...]
    gm = jnp.max(jnp.where(real, ms, NEG_INF), axis=0)
    w = jnp.where(real, jnp.exp(ms - gm[None]) * ls, 0.0)
    num = jnp.sum(outs32 * w[..., None], axis=0)
    den = jnp.maximum(jnp.sum(w, axis=0), 1e-30)
    merged = num / den[..., None]
    # <= 1 contributing shard: bypass the renormalization entirely —
    # sum-of-masked picks the single real shard's output exactly (or 0)
    single = jnp.sum(jnp.where(real[..., None], outs32, 0.0), axis=0)
    out = jnp.where((nreal <= 1)[..., None], single, merged)
    return out.astype(outs.dtype)


__all__ = [
    "decode_items_from_ids",
    "flash_decode_kernel",
    "flash_decode_paged_kernel",
    "flash_decode_paged_reference",
    "flash_decode_reference",
    "merge_partials",
]
