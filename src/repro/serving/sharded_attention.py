"""shard_map S-HPLB attention islands (DESIGN.md §2.4).

Per-device DISTINCT work is impossible under plain GSPMD jit (one program,
uniform shapes); the S-HPLB execution model therefore runs inside shard_map
islands over the ``model`` axis:

- :func:`hplb_prefill_attention` — each model-shard executes ITS OWN
  work-list (the per-device lists built by the HPLB planner; lengths
  equalized to max_d L_d, which the partitioner minimizes).  Heads are
  already permuted into slot order in the weights, so shard d's q/k/v slices
  are exactly its assigned heads.

- :func:`flash_decode_attention` — decode against a SEQUENCE-sharded KV
  cache (the long-context layout): each shard computes a partial online
  softmax over its local kv blocks — budgeted via per-shard block-id lists —
  and the partials merge with the flash-decoding (acc, m, l) combine over
  the mesh axes.  S-HPLB balances the per-shard block counts.

Both islands use the pure-jnp work-list executors on CPU and the Pallas
kernels (kernels.ops) on TPU.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.attention.worklist_jnp import worklist_attention
from repro.kernels import ops

NEG_INF = -1e30


def _batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def hplb_prefill_attention(mesh, *, block_q=128, block_kv=128,
                           kv_sharded: bool = True):
    """Build the shard_map prefill attention: (q, k, v, items) -> o.

    q [B, H, S, D] sharded (batch, model, -, -); items
    [n_model, L, Lpad, 7] sharded on axis 0 — inside the island each shard
    sees its own [1, L, Lpad, 7] list.  Returns a callable taking the LAYER
    index to slice items (so one shard_map signature serves every layer).

    ``kv_sharded``: kv_group mode (kv heads sharded with their q heads,
    item kv indices device-local).  False = kv_replication mode (fewer kv
    heads than shards, e.g. minitron 8 kv over 16): k/v replicate over the
    model axis (shard_map inserts the all-gather) and item kv indices are
    GLOBAL.
    """
    ba = _batch_axes(mesh)
    bspec = ba[0] if len(ba) == 1 else (ba if ba else None)
    kv_spec = "model" if kv_sharded else None

    def attend(l, q, k, v, items):
        def island(q_l, k_l, v_l, items_l):
            # q_l [B_l, H_loc, S, D]; items_l [1, L, Lpad, 7]
            it = items_l[0, l]
            fn = functools.partial(
                worklist_attention, items=it,
                block_q=block_q, block_kv=block_kv)
            return jax.vmap(lambda qq, kk, vv: fn(qq, kk, vv))(q_l, k_l, v_l)

        return jax.shard_map(
            island, mesh=mesh,
            in_specs=(P(bspec, "model", None, None),
                      P(bspec, kv_spec, None, None),
                      P(bspec, kv_spec, None, None),
                      P("model", None, None, None)),
            out_specs=P(bspec, "model", None, None),
            check_vma=False,
        )(q, k, v, items)

    return attend


def hplb_prefill_attention_rows(mesh, *, block_q=128, block_kv=128):
    """Row-mode shard_map prefill: (head, q_blk) rows partitioned over the
    model axis (archs whose head count does not divide the mesh — see
    ``core.worklist.build_row_worklist``).  q/k/v replicated inside the
    island; disjoint output tiles combine via psum over 'model'."""
    ba = _batch_axes(mesh)
    bspec = ba[0] if len(ba) == 1 else (ba if ba else None)

    def attend(l, q, k, v, items):
        def island(q_l, k_l, v_l, items_l):
            it = items_l[0, l]
            fn = functools.partial(
                worklist_attention, items=it,
                block_q=block_q, block_kv=block_kv)
            o = jax.vmap(lambda qq, kk, vv: fn(qq, kk, vv))(q_l, k_l, v_l)
            return jax.lax.psum(o, "model")

        return jax.shard_map(
            island, mesh=mesh,
            in_specs=(P(bspec, None, None, None),
                      P(bspec, None, None, None),
                      P(bspec, None, None, None),
                      P("model", None, None, None)),
            out_specs=P(bspec, None, None, None),
            check_vma=False,
        )(q, k, v, items)

    return attend


def hplb_decode_attention_packed(mesh, *, block_kv=128):
    """Head-parallel cost-packed decode island (DESIGN.md §2.8): each
    model shard executes ITS OWN packed decode worklist against its head
    shard of the slot cache — the decode twin of
    :func:`hplb_prefill_attention`.

    q ``[B, H, 1, D]`` sharded on heads over 'model'; kc/vc
    ``[B, Hkv, Smax, D]`` sharded on kv heads; items
    ``[n_model, L_pad, DEC_FIELDS]`` sharded on axis 0 — built by
    ``core.worklist.pack_decode_items(..., shard_of_kvhead=...,
    kvhead_local=True)`` so every item's kv head indexes the LOCAL cache
    shard.  Lists are equalized to ``max_d L_d``, which the cost packing
    minimizes; heads are disjoint across shards so no cross-shard merge is
    needed.  ``pos [B]`` replicates.
    """
    ba = _batch_axes(mesh)
    bspec = ba[0] if len(ba) == 1 else (ba if ba else None)

    def attend(q, kc, vc, items, pos, k_scales=None, v_scales=None):
        B = q.shape[0]
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        qz = k_scales is not None

        if qz:
            # quantized cache (§2.12): dequant scales [B, Hkv, S/blk]
            # shard on kv heads WITH their cache shard — dequantization
            # stays entirely island-local, no extra collective
            def island(q_l, kc_l, vc_l, items_l, pos_l, ks_l, vs_l):
                return ops.flash_decode_packed(
                    q_l, kc_l, vc_l, items_l[0], pos_l, block_kv=block_kv,
                    k_scales=ks_l, v_scales=vs_l)

            return jax.shard_map(
                island, mesh=mesh,
                in_specs=(P(bspec, "model", None, None),
                          P(bspec, "model", None, None),
                          P(bspec, "model", None, None),
                          P("model", None, None),
                          P(bspec),
                          P(bspec, "model", None),
                          P(bspec, "model", None)),
                out_specs=P(bspec, "model", None, None),
                check_vma=False,
            )(q, kc, vc, items, pos_b, k_scales, v_scales)

        def island(q_l, kc_l, vc_l, items_l, pos_l):
            # q_l [B_l, H_loc, 1, D]; kc_l [B_l, Hkv_loc, S, D];
            # items_l [1, L_pad, DEC_FIELDS] — this shard's packed list
            return ops.flash_decode_packed(
                q_l, kc_l, vc_l, items_l[0], pos_l, block_kv=block_kv)

        return jax.shard_map(
            island, mesh=mesh,
            in_specs=(P(bspec, "model", None, None),
                      P(bspec, "model", None, None),
                      P(bspec, "model", None, None),
                      P("model", None, None),
                      P(bspec)),
            out_specs=P(bspec, "model", None, None),
            check_vma=False,
        )(q, kc, vc, items, pos_b)

    return attend


def hplb_repermute_kv_cache(mesh, *, axis="model"):
    """Plan-epoch swap island (DESIGN.md §2.9): re-permute the kv-head
    axis of a HEAD-SHARDED resident cache across model shards.

    ``cache``: any 6-d layout with kv heads on axis 3 and that axis
    sharded over ``axis`` — the contiguous slot cache
    ``[L, 2, B, Hkv, Smax, Dh]`` or the paged pool
    ``[L, 2, N, Hkv, block, Dh]``.  ``kv_perm [L, Hkv]`` is the GLOBAL
    delta shuffle (new kv slot -> previous kv slot) from
    :meth:`repro.core.planner.PlanDelta.kv_perm_table`; a replan may move
    a kv head BETWEEN shards, so the island all-gathers the kv-head axis
    and each shard takes its new heads (one collective per swap — epoch
    swaps are rare; a production mesh would ppermute only the moved
    heads).  Single-shard callers should use
    ``models.transformer.permute_cache_kv_heads`` directly (no
    collective).
    """
    def repermute(cache, kv_perm, scales=None):
        def island(c_l, perm_l):
            # c_l [L, 2, *, Hkv_loc, *, Dh] (or a scales tensor with kv
            # heads on axis 3); perm_l [L, Hkv] replicated
            full = jax.lax.all_gather(c_l, axis, axis=3, tiled=True)
            d = jax.lax.axis_index(axis)
            hl = c_l.shape[3]
            mine = jax.lax.dynamic_slice_in_dim(
                jnp.asarray(perm_l, jnp.int32), d * hl, hl, axis=1)
            idx = mine.reshape((mine.shape[0], 1, 1, hl)
                               + (1,) * (c_l.ndim - 4))
            return jnp.take_along_axis(full, idx, axis=3)

        def run(x):
            nd = np.asarray(x.ndim)
            spec = P(*((None, None, None, axis) + (None,) * (int(nd) - 4)))
            return jax.shard_map(
                island, mesh=mesh,
                in_specs=(spec, P(None, None)),
                out_specs=spec,
                check_vma=False,
            )(x, jnp.asarray(kv_perm, jnp.int32))

        if scales is None:
            return run(cache)
        # quantized (§2.12): the scales tensor — paged [L, 2, N, Hkv] or
        # contiguous [L, 2, B, Hkv, S/blk], kv heads on axis 3 like the
        # cache — re-permutes through the identical island
        return run(cache), run(scales)

    return repermute


def hplb_swap_gather_kv_blocks(mesh, *, axis="model"):
    """Preemption swap-out island (DESIGN.md §2.10): gather a preempted
    sequence's mapped pool blocks off a HEAD-SHARDED cache, shard-LOCAL.

    ``pool [L, 2, N, Hkv, block, Dh]`` has its kv-head axis sharded over
    ``axis``; ``ids [nblk]`` (pool-global block ids, trash-padded) are
    replicated.  Each shard slices ITS OWN kv-head rows of the selected
    blocks — no collective, unlike the epoch re-permute above — so the
    host copy comes back still laid out in the CURRENT epoch's kv-head
    arrangement.  That is exactly why a plan-epoch re-permute between
    swap-out and swap-in must re-arrange the host copy once at swap-in
    (the engine tracks the cumulative arrangement; the resident cache's
    §2.9 gather never touches host copies).  The pool passes through
    donated/aliased so the jitted caller keeps the buffer chain.
    """
    def gather(pool, ids, scales=None):
        def island(p_l, ids_l):
            # p_l [L, 2, N, Hkv_loc, block, Dh] (or scales [L, 2, N,
            # Hkv_loc]): local take on the block axis, no collective
            return p_l, jnp.take(p_l, ids_l, axis=2)

        def run(x):
            spec = P(*((None, None, None, axis) + (None,) * (x.ndim - 4)))
            return jax.shard_map(
                island, mesh=mesh,
                in_specs=(spec, P(None)),
                out_specs=(spec, spec),
                check_vma=False,
            )(x, jnp.asarray(ids, jnp.int32))

        if scales is None:
            return run(pool)
        # quantized (§2.12): scales [L, 2, N, Hkv] gather through the same
        # ids — the host swap copy is (codes, scales), byte-true
        (pool, blocks), (scales, sc) = run(pool), run(scales)
        return (pool, scales), (blocks, sc)

    return gather


def hplb_swap_scatter_kv_blocks(mesh, *, axis="model"):
    """Preemption swap-in island: scatter a host copy back into freshly
    mapped pool blocks, shard-local (each shard writes its own kv-head
    slice; trash-padded ids absorb the bucket padding).  The host copy
    must already be in the CURRENT epoch's kv-head arrangement — the
    engine re-arranges stale copies host-side before dispatch."""
    def scatter(pool, blocks, ids, scales=None, block_scales=None):
        def island(p_l, b_l, ids_l):
            return p_l.at[:, :, ids_l].set(b_l.astype(p_l.dtype))

        def run(x, b):
            spec = P(*((None, None, None, axis) + (None,) * (x.ndim - 4)))
            return jax.shard_map(
                island, mesh=mesh,
                in_specs=(spec, spec, P(None)),
                out_specs=spec,
                check_vma=False,
            )(x, b, jnp.asarray(ids, jnp.int32))

        if scales is None:
            return run(pool, blocks)
        return run(pool, blocks), run(scales, block_scales)

    return scatter


def flash_decode_attention_paged(mesh, *, block_kv=128, seq_axes=("model",),
                                 batch_axes=None):
    """Paged twin of :func:`flash_decode_attention`: the device cache is a
    block POOL ``[N, Hkv, block, D]`` sharded on its BLOCK axis over
    ``seq_axes`` (each shard owns pool blocks ``[s*N_loc, (s+1)*N_loc)``),
    and selections stay LOGICAL — the per-slot block table ``[B, T]``
    (pool-GLOBAL ids) is remapped shard-local inside the island, entries
    another shard owns becoming -1 (masked).  Because positions derive
    from the logical ids, no position shifting is needed; partials merge
    with the same flash-decoding psum/pmax combine.  S-HPLB balance now
    acts on the one true unit: per-shard POOL BLOCK counts.
    """
    if batch_axes is None:
        batch_axes = tuple(a for a in _batch_axes(mesh)
                           if a not in seq_axes)
    ba = tuple(batch_axes)
    bspec = ba[0] if len(ba) == 1 else (ba if ba else None)
    sspec = seq_axes[0] if len(seq_axes) == 1 else tuple(seq_axes)

    def attend(q, k_pool, v_pool, ids, table, pos, k_scales=None,
               v_scales=None):
        B, H, _, dh = q.shape
        hkv = k_pool.shape[1]
        G = H // hkv
        n_pool = k_pool.shape[0]
        n_shards = int(np.prod([mesh.shape[a] for a in seq_axes]))
        n_loc = n_pool // n_shards
        # per-slot positions shard with the batch like q/ids/table do
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        qz = k_scales is not None

        def island(q_l, kp_l, vp_l, ids_l, tbl_l, pos_l, ks_l=None,
                   vs_l=None):
            # q_l [B_l, H, 1, D]; kp_l [N_loc, Hkv, blk, D];
            # ids_l [B_l, Hkv, nb] LOGICAL; tbl_l [B_l, T] GLOBAL pool ids
            if len(seq_axes) == 1:
                sidx = jax.lax.axis_index(seq_axes[0])
            else:
                sidx = jax.lax.axis_index(seq_axes)
            lo = sidx * n_loc
            local = tbl_l - lo
            ok = (tbl_l >= 0) & (local >= 0) & (local < n_loc)
            tbl_local = jnp.where(ok, local, -1)
            Bl = q_l.shape[0]
            out, m, l = ops.flash_decode_paged(
                q_l, kp_l, vp_l, ids_l, tbl_local, pos_l,
                block_kv=block_kv, partials=True,
                k_scales=ks_l, v_scales=vs_l)
            ax = seq_axes if len(seq_axes) > 1 else seq_axes[0]
            gm = jax.lax.pmax(m, ax)                          # [B,hkv,G]
            w = jnp.exp(m - gm) * l
            den = jax.lax.psum(w, ax)
            num = jax.lax.psum(
                out.astype(jnp.float32).reshape(Bl, hkv, G, dh)
                * w[..., None], ax)
            o = num / jnp.maximum(den, 1e-30)[..., None]
            return o.reshape(Bl, H, 1, dh).astype(q_l.dtype)

        in_specs = (P(bspec, None, None, None),
                    P(sspec, None, None, None),
                    P(sspec, None, None, None),
                    P(bspec, None, None),
                    P(bspec, None),
                    P(bspec))
        args = (q, k_pool, v_pool, ids, table, pos_b)
        if qz:
            # quantized (§2.12): scales [N, Hkv] (PHYSICAL ids) shard on
            # the block axis WITH their pool stripe — the translated local
            # table indexes the local scales shard directly
            in_specs += (P(sspec, None), P(sspec, None))
            args += (k_scales, v_scales)
        return jax.shard_map(
            island, mesh=mesh, in_specs=in_specs,
            out_specs=P(bspec, None, None, None),
            check_vma=False,
        )(*args)

    return attend


def flash_decode_attention_2d(mesh, *, block_kv=128, model_axis="model",
                              seq_axis="seq", batch_axes=None):
    """2D head x sequence decode island (DESIGN.md §2.11).

    The paged pool ``[N, Hkv, block, D]`` is sharded BOTH ways: kv heads
    over ``model_axis`` (the HPLB axis) and pool blocks over ``seq_axis``
    (contiguous stripes of ``N_loc = N // n_seq`` ids — exactly the
    stripe-aware allocator's ownership ranges).  Each device ``(d, s)``
    computes flash-decode partials for ITS kv-head shard over ITS stripe's
    blocks: the GLOBAL per-slot table ``[B, T]`` is remapped stripe-local
    inside the island (foreign/unmapped entries become -1, masked), so
    selections stay LOGICAL and shard with their kv heads over
    ``model_axis``.  Partials merge with ONE psum/pmax flash-decoding
    combine along ``seq_axis`` ONLY — heads are disjoint along
    ``model_axis``, so no collective ever crosses it.  A stripe holding
    none of a row's blocks contributes ``l = 0`` weights and drops out of
    the merge exactly (``NEG_INF`` is finite — no 0/0).
    """
    if batch_axes is None:
        batch_axes = tuple(a for a in _batch_axes(mesh)
                           if a not in (model_axis, seq_axis))
    ba = tuple(batch_axes)
    bspec = ba[0] if len(ba) == 1 else (ba if ba else None)

    def attend(q, k_pool, v_pool, ids, table, pos, k_scales=None,
               v_scales=None):
        B, H, _, dh = q.shape
        n_pool = k_pool.shape[0]
        n_seq = mesh.shape[seq_axis]
        n_loc = n_pool // n_seq
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        qz = k_scales is not None

        def island(q_l, kp_l, vp_l, ids_l, tbl_l, pos_l, ks_l=None,
                   vs_l=None):
            # q_l [B_l, H_loc, 1, D]; kp_l [N_loc, Hkv_loc, blk, D];
            # ids_l [B_l, Hkv_loc, nb] LOGICAL; tbl_l [B_l, T] GLOBAL
            sidx = jax.lax.axis_index(seq_axis)
            lo = sidx * n_loc
            local = tbl_l - lo
            ok = (tbl_l >= 0) & (local >= 0) & (local < n_loc)
            tbl_local = jnp.where(ok, local, -1)
            Bl, Hl = q_l.shape[0], q_l.shape[1]
            hkv_l = kp_l.shape[1]
            G = Hl // hkv_l
            out, m, l = ops.flash_decode_paged(
                q_l, kp_l, vp_l, ids_l, tbl_local, pos_l,
                block_kv=block_kv, partials=True,
                k_scales=ks_l, v_scales=vs_l)
            gm = jax.lax.pmax(m, seq_axis)                # [B,hkv_l,G]
            w = jnp.exp(m - gm) * l
            den = jax.lax.psum(w, seq_axis)
            num = jax.lax.psum(
                out.astype(jnp.float32).reshape(Bl, hkv_l, G, dh)
                * w[..., None], seq_axis)
            o = num / jnp.maximum(den, 1e-30)[..., None]
            return o.reshape(Bl, Hl, 1, dh).astype(q_l.dtype)

        in_specs = (P(bspec, model_axis, None, None),
                    P(seq_axis, model_axis, None, None),
                    P(seq_axis, model_axis, None, None),
                    P(bspec, model_axis, None),
                    P(bspec, None),
                    P(bspec))
        args = (q, k_pool, v_pool, ids, table, pos_b)
        if qz:
            # quantized (§2.12): scales [N, Hkv] shard BOTH ways with the
            # pool — blocks over seq, kv heads over model
            in_specs += (P(seq_axis, model_axis), P(seq_axis, model_axis))
            args += (k_scales, v_scales)
        return jax.shard_map(
            island, mesh=mesh, in_specs=in_specs,
            out_specs=P(bspec, model_axis, None, None),
            check_vma=False,
        )(*args)

    return attend


def flash_decode_attention(mesh, *, block_kv=128, seq_axes=("model",),
                           batch_axes=None):
    """Build the shard_map budgeted flash-decode: (q, kc, vc, ids, pos) -> o.

    kc/vc [B, Hkv, S, D] sharded on S over ``seq_axes``; ids
    [n_shards, Hkv, nb_loc] int32 GLOBAL block indices owned by each shard
    (-1 padding), sharded on axis 0.  q [B, H, 1, D] replicated over
    seq_axes.  Partial (acc, m, l) per shard; psum-merge over seq_axes.
    ``batch_axes``: axes sharding the batch dim (default: all of pod/data
    not used for seq; pass () when B is too small to shard — long_500k B=1).
    """
    if batch_axes is None:
        batch_axes = tuple(a for a in _batch_axes(mesh)
                           if a not in seq_axes)
    ba = tuple(batch_axes)
    bspec = ba[0] if len(ba) == 1 else (ba if ba else None)
    sspec = seq_axes[0] if len(seq_axes) == 1 else tuple(seq_axes)

    def attend(q, kc, vc, ids, pos, k_scales=None, v_scales=None):
        B, H, _, dh = q.shape
        hkv = kc.shape[1]
        G = H // hkv
        smax = kc.shape[2]
        n_shards = int(np.prod([mesh.shape[a] for a in seq_axes]))
        s_loc = smax // n_shards
        nblk_loc = s_loc // block_kv
        qz = k_scales is not None

        def island(q_l, kc_l, vc_l, ids_l, ks_l=None, vs_l=None):
            # q_l [B_l, H, 1, D]; kc_l [B_l, Hkv, S_loc, D];
            # ids_l [1, Hkv, nb_loc] (global block ids)
            if len(seq_axes) == 1:
                sidx = jax.lax.axis_index(seq_axes[0])
            else:
                sidx = jax.lax.axis_index(seq_axes)
            ids0 = ids_l[0]                                   # [Hkv, nb_loc]
            local = ids0 - sidx * nblk_loc
            ok = (ids0 >= 0) & (local >= 0) & (local < nblk_loc)
            local_ids = jnp.where(ok, local, -1)
            Bl = kc_l.shape[0]
            # fused budgeted flash-decode against the LOCAL cache shard —
            # streams only this shard's selected blocks, no dense gather.
            # Positions shift by the shard's token offset so the in-kernel
            # `kpos <= pos` mask matches global causality.
            pos_local = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (Bl,))
                         - sidx * nblk_loc * block_kv)
            out, m, l = ops.flash_decode(
                q_l, kc_l, vc_l,
                jnp.broadcast_to(local_ids[None],
                                 (Bl, hkv, local_ids.shape[-1])),
                pos_local, block_kv=block_kv, partials=True,
                k_scales=ks_l, v_scales=vs_l)
            # flash-decoding merge across seq shards
            ax = seq_axes if len(seq_axes) > 1 else seq_axes[0]
            gm = jax.lax.pmax(m, ax)                          # [B,hkv,G]
            w = jnp.exp(m - gm) * l
            den = jax.lax.psum(w, ax)
            num = jax.lax.psum(
                out.astype(jnp.float32).reshape(Bl, hkv, G, dh)
                * w[..., None], ax)
            o = num / jnp.maximum(den, 1e-30)[..., None]
            return o.reshape(Bl, H, 1, dh).astype(q_l.dtype)

        in_specs = (P(bspec, None, None, None),
                    P(bspec, None, sspec, None),
                    P(bspec, None, sspec, None),
                    P(sspec, None, None))
        args = (q, kc, vc, ids)
        if qz:
            # quantized (§2.12): scales [B, Hkv, S/blk] shard on the BLOCK
            # axis with their cache rows — the shard-local block ids index
            # the local scales slice directly
            in_specs += (P(bspec, None, sspec), P(bspec, None, sspec))
            args += (k_scales, v_scales)
        return jax.shard_map(
            island, mesh=mesh, in_specs=in_specs,
            out_specs=P(bspec, None, None, None),
            check_vma=False,
        )(*args)

    return attend
