"""Decoder-only transformer LM — the workhorse for 7 of the 10 assigned archs.

Covers: minitron-8b, smollm-135m, yi-6b (plain GQA); gemma3-1b (5:1
local:global pattern); granite-moe / llama4-scout (MoE FFN via
``repro.models.moe``); llava-next (backbone; patch embeddings injected via
``extra_embeddings``).

Functional style: explicit param pytrees, pure functions.  Layer loop is a
``lax.scan`` over stacked layer params when the config is uniform (fast
compile for the 40-cell dry-run) and a Python loop otherwise (exact per-
layer-kind FLOPs for local/global patterns).

Three entry points (all jit/pjit-compatible, O(1) HLO in seq len):
- :func:`forward`      — training forward: tokens -> logits.
- :func:`prefill`      — serving prefill: tokens -> (last logits, KV cache);
                         dense or S-HPLB sparse (work-list) attention.
- :func:`decode_step`  — one-token decode against the cache; dense or
                         budgeted-sparse (gathered KV blocks).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp

from repro.attention.flash_scan import flash_scan_attention
from repro.attention.worklist_jnp import (
    batched_worklist_attention,
    worklist_attention_paged,
)
from repro.attention.dense import attention_maps, decode_attention_ref
from repro.attention.rope import apply_rope
from repro.core import quant
from repro.kernels import ops as kernel_ops
from repro.models import common
from repro.models.moe import MoEConfig, moe_ffn, moe_init
from repro.sharding.ctx import constrain


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str = "lm"
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 2
    d_ff: int = 512
    vocab_size: int = 1024
    head_dim: int | None = None
    rope_theta: float = 10000.0
    max_seq_len: int = 131072
    # layer pattern, cycled: 'G' global causal, 'L' local sliding window
    attn_pattern: str = "G"
    local_window: int = 4096
    moe: MoEConfig | None = None
    dtype: Any = jnp.bfloat16
    block_q: int = 128
    block_kv: int = 128
    tie_embeddings: bool = True
    # "scan" = lax.scan over stacked layers (uniform pattern only),
    # "unroll" = python loop (needed for mixed local/global exact windows)
    layer_loop: str = "auto"

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def group_size(self) -> int:
        return self.num_heads // self.num_kv_heads

    def layer_kind(self, layer: int) -> str:
        return self.attn_pattern[layer % len(self.attn_pattern)]

    @property
    def uniform(self) -> bool:
        # MoE layers are structurally uniform too — scan-over-layers works
        return len(set(self.attn_pattern)) == 1

    @property
    def loop_mode(self) -> str:
        if self.layer_loop != "auto":
            return self.layer_loop
        return "scan" if self.uniform else "unroll"

    @property
    def num_params(self) -> int:
        """Exact parameter count (embeddings included once if tied)."""
        dh = self.head_dim_
        attn = self.d_model * dh * (self.num_heads * 2 +
                                    self.num_kv_heads * 2)
        if self.moe is not None:
            ffn = (self.d_model * self.moe.num_experts +      # router
                   3 * self.d_model * self.d_ff * self.moe.num_experts)
        else:
            ffn = 3 * self.d_model * self.d_ff
        norms = 2 * self.d_model
        per_layer = attn + ffn + norms
        embed = self.vocab_size * self.d_model
        head = 0 if self.tie_embeddings else embed
        return self.num_layers * per_layer + embed + head + self.d_model

    @property
    def active_params(self) -> int:
        """Active (per-token) params for MoE rooflines (6*N_active*D)."""
        if self.moe is None:
            return self.num_params
        dh = self.head_dim_
        attn = self.d_model * dh * (self.num_heads * 2 + self.num_kv_heads * 2)
        ffn = (self.d_model * self.moe.num_experts +
               3 * self.d_model * self.d_ff * self.moe.experts_per_token)
        per_layer = attn + ffn + 2 * self.d_model
        embed = self.vocab_size * self.d_model
        head = 0 if self.tie_embeddings else embed
        return self.num_layers * per_layer + embed + head + self.d_model


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _layer_init(rng, cfg: TransformerConfig):
    r_attn, r_ffn = jax.random.split(rng)
    p = {
        "attn": common.attn_init(
            r_attn, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim_, cfg.dtype),
        "ln1": common.rmsnorm_init(cfg.d_model),
        "ln2": common.rmsnorm_init(cfg.d_model),
    }
    if cfg.moe is not None:
        p["moe"] = moe_init(r_ffn, cfg.d_model, cfg.d_ff, cfg.moe, cfg.dtype)
    else:
        p["mlp"] = common.mlp_init(r_ffn, cfg.d_model, cfg.d_ff, cfg.dtype)
    return p


def init_params(rng, cfg: TransformerConfig):
    r_emb, r_layers, r_head = jax.random.split(rng, 3)
    layer_rngs = jax.random.split(r_layers, cfg.num_layers)
    if cfg.loop_mode == "scan":
        # stacked: every leaf gets leading [L] dim
        stacked = jax.vmap(lambda r: _layer_init(r, cfg))(layer_rngs)
        layers = stacked
    else:
        layers = [_layer_init(layer_rngs[i], cfg)
                  for i in range(cfg.num_layers)]
    params = {
        "embed": common.embed_init(r_emb, cfg.vocab_size, cfg.d_model,
                                   cfg.dtype),
        "layers": layers,
        "ln_f": common.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(
            r_head, cfg.d_model, cfg.vocab_size, cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# Attention sub-layer
# ---------------------------------------------------------------------------

def _qkv(x, ap, cfg: TransformerConfig, positions):
    """x [B,S,d] -> q [B,H,S,Dh], k/v [B,Hkv,S,Dh] with RoPE applied."""
    q = jnp.einsum("bsd,df->bsf", x, ap["wq"])
    k = jnp.einsum("bsd,df->bsf", x, ap["wk"])
    v = jnp.einsum("bsd,df->bsf", x, ap["wv"])
    q = common.split_heads(q, cfg.num_heads)
    k = common.split_heads(k, cfg.num_kv_heads)
    v = common.split_heads(v, cfg.num_kv_heads)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attend_dense(q, k, v, cfg: TransformerConfig, window, q_offset=0):
    return flash_scan_attention(
        q, k, v, causal=True, window=window, q_offset=q_offset,
        block_q=cfg.block_q, block_kv=cfg.block_kv)


def attention_layer(
    x, ap, cfg: TransformerConfig, *,
    window: int | None,
    positions,
    sparse_items=None,
    maps_out: list | None = None,
):
    """Full attention sub-layer (pre-norm residual outside)."""
    B = x.shape[0]
    q, k, v = _qkv(x, ap, cfg, positions)
    q = constrain(q, "batch", "model", None, None)
    k = constrain(k, "batch", "model", None, None)
    v = constrain(v, "batch", "model", None, None)
    if maps_out is not None:
        maps_out.append(attention_maps(q, k))
    if sparse_items is not None:
        o = batched_worklist_attention(
            q, k, v, jnp.asarray(sparse_items),
            block_q=cfg.block_q, block_kv=cfg.block_kv)
    else:
        o = _attend_dense(q, k, v, cfg, window)
    o = common.merge_heads(o)
    out = jnp.einsum("bsf,fd->bsd", o, ap["wo"])
    return constrain(out, "batch", None, None)


# ---------------------------------------------------------------------------
# Layer + model forward
# ---------------------------------------------------------------------------

def _ffn(x, lp, cfg: TransformerConfig):
    if cfg.moe is not None:
        return moe_ffn(x, lp["moe"], cfg.moe)
    h = common.swiglu(x, lp["mlp"]["gate"], lp["mlp"]["up"],
                      lp["mlp"]["down"])
    return constrain(h, "batch", None, None)


def _layer_fwd(x, lp, cfg: TransformerConfig, *, window, positions,
               sparse_items=None, maps_out=None):
    h = common.rmsnorm(x, lp["ln1"])
    x = x + attention_layer(h, lp["attn"], cfg, window=window,
                            positions=positions, sparse_items=sparse_items,
                            maps_out=maps_out)
    h = common.rmsnorm(x, lp["ln2"])
    x = x + _ffn(h, lp, cfg)
    return x


def _logits(x, params, cfg: TransformerConfig):
    x = common.rmsnorm(x, params["ln_f"])
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, w)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, w)
    return constrain(logits.astype(jnp.float32), "batch", None, "model")


def _window_of(cfg: TransformerConfig, layer: int) -> int | None:
    return cfg.local_window if cfg.layer_kind(layer) == "L" else None


def forward(params, tokens, cfg: TransformerConfig, *,
            extra_embeddings=None, maps_out=None, remat: bool = False):
    """Training/eval forward.  tokens [B, S] int32 -> logits [B, S, V] f32.

    extra_embeddings: optional [B, S_extra, d] prepended (VLM/audio stubs).
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    if extra_embeddings is not None:
        x = jnp.concatenate([extra_embeddings.astype(x.dtype), x], axis=1)
    x = constrain(x, "batch", None, None)
    S = x.shape[1]
    positions = jnp.arange(S)

    if cfg.loop_mode == "scan" and maps_out is None:
        body = lambda x, lp: (_layer_fwd(
            x, lp, cfg, window=_window_of(cfg, 0), positions=positions), None)
        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        for l in range(cfg.num_layers):
            fn = lambda x, lp, l=l: _layer_fwd(
                x, lp, cfg, window=_window_of(cfg, l), positions=positions,
                maps_out=maps_out)
            if remat:
                fn = jax.checkpoint(fn)
            x = fn(x, params["layers"][l])
    return _logits(x, params, cfg)


def loss_fn(params, batch, cfg: TransformerConfig, *, remat: bool = False):
    logits = forward(params, batch["tokens"], cfg, remat=remat)
    return common.cross_entropy(logits, batch["labels"],
                                batch.get("mask"))


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None):
    """Contiguous KV cache [L, 2, B, Hkv, Smax, Dh] for
    :func:`decode_step`; the pod dry-run (``launch/steps.py``) is its only
    caller — the serving engine keeps its KV in the paged pool."""
    dtype = dtype or cfg.dtype
    return jnp.zeros(
        (cfg.num_layers, 2, batch, cfg.num_kv_heads, max_len, cfg.head_dim_),
        dtype)


def init_paged_cache(cfg: TransformerConfig, num_blocks: int, block: int,
                     dtype=None):
    """Paged KV block pool [L, 2, N, Hkv, block, Dh] (DESIGN.md §2.7).

    ``num_blocks`` is the TOTAL physical block count — callers that want a
    trash block (``serving.kv_cache.PagedKVCache``) include it here.  The
    block is the single unit of device memory: sequences own scattered
    pool blocks through their block tables, so HBM scales with resident
    TOKENS, not with ``num_slots * max_seq_len``.
    """
    dtype = dtype or cfg.dtype
    return jnp.zeros(
        (cfg.num_layers, 2, num_blocks, cfg.num_kv_heads, block,
         cfg.head_dim_), dtype)


def init_paged_scales(cfg: TransformerConfig, num_blocks: int):
    """Dequant scales for a quantized paged pool: ``[L, 2, N, Hkv]`` f32,
    one per (K|V, physical block, kv head) tile (DESIGN.md §2.12).  Init
    is 1.0 — the neutral scale ``quantize_tiles`` assigns all-zero tiles,
    so unwritten blocks dequantize to the zeros they hold."""
    return jnp.ones((cfg.num_layers, 2, num_blocks, cfg.num_kv_heads),
                    jnp.float32)


def init_cache_scales(cfg: TransformerConfig, batch: int, max_len: int,
                      block: int):
    """Dequant scales for a quantized contiguous cache:
    ``[L, 2, B, Hkv, Smax/block]`` f32 (``max_len`` a block multiple).
    Like :func:`init_cache`, only the pod dry-run calls it."""
    assert max_len % block == 0, "quantized contiguous cache needs " \
        "max_len % block == 0 (scales are per block tile)"
    return jnp.ones((cfg.num_layers, 2, batch, cfg.num_kv_heads,
                     max_len // block), jnp.float32)


def scatter_seq_cache_paged(pool, seq_cache, table, *, scales=None,
                            kv_dtype: str = "bf16"):
    """Land a whole prefilled sequence cache in the pool (monolithic
    prefill's merge: one block scatter through the table).

    ``seq_cache [L, 2, 1, Hkv, S, Dh]`` with ``S`` a block multiple;
    ``table [T]`` int32 logical -> pool block (-1 pad).  Blocks past the
    mapped prefix (bucket padding) scatter into the trash block (the
    pool's last physical block).

    Quantized pool (DESIGN.md §2.12): pass ``scales [L, 2, N, Hkv]`` and
    the storage ``kv_dtype`` — each block tile quantizes AT SCATTER TIME
    (the full-precision sequence cache is a prefill temporary, never
    resident) and its scale scatters through the same ``gids``, so scale
    and block can never separate.  Returns ``(pool, scales)`` then.
    """
    L, _, _, hkv, S, dh = seq_cache.shape
    block = pool.shape[4]
    trash = pool.shape[2] - 1
    nblk = S // block
    blocks = jnp.moveaxis(
        seq_cache[:, :, 0].reshape(L, 2, hkv, nblk, block, dh), 3, 2)
    tbl = jnp.asarray(table, jnp.int32)[:nblk]
    gids = jnp.where(tbl >= 0, tbl, trash)
    if scales is None:
        return pool.at[:, :, gids].set(blocks.astype(pool.dtype))
    codes, s = quant.quantize_pool_blocks(blocks, kv_dtype)
    return (pool.at[:, :, gids].set(codes),
            scales.at[:, :, gids].set(s))


def prefill(params, tokens, cfg: TransformerConfig, *,
            cache_len: int | None = None,
            sparse_items=None,
            attn_override=None,
            extra_embeddings=None,
            last_index=None):
    """Prefill: tokens [B, S] -> (logits_last [B, V], cache).

    ``sparse_items``: per-layer work-lists [L][Litems, 7] (S-HPLB sparse
    prefill, single-device path) or None (dense).  ``attn_override(l, q, k,
    v) -> o`` replaces the attention compute entirely (the serving engine
    injects the shard_map S-HPLB island here).  The cache always stores the
    FULL K/V (sparsity reduces attention compute, not cache contents).
    ``last_index``: position of the last REAL token (traced scalar ok) —
    logits are read there instead of at row -1, so prompts padded up to a
    compile bucket still sample from the right row.
    """
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if extra_embeddings is not None:
        x = jnp.concatenate([extra_embeddings.astype(x.dtype), x], axis=1)
    x = constrain(x, "batch", None, None)
    Sx = x.shape[1]
    max_len = cache_len or Sx
    positions = jnp.arange(Sx)
    cache_k, cache_v = [], []

    def layer(x, lp, l):
        h = common.rmsnorm(x, lp["ln1"])
        q, k, v = _qkv(h, lp["attn"], cfg, positions)
        q = constrain(q, "batch", "model", None, None)
        k = constrain(k, "batch", "model", None, None)
        v = constrain(v, "batch", "model", None, None)
        items = None if sparse_items is None else sparse_items[l]
        if attn_override is not None:
            o = attn_override(l, q, k, v)
        elif items is not None:
            o = batched_worklist_attention(
                q, k, v, jnp.asarray(items),
                block_q=cfg.block_q, block_kv=cfg.block_kv)
        else:
            o = _attend_dense(q, k, v, cfg, _window_of(cfg, l))
        o = common.merge_heads(o)
        x = x + constrain(
            jnp.einsum("bsf,fd->bsd", o, lp["attn"]["wo"]), "batch", None, None)
        h2 = common.rmsnorm(x, lp["ln2"])
        x = x + _ffn(h2, lp, cfg)
        pad = max_len - Sx
        kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return x, kc, vc

    layers = params["layers"]
    if cfg.loop_mode == "scan":
        def body(x, lp):
            x, kc, vc = layer(x, lp, 0)
            return x, (kc, vc)
        x, (ks, vs) = jax.lax.scan(body, x, layers)
        cache = jnp.stack([ks, vs], axis=1)  # [L, 2, B, Hkv, Smax, Dh]
    else:
        for l in range(cfg.num_layers):
            x, kc, vc = layer(x, layers[l], l)
            cache_k.append(kc)
            cache_v.append(vc)
        cache = jnp.stack(
            [jnp.stack(cache_k), jnp.stack(cache_v)], axis=1)
    cache = constrain(cache, None, None, "batch", "model", None, None)
    if last_index is None:
        x_last = x[:, -1:, :]
    else:
        x_last = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(last_index, jnp.int32), 1, axis=1)
    logits = _logits(x_last, params, cfg)[:, 0]
    return logits, cache


def decode_step(params, cache, token, pos, cfg: TransformerConfig, *,
                block_ids=None, packed_items=None,
                cache_len: int | jnp.ndarray | None = None,
                active=None, attn_override=None,
                scales=None, kv_dtype: str = "bf16"):
    """One decode step over a contiguous cache.

    The serving engine decodes with :func:`decode_step_paged`; this step's
    only remaining caller is the pod dry-run (``launch/steps.py``), whose
    shard_map islands run the contiguous flash-decode kernel.

    token [B] int32; pos scalar OR [B] int32 (current position per
    sequence, 0-based — per-sequence positions enable continuous batching).
    cache [L, 2, B, Hkv, Smax, Dh]; returns (logits [B, V], new cache).

    ``block_ids``: selected KV blocks per layer/kv-head, ``[L, Hkv, nb]``
    (shared across slots) or ``[L, B, Hkv, nb]`` (per-slot, position-aware
    continuous batching) int32, -1 padded — S-HPLB budgeted decode.  The
    fused flash-decode streams ONLY those blocks from the cache (the
    memory-roofline win; no dense gather buffer).  ``packed_items``
    (mutually exclusive): ``[L, Lb, DEC_FIELDS]`` cost-packed ragged decode
    worklists per layer (DESIGN.md §2.8) — the same selections flattened to
    one (row, kv_head, kv_block) tile per item, so the attention grid is
    the true selected-block count, not ``B x Hkv x max-budget``.  None for
    both = dense decode over the full cache.  ``active``: optional [B] bool
    — slots marked False (free, or mid-chunked-prefill under mixed ticks)
    keep their cache rows UNTOUCHED; without it the batched step would
    clobber row ``pos`` (= 0 for padded slots) of every slot in the batch.
    ``attn_override(l, q, kc, vc) -> o [B, H, 1, Dh]`` replaces the
    attention compute (serving engine's shard_map island; with a quantized
    cache it receives two extra args ``(ks, vs) [B, Hkv, Smax/block_kv]``).

    Quantized cache (DESIGN.md §2.12): pass ``scales [L, 2, B, Hkv,
    Smax/block_kv]`` f32 and the storage ``kv_dtype``.  The token append
    becomes a gather -> :func:`repro.core.quant.insert_token_requant` ->
    scatter on the row's CURRENT block tile, the flash executors fold the
    scales into their post-dot rescale, and the return grows to
    ``(logits, cache, scales)``.  ``scales=None`` (default) leaves every
    code path — and its compiled program — bitwise identical to pre-§2.12.
    """
    assert block_ids is None or packed_items is None, \
        "block_ids and packed_items are mutually exclusive"
    packed = packed_items is not None
    sel = packed_items if packed else block_ids
    qz = scales is not None
    B = token.shape[0]
    x = jnp.take(params["embed"], token[:, None], axis=0)  # [B, 1, d]
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    smax = cache.shape[4]
    blkq = cfg.block_kv
    if qz:
        assert smax % blkq == 0, "quantized contiguous cache needs " \
            "Smax % block_kv == 0 (per-block scale tiles)"
    hkv, dh = cfg.num_kv_heads, cfg.head_dim_
    clen = pos_arr + 1 if cache_len is None else jnp.broadcast_to(
        jnp.asarray(cache_len), (B,))

    def layer(x, lp, layer_cache, layer_scales, l, items_l):
        h = common.rmsnorm(x, lp["ln1"])
        ap = lp["attn"]
        q = common.split_heads(jnp.einsum("bsd,df->bsf", h, ap["wq"]),
                               cfg.num_heads)
        k = common.split_heads(jnp.einsum("bsd,df->bsf", h, ap["wk"]),
                               cfg.num_kv_heads)
        v = common.split_heads(jnp.einsum("bsd,df->bsf", h, ap["wv"]),
                               cfg.num_kv_heads)
        rope = lambda t, p: apply_rope(t, p[None], cfg.rope_theta)
        q = jax.vmap(rope)(q, pos_arr)
        k = jax.vmap(rope)(k, pos_arr)
        if not qz:
            ks = vs = None
            if active is None:
                upd = lambda c, kn, p: jax.lax.dynamic_update_slice(
                    c, kn.astype(c.dtype), (0, p, 0))
                kc = jax.vmap(upd)(layer_cache[0], k, pos_arr)
                vc = jax.vmap(upd)(layer_cache[1], v, pos_arr)
            else:
                # inactive slots write their CURRENT row back (a no-op
                # update): the batched step must never mutate a freed or
                # mid-prefill slot
                def upd(c, kn, p, a):
                    cur = jax.lax.dynamic_slice(c, (0, p, 0), kn.shape)
                    kn = jnp.where(a, kn.astype(c.dtype), cur)
                    return jax.lax.dynamic_update_slice(c, kn, (0, p, 0))
                act = jnp.asarray(active)
                kc = jax.vmap(upd)(layer_cache[0], k, pos_arr, act)
                vc = jax.vmap(upd)(layer_cache[1], v, pos_arr, act)
        else:
            # quantized append: gather the row's CURRENT block tile + its
            # scale, requantize with the new token in place
            # (repro.core.quant.insert_token_requant), scatter both back.
            # Inactive rows keep tile and scale via the where — the
            # contiguous layout has no trash block to route junk into.
            act = (jnp.ones((B,), bool) if active is None
                   else jnp.asarray(active))
            blk_i = pos_arr // blkq                             # [B]
            offs = pos_arr % blkq
            rows = jnp.arange(B)[:, None]
            heads = jnp.arange(hkv)[None, :]

            def rmw(c, sc, tok):
                cur = jax.vmap(
                    lambda cr, bi: jax.lax.dynamic_slice(
                        cr, (0, bi * blkq, 0), (hkv, blkq, dh)))(c, blk_i)
                cur_s = jnp.take_along_axis(
                    sc, blk_i[:, None, None], axis=2)[:, :, 0]  # [B, Hkv]
                new_c, new_s = quant.insert_token_requant(
                    cur, cur_s, tok[:, :, 0, :], offs, kv_dtype)
                new_c = jnp.where(act[:, None, None, None], new_c, cur)
                new_s = jnp.where(act[:, None], new_s, cur_s)
                c = jax.vmap(
                    lambda cr, nc, bi: jax.lax.dynamic_update_slice(
                        cr, nc, (0, bi * blkq, 0)))(c, new_c, blk_i)
                return c, sc.at[rows, heads, blk_i[:, None]].set(new_s)

            kc, ks = rmw(layer_cache[0], layer_scales[0], k)
            vc, vs = rmw(layer_cache[1], layer_scales[1], v)
        window = _window_of(cfg, l)
        if attn_override is not None:
            o = (attn_override(l, q, kc, vc, ks, vs) if qz
                 else attn_override(l, q, kc, vc))
        elif items_l is not None and packed:
            # cost-packed ragged decode: the flat per-layer worklist drives
            # the grid — total selected tiles, not B x Hkv x max-budget
            o = kernel_ops.flash_decode_packed(
                q, kc, vc, items_l, pos_arr, block_kv=cfg.block_kv,
                window=window, k_scales=ks, v_scales=vs)
        elif items_l is not None:
            # fused budgeted flash-decode: stream only the selected blocks
            # from the cache in place (no [B, Hkv, nb*blk, Dh] gather).
            # items_l: [Hkv, nb] (shared) or [B, Hkv, nb] (per-slot).
            ids_b = (jnp.broadcast_to(items_l[None], (B,) + items_l.shape)
                     if items_l.ndim == 2 else items_l)
            o = kernel_ops.flash_decode(
                q, kc, vc, ids_b, pos_arr, block_kv=cfg.block_kv,
                window=window, k_scales=ks, v_scales=vs)
        else:
            kpos = jnp.arange(smax)
            valid = kpos[None] < clen[:, None]      # [B, Smax]
            if window is not None:
                valid = valid & (kpos[None] > (pos_arr[:, None] - window))
            if qz:
                deq = lambda c, s: (
                    c.reshape(B, hkv, -1, blkq, dh).astype(jnp.float32)
                    * s[..., None, None]).reshape(B, hkv, smax, dh)
                o = _decode_attend(q, deq(kc, ks), deq(vc, vs),
                                   valid[:, None], cfg)
            else:
                o = _decode_attend(q, kc, vc, valid[:, None], cfg)
        o = common.merge_heads(o)
        x = x + jnp.einsum("bsf,fd->bsd", o, lp["attn"]["wo"])
        h2 = common.rmsnorm(x, lp["ln2"])
        x = x + _ffn(h2, lp, cfg)
        if qz:
            return x, jnp.stack([kc, vc]), jnp.stack([ks, vs])
        return x, jnp.stack([kc, vc])

    if cfg.loop_mode == "scan":
        xs = [params["layers"], cache]
        if qz:
            xs.append(scales)
        if sel is not None:
            xs.append(jnp.asarray(sel))

        def body(x, scan_in):
            it = iter(scan_in)
            lp, layer_cache = next(it), next(it)
            layer_scales = next(it) if qz else None
            items_l = next(it) if sel is not None else None
            out = layer(x, lp, layer_cache, layer_scales, 0, items_l)
            return out[0], out[1:]
        x, ys = jax.lax.scan(body, x, tuple(xs))
        new_cache = ys[0]
        new_scales = ys[1] if qz else None
    else:
        new_layers, new_scale_layers = [], []
        for l in range(cfg.num_layers):
            items_l = None if sel is None else jnp.asarray(sel[l])
            out = layer(x, params["layers"][l], cache[l],
                        scales[l] if qz else None, l, items_l)
            x = out[0]
            new_layers.append(out[1])
            if qz:
                new_scale_layers.append(out[2])
        new_cache = jnp.stack(new_layers)
        new_scales = jnp.stack(new_scale_layers) if qz else None
    logits = _logits(x, params, cfg)[:, 0]
    if qz:
        return logits, new_cache, new_scales
    return logits, new_cache


def _decode_attend(q, k, v, valid, cfg: TransformerConfig):
    """q [B,H,1,Dh]; k/v [B,Hkv,Skv,Dh]; valid [B|1, Hkv|1, Skv] bool."""
    B, H, _, dh = q.shape
    hkv = k.shape[1]
    G = H // hkv
    qg = q.reshape(B, hkv, G, dh)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    s = jnp.where(valid[:, :, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", w, v.astype(jnp.float32))
    return o.reshape(B, H, 1, dh).astype(q.dtype)


def _chunk_attend(q, k, v, valid, cfg: TransformerConfig):
    """Masked multi-query attend for dense chunked prefill.

    q [B,H,C,Dh]; k/v [B,Hkv,Skv,Dh]; valid [B|1, Hkv|1, C, Skv] bool.
    The per-chunk validity mask (causal at a traced offset + kv length)
    cannot be a static pair list, so this computes the [C, Skv] score tile
    with a mask — fine at C x Smax chunk scale; a TPU deployment would swap
    in a Pallas chunk kernel with the same contract.
    """
    B, H, C, dh = q.shape
    hkv = k.shape[1]
    G = H // hkv
    qg = q.reshape(B, hkv, G, C, dh)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    s = jnp.where(valid[:, :, None, :, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", w, v.astype(jnp.float32))
    return o.reshape(B, H, C, dh).astype(q.dtype)


def _paged_layer_loop(layer, cfg: TransformerConfig, params, x, pool,
                      scales, sel):
    """Run ``layer(x, pool, scales, lp, l, window, items_l) -> (x, pool,
    scales)`` over every layer with the whole paged pool (and its scales)
    as the loop's carry: each layer scatters its K/V into ``pool[l]`` and
    reads that layer back, so under donation the pool is updated in place
    and never sliced out, stacked or rebuilt per layer.  ``sel`` ([L, ...]
    per-layer selections or None) is scanned with the layer params; the
    scan path is for uniform configs, whose every layer has layer 0's
    window.  Returns ``(x, pool, scales)``."""
    if cfg.loop_mode == "scan":
        window = _window_of(cfg, 0)

        def body(carry, scan_in):
            lp, l, items_l = scan_in
            return layer(*carry, lp, l, window, items_l), None
        carry, _ = jax.lax.scan(
            body, (x, pool, scales),
            (params["layers"], jnp.arange(cfg.num_layers),
             None if sel is None else jnp.asarray(sel)))
        return carry
    for l in range(cfg.num_layers):
        items_l = None if sel is None else jnp.asarray(sel[l])
        x, pool, scales = layer(x, pool, scales, params["layers"][l], l,
                                _window_of(cfg, l), items_l)
    return x, pool, scales


def prefill_chunk_paged(params, pool, tokens, table, q_offset,
                        cfg: TransformerConfig, *,
                        kv_len=None, sparse_items=None, last_index=None,
                        scales=None, kv_dtype: str = "bf16"):
    """Paged partial prefill (DESIGN.md §2.7): the chunk's K/V lands
    directly in the sequence's pool blocks (a block SCATTER at the
    table-translated indices — no staging cache, no final merge), and the
    chunk queries attend the resident prefix through the block table.

    tokens [1, C] int32 with C a whole number of cache blocks (the chunk
    compile bucket); pool [L, 2, N, Hkv, block, Dh]; ``table [T]`` int32
    logical -> pool block for THIS sequence (-1 pad — bucket-padding
    blocks past the prompt scatter into the trash block N-1);
    ``q_offset`` / ``kv_len`` / ``last_index`` are traced scalars and the
    table is data, so one compile per chunk bucket serves every sequence,
    offset, and block placement.  Sparse items execute via
    ``worklist_attention_paged`` (each item's tile gathered from the pool);
    dense chunks gather the table's blocks into one [Smax] view — O(one
    sequence).  Returns (logits [1, V] at chunk-local ``last_index``, new
    pool).

    The whole pool is the layer loop's carry: layer ``l`` scatters its
    blocks into ``pool[l]`` and reads ``pool[l]`` back for its attention,
    so under donation the pool is written in place — no per-layer copy of
    it is stacked into a new pool.

    Quantized pool (DESIGN.md §2.12): pass ``scales [L, 2, N, Hkv]`` f32 +
    the storage ``kv_dtype`` — the chunk's block tiles quantize at scatter
    time (scales scatter through the same ``gids``), sparse chunks fold
    the scales into ``worklist_attention_paged``'s post-dot rescale, dense
    chunks dequantize their gathered per-sequence view.  Returns
    ``(logits, pool, scales)`` then.
    """
    B, C = tokens.shape
    block = pool.shape[4]
    trash = pool.shape[2] - 1
    hkv, dh = cfg.num_kv_heads, cfg.head_dim_
    assert C % block == 0, "chunk bucket must span whole cache blocks"
    qz = scales is not None
    nblk = C // block
    q_offset = jnp.asarray(q_offset, jnp.int32)
    kv_len = (q_offset + C if kv_len is None
              else jnp.asarray(kv_len, jnp.int32))
    positions = q_offset + jnp.arange(C)
    tbl = jnp.asarray(table, jnp.int32)
    T = tbl.shape[0]
    ob = q_offset // block
    x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, "batch", None, None)

    def layer(x, pool, scales, lp, li, window, items_l):
        h = common.rmsnorm(x, lp["ln1"])
        q, k, v = _qkv(h, lp["attn"], cfg, positions)
        q = constrain(q, "batch", "model", None, None)
        # block-scatter the chunk's K/V through the table into layer li of
        # the whole pool, then read that layer back: the write comes first
        # so the read sees it and the scatter stays in place.  One index
        # per (block, kv head) tile keeps the write a scatter even for a
        # one-block chunk: as one whole block XLA turns it into a
        # bounds-checked update that reads the pool back, and at head_dim
        # 128 it then lays the carried pool out to suit the chunk's K,
        # copying the whole pool into and out of the layer loop.
        gsl = jax.lax.dynamic_slice(tbl, (ob,), (nblk,))
        gids = jnp.where(gsl >= 0, gsl, trash)[:, None]
        heads = jnp.arange(hkv)[None, :]
        as_blocks = lambda t: jnp.moveaxis(
            t[0].reshape(hkv, nblk, block, dh), 1, 0)
        if not qz:
            ks = vs = None
            pool = pool.at[li, 0, gids, heads].set(
                as_blocks(k).astype(pool.dtype))
            pool = pool.at[li, 1, gids, heads].set(
                as_blocks(v).astype(pool.dtype))
        else:
            kcodes, ksc = quant.quantize_pool_blocks(as_blocks(k), kv_dtype)
            vcodes, vsc = quant.quantize_pool_blocks(as_blocks(v), kv_dtype)
            pool = pool.at[li, 0, gids, heads].set(kcodes)
            pool = pool.at[li, 1, gids, heads].set(vcodes)
            scales = scales.at[li, 0, gids, heads].set(ksc)
            scales = scales.at[li, 1, gids, heads].set(vsc)
            ks, vs = scales[li, 0], scales[li, 1]
        if items_l is not None:
            # the tiles are gathered from layer li of the whole pool: a
            # slice of the layer would be a temporary the size of one
            # layer's K and V
            o = worklist_attention_paged(
                q[0], pool, items_l, tbl, li,
                block_q=cfg.block_q, block_kv=block,
                q_offset=q_offset, kv_len=kv_len,
                k_scales=ks, v_scales=vs)[None]
        else:
            rows = jnp.maximum(tbl, 0)
            if qz:
                # dequantized per-sequence view: gather codes AND scales
                # through the table, one broadcast multiply (O(sequence) —
                # the same staging traffic the dense chunk already pays)
                view = lambda kv, s: (
                    jnp.moveaxis(pool[li, kv, rows], 0, 1).astype(jnp.float32)
                    * jnp.moveaxis(jnp.take(s, rows, axis=0),
                                   0, 1)[:, :, None, None]
                ).reshape(hkv, T * block, dh)
                kview, vview = view(0, ks), view(1, vs)
            else:
                view = lambda kv: jnp.moveaxis(
                    pool[li, kv, rows], 0, 1).reshape(hkv, T * block, dh)
                kview, vview = view(0), view(1)
            kpos = jnp.arange(T * block)
            valid = ((kpos[None, :] <= positions[:, None])
                     & (kpos[None, :] < kv_len))          # [C, T*block]
            if window is not None:
                valid = valid & (kpos[None, :] > positions[:, None] - window)
            o = _chunk_attend(q, kview[None], vview[None],
                              valid[None, None], cfg)
        o = common.merge_heads(o)
        x = x + jnp.einsum("bsf,fd->bsd", o, lp["attn"]["wo"])
        h2 = common.rmsnorm(x, lp["ln2"])
        x = x + _ffn(h2, lp, cfg)
        return x, pool, scales

    x, pool, scales = _paged_layer_loop(layer, cfg, params, x, pool, scales,
                                        sparse_items)
    if last_index is None:
        x_last = x[:, -1:, :]
    else:
        x_last = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(last_index, jnp.int32), 1, axis=1)
    logits = _logits(x_last, params, cfg)[:, 0]
    if qz:
        return logits, pool, scales
    return logits, pool


def _merge_stripe_partials(parts, B, hkv, dh, dtype):
    """Combine per-stripe flash-decode partials (DESIGN.md §2.11).

    ``parts``: list of ``(out [B, H, 1, dh] f32, m [B, Hkv, G],
    l [B, Hkv, G])`` — one per virtual seq stripe.  Stacks them on a
    leading stripe axis and applies the exact flash-decoding
    ``(out, m, l)`` merge; fully-masked stripes (``l == 0``) drop out of
    the combine identically (no 0/0).  Returns ``[B, H, 1, dh]``.
    """
    outs = jnp.stack([o.reshape(B, hkv, -1, dh) for o, _, _ in parts])
    ms = jnp.stack([m for _, m, _ in parts])
    ls = jnp.stack([l for _, _, l in parts])
    merged = kernel_ops.merge_partials(outs, ms, ls)   # [B, Hkv, G, dh]
    return merged.reshape(B, -1, 1, dh).astype(dtype)


def decode_step_paged(params, pool, token, pos, table,
                      cfg: TransformerConfig, *,
                      block_ids=None, packed_items=None, cache_len=None,
                      active=None, seq_stripes: int = 1,
                      stripe_size: int | None = None,
                      scales=None, kv_dtype: str = "bf16"):
    """One paged decode step (DESIGN.md §2.7).

    token [B] int32; pos scalar OR [B] int32; pool [L, 2, N, Hkv, block,
    Dh]; ``table [B, T]`` int32 per-slot block tables (logical -> pool
    block, -1 = unmapped/free slot).  The whole pool is the layer loop's
    carry: layer ``l`` scatters each row's K/V token into its current
    block of ``pool[l]``, then reads ``pool[l]`` for its attention, so
    under donation the pool is written in place and never rebuilt.  Rows
    that are inactive (``active`` False) or unmapped write the trash block
    N-1, so the batched step never needs a read-modify-write mask.
    ``block_ids``
    ([L, Hkv, nb] or [L, B, Hkv, nb], LOGICAL, -1 pad) select the blocks
    the budgeted flash-decode streams from the pool through the table;
    ``packed_items`` (mutually exclusive): ``[L, Lb, DEC_FIELDS]``
    cost-packed ragged decode worklists per layer (DESIGN.md §2.8, kv
    blocks LOGICAL).  None for both = dense decode over the resident
    prefix (gathered through the table into one per-sequence view).
    Returns (logits [B, V], new pool).

    The decode kernel's operand follows head_dim: at a multiple of 128 it
    gets the whole carried pool and the layer index and copies its tiles
    from ``pool[l]`` as it lies; at 64 it gets ``pool[l, 0]`` and
    ``pool[l, 1]``, sliced out per layer and padded to 128 lanes (ROADMAP
    S7b).  The two forms compute the same tiles bit for bit.

    Sequence striping (DESIGN.md §2.11): ``seq_stripes > 1`` emulates the
    2D head x sequence mesh on one device — the pool's usable blocks are
    owned in contiguous ``stripe_size`` ranges by ``seq_stripes`` virtual
    seq shards, attention runs one partial pass per stripe over only that
    stripe's blocks, and partials combine via the flash-decoding
    ``(out, m, l)`` merge (``kernels.flash_decode.merge_partials``) —
    exactly the algebra the ``flash_decode_attention_2d`` island performs
    with one psum/pmax collective along ``seq``.  ``packed_items`` then
    carries per-stripe lists ``[L, S, Lb, DEC_FIELDS]``; ``block_ids``
    and dense mode restrict each pass via a stripe-masked table.  The KV
    write is stripe-oblivious (the table routes it to the owning block).

    Quantized pool (DESIGN.md §2.12): pass ``scales [L, 2, N, Hkv]`` f32
    and the storage ``kv_dtype``.  The single-block token write becomes a
    gather -> :func:`repro.core.quant.insert_token_requant` -> full-block
    scatter (inactive/unmapped rows still collapse onto the trash block —
    its codes AND scale are junk by the same contract), the flash
    executors take the PHYSICAL-indexed scales next to the pool, and the
    return grows to ``(logits, pool, scales)``.  ``scales=None`` keeps
    every path bitwise pre-§2.12.
    """
    assert block_ids is None or packed_items is None, \
        "block_ids and packed_items are mutually exclusive"
    if seq_stripes > 1:
        assert stripe_size is not None, \
            "striped decode needs the allocator's stripe_size"
    packed = packed_items is not None
    sel = packed_items if packed else block_ids
    qz = scales is not None
    B = token.shape[0]
    block = pool.shape[4]
    trash = pool.shape[2] - 1
    hkv, dh = cfg.num_kv_heads, cfg.head_dim_
    x = jnp.take(params["embed"], token[:, None], axis=0)  # [B, 1, d]
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    clen = pos_arr + 1 if cache_len is None else jnp.broadcast_to(
        jnp.asarray(cache_len), (B,))
    tbl = jnp.asarray(table, jnp.int32)
    T = tbl.shape[1]
    act = (jnp.ones((B,), bool) if active is None
           else jnp.asarray(active))

    def layer(x, pool, scales, lp, li, window, items_l):
        h = common.rmsnorm(x, lp["ln1"])
        ap = lp["attn"]
        q = common.split_heads(jnp.einsum("bsd,df->bsf", h, ap["wq"]),
                               cfg.num_heads)
        k = common.split_heads(jnp.einsum("bsd,df->bsf", h, ap["wk"]),
                               cfg.num_kv_heads)
        v = common.split_heads(jnp.einsum("bsd,df->bsf", h, ap["wv"]),
                               cfg.num_kv_heads)
        rope = lambda t, p: apply_rope(t, p[None], cfg.rope_theta)
        q = jax.vmap(rope)(q, pos_arr)
        k = jax.vmap(rope)(k, pos_arr)

        # one vectorized scatter per tensor into layer li of the whole
        # pool: row b lands at (pool block, row offset) = (table[b,
        # pos//block], pos % block); inactive/unmapped rows collapse onto
        # the trash block (their values are junk by contract, so duplicate
        # trash hits are fine).  The layer's K/V are read back after the
        # write, so the scatter stays in place on the carried pool.
        phys = jnp.take_along_axis(tbl, (pos_arr // block)[:, None],
                                   axis=1)[:, 0]
        gids = jnp.where(act & (phys >= 0), phys, trash)       # [B]
        offs = pos_arr % block                                 # [B]
        heads = jnp.arange(hkv)

        if not qz:
            ks = vs = None

            def write(pool, kv, new):
                return pool.at[li, kv, gids[:, None], heads[None, :],
                               offs[:, None]].set(
                    new[:, :, 0, :].astype(pool.dtype))

            pool = write(write(pool, 0, k), 1, v)
        else:
            # quantized append: gather each row's current block tile +
            # scale, requantize with the new token, scatter the FULL tile
            # back (same B-blocks-per-layer traffic class as the gathers
            # the attention itself performs; inactive rows hit the trash
            # block, whose codes/scale are junk by contract)
            def rmw(pool, scales, kv, tok):
                cur = pool[li, kv, gids]                 # [B, Hkv, blk, Dh]
                cur_s = scales[li, kv, gids]             # [B, Hkv]
                new_c, new_s = quant.insert_token_requant(
                    cur, cur_s, tok[:, :, 0, :], offs, kv_dtype)
                return (pool.at[li, kv, gids].set(new_c),
                        scales.at[li, kv, gids].set(new_s))

            pool, scales = rmw(*rmw(pool, scales, 0, k), 1, v)
            ks, vs = scales[li, 0], scales[li, 1]
        # the kernel's pool operand: at a head_dim of whole 128-lane tiles
        # it copies layer li's tiles from the carried pool itself; at 64 it
        # takes the layer's K and V, padded (ROADMAP S7b)
        if dh % 128 == 0:
            kc, vc, at = pool, None, {"layer": li}
        else:
            kc, vc, at = pool[li, 0], pool[li, 1], {}

        def stripe_table(s):
            # entries another stripe owns become -1 (masked): each block
            # is computed by exactly the stripe that physically holds it
            mine = (tbl >= 0) & (tbl // stripe_size == s)
            return jnp.where(mine, tbl, -1)

        if items_l is not None and packed:
            if seq_stripes > 1:
                # items_l [S, Lb, F]: one partial pass per stripe (the
                # per-stripe split already routes every run's sub-runs to
                # their owning stripes), then the flash-decoding merge —
                # the single-device twin of the island's psum over 'seq'
                parts = [kernel_ops.flash_decode_packed_paged(
                    q, kc, vc, items_l[s], tbl, pos_arr, block_kv=block,
                    window=window, partials=True, k_scales=ks, v_scales=vs,
                    **at)
                    for s in range(seq_stripes)]
                o = _merge_stripe_partials(parts, B, hkv, dh, q.dtype)
            else:
                o = kernel_ops.flash_decode_packed_paged(
                    q, kc, vc, items_l, tbl, pos_arr, block_kv=block,
                    window=window, k_scales=ks, v_scales=vs, **at)
        elif items_l is not None:
            ids_b = (jnp.broadcast_to(items_l[None], (B,) + items_l.shape)
                     if items_l.ndim == 2 else items_l)
            if seq_stripes > 1:
                parts = [kernel_ops.flash_decode_paged(
                    q, kc, vc, ids_b, stripe_table(s), pos_arr,
                    block_kv=block, window=window, partials=True,
                    k_scales=ks, v_scales=vs, **at)
                    for s in range(seq_stripes)]
                o = _merge_stripe_partials(parts, B, hkv, dh, q.dtype)
            else:
                o = kernel_ops.flash_decode_paged(
                    q, kc, vc, ids_b, tbl, pos_arr, block_kv=block,
                    window=window, k_scales=ks, v_scales=vs, **at)
        elif seq_stripes > 1:
            # dense under striping: every resident logical block selected,
            # each stripe streams only its own via the masked table
            ids_all = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                       (B, hkv, T))
            parts = [kernel_ops.flash_decode_paged(
                q, kc, vc, ids_all, stripe_table(s), pos_arr,
                block_kv=block, window=window, partials=True,
                k_scales=ks, v_scales=vs, **at)
                for s in range(seq_stripes)]
            o = _merge_stripe_partials(parts, B, hkv, dh, q.dtype)
        else:
            kc, vc = pool[li, 0], pool[li, 1]
            if qz:
                # dequantized per-row view: gather codes AND scales
                # through the table, one broadcast multiply — the dense
                # fallback already pays the O(B x resident) gather
                view = lambda c, s: (
                    jnp.moveaxis(jnp.take(c, jnp.maximum(tbl, 0), axis=0),
                                 1, 2).astype(jnp.float32)
                    * jnp.moveaxis(jnp.take(s, jnp.maximum(tbl, 0), axis=0),
                                   1, 2)[..., None, None]
                ).reshape(B, hkv, T * block, dh)
                kview, vview = view(kc, ks), view(vc, vs)
            else:
                view = lambda c: jnp.moveaxis(
                    jnp.take(c, jnp.maximum(tbl, 0), axis=0), 1, 2
                ).reshape(B, hkv, T * block, dh)
                kview, vview = view(kc), view(vc)
            kpos = jnp.arange(T * block)
            valid = kpos[None] < clen[:, None]            # [B, T*block]
            if window is not None:
                valid = valid & (kpos[None] > (pos_arr[:, None] - window))
            o = _decode_attend(q, kview, vview, valid[:, None], cfg)
        o = common.merge_heads(o)
        x = x + jnp.einsum("bsf,fd->bsd", o, lp["attn"]["wo"])
        h2 = common.rmsnorm(x, lp["ln2"])
        x = x + _ffn(h2, lp, cfg)
        return x, pool, scales

    x, pool, scales = _paged_layer_loop(layer, cfg, params, x, pool, scales,
                                        sel)
    logits = _logits(x, params, cfg)[:, 0]
    if qz:
        return logits, pool, scales
    return logits, pool


# ---------------------------------------------------------------------------
# Plan-epoch support: online recovery telemetry + KV-cache re-permutation
# (DESIGN.md §2.9)
# ---------------------------------------------------------------------------

def permute_cache_kv_heads(cache, kv_perm):
    """Re-permute the kv-head axis of a resident KV cache for a plan-epoch
    swap.

    ``cache``: the paged pool ``[L, 2, N, Hkv, block, Dh]`` (any tensor
    with kv heads on axis 3).
    ``kv_perm [L, Hkv]``: per-layer delta shuffle from
    :meth:`repro.core.planner.PlanDelta.kv_perm_table` (new slot ->
    previous slot).  Weights permuted by the delta expect the cache's
    kv-head slots shuffled the same way; this one gather is the entire
    device-side cost of an epoch swap.
    """
    idx = jnp.asarray(kv_perm, jnp.int32)[:, None, None, :, None, None]
    return jnp.take_along_axis(cache, idx, axis=3)


def permute_cache_scales(scales, kv_perm):
    """Scales twin of :func:`permute_cache_kv_heads` (DESIGN.md §2.12):
    the same per-layer kv-head gather applied to the dequant-scales
    tensor ``[L, 2, N, Hkv]`` (kv heads on axis 3) — so an epoch swap
    moves every block's scale with its codes in the same jit."""
    idx = jnp.asarray(kv_perm, jnp.int32).reshape(
        scales.shape[0], 1, 1, scales.shape[3])
    return jnp.take_along_axis(scales, idx, axis=3)


def decode_telemetry(params, cache, token, pos, cfg: TransformerConfig, *,
                     block_ids, cache_len, table, scales=None,
                     with_health=False):
    """Quest-bound estimate of the recovery each head's selection realizes.

    The in-graph half of the online sparsity telemetry (DESIGN.md §2.9):
    re-runs one decode forward over the RESIDENT cache prefix (the current
    token's K/V are not yet written — ``cache_len`` is the per-row resident
    length, i.e. ``pos``) and per layer computes, from the same per-block
    key min/max summaries Quest uses for selection
    (:func:`repro.attention.policies.quest_block_scores`), the fraction of
    estimated attention mass the plan's selected blocks capture:

        ``rec[l, b, h] = sum_{blk in sel} w / sum_{blk causal} w``,
        ``w = exp(ub - max ub) * resident_tokens(blk)``

    plus the normalized budget actually spent, ``frac[l, b, h] =
    selected resident tokens / cache_len``.  Hidden states propagate
    through DENSE attention over the prefix (an estimator forward, not the
    serving step: nothing is sampled and no cache is written), so the
    probe is a separate un-donated jit the engine runs every
    ``telemetry_every`` ticks.

    ``cache``: the paged pool ``[L, 2, N, Hkv, block, Dh]``, read through
    ``table [B, T]`` (logical -> pool block, -1 pad).  ``block_ids``:
    ``[L, B, Hkv, nb]`` LOGICAL selections, -1 pad — exactly the engine's
    position-aware decode tables.  Returns ``(rec, frac)`` both
    ``[L, B, H]`` float32 (rows with ``cache_len == 0`` return garbage the
    caller must mask — the engine filters to active slots).

    Quantized cache (DESIGN.md §2.12): pass ``scales [L, 2, N, Hkv]`` —
    the probe's Quest summaries and its dense estimator forward both run on
    DEQUANTIZED values, so realized-recovery estimates (and hence drift /
    replans) reflect what decode attention actually computes.

    ``with_health`` additionally returns ``fin [B]`` bool — whether each
    row's hidden state stayed finite through ALL layers (the deep sentinel
    of DESIGN.md §2.13: a corrupted KV block poisons the estimator forward
    exactly like the serving step, so the probe doubles as a per-sequence
    health check with no extra pass).
    """
    B = token.shape[0]
    hkv, dh = cfg.num_kv_heads, cfg.head_dim_
    n_rep = cfg.num_heads // hkv
    blk = cache.shape[4]
    x = jnp.take(params["embed"], token[:, None], axis=0)  # [B, 1, d]
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    tbl = jnp.asarray(table, jnp.int32)
    nkvb = tbl.shape[1]
    skv = nkvb * blk
    kpos = jnp.arange(nkvb * blk)
    valid = kpos[None] < clen[:, None]                    # [B, Skv]
    ntok = jnp.clip(clen[:, None] - jnp.arange(nkvb)[None] * blk,
                    0, blk)                               # [B, nkvb]

    layers = params["layers"]
    stacked = not isinstance(layers, (list, tuple))

    def layer_fn(x, lp, layer_cache, layer_scales, l, ids_l):
        h = common.rmsnorm(x, lp["ln1"])
        ap = lp["attn"]
        q = common.split_heads(jnp.einsum("bsd,df->bsf", h, ap["wq"]),
                               cfg.num_heads)
        rope = lambda t, p: apply_rope(t, p[None], cfg.rope_theta)
        q = jax.vmap(rope)(q, pos_arr)                    # [B, H, 1, Dh]
        if layer_scales is None:
            view = lambda c: jnp.moveaxis(
                jnp.take(c, jnp.maximum(tbl, 0), axis=0), 1, 2
            ).reshape(B, hkv, skv, dh)
            kc, vc = view(layer_cache[0]), view(layer_cache[1])
        else:
            # dequantized view: scales gather through the same table (the
            # probe is un-donated and O(B x resident) already)
            view = lambda c, s: (
                jnp.moveaxis(jnp.take(c, jnp.maximum(tbl, 0), axis=0),
                             1, 2).astype(jnp.float32)
                * jnp.moveaxis(jnp.take(s, jnp.maximum(tbl, 0), axis=0),
                               1, 2)[..., None, None]
            ).reshape(B, hkv, skv, dh)
            kc = view(layer_cache[0], layer_scales[0])
            vc = view(layer_cache[1], layer_scales[1])
        # -- per-block Quest summaries over the RESIDENT prefix ------------
        kb = kc.reshape(B, hkv, nkvb, blk, dh)
        vmask = valid.reshape(B, 1, nkvb, blk, 1)
        kmin = jnp.where(vmask, kb, jnp.inf).min(axis=3)  # [B, Hkv, nkvb, d]
        kmax = jnp.where(vmask, kb, -jnp.inf).max(axis=3)
        has = vmask.any(axis=3)                           # [B, Hkv, nkvb, 1]
        kmin = jnp.where(has, kmin, 0.0)
        kmax = jnp.where(has, kmax, 0.0)
        kmin = jnp.repeat(kmin, n_rep, axis=1)            # [B, H, nkvb, d]
        kmax = jnp.repeat(kmax, n_rep, axis=1)
        qf = q[:, :, 0, :].astype(jnp.float32) * (dh ** -0.5)
        ub = (jnp.einsum("bhd,bhkd->bhk", jnp.maximum(qf, 0.0),
                         kmax.astype(jnp.float32))
              + jnp.einsum("bhd,bhkd->bhk", jnp.minimum(qf, 0.0),
                           kmin.astype(jnp.float32)))     # [B, H, nkvb]
        bvalid = has[:, :, :, 0]            # [B, 1, nkvb] (broadcasts to H)
        ub = jnp.where(bvalid, ub, -jnp.inf)
        m = jnp.exp(ub - jnp.max(ub, axis=-1, keepdims=True))
        ntok_f = ntok[:, None].astype(jnp.float32)        # [B, 1, nkvb]
        w = jnp.where(bvalid, m, 0.0) * ntok_f            # [B, H, nkvb]
        # -- the plan's selection, as a block mask -------------------------
        sel = (ids_l[..., None] == jnp.arange(nkvb)[None, None, None]
               ).any(axis=2)                              # [B, Hkv, nkvb]
        sel = jnp.repeat(sel, n_rep, axis=1) & bvalid     # [B, H, nkvb]
        tot = jnp.maximum(w.sum(-1), 1e-30)
        rec_l = jnp.where(sel, w, 0.0).sum(-1) / tot      # [B, H]
        sel_tok = jnp.where(sel, ntok_f, 0.0).sum(-1)
        frac_l = sel_tok / jnp.maximum(clen[:, None], 1)  # [B, H]
        # -- propagate hidden state (dense estimator forward) --------------
        o = _decode_attend(q, kc, vc, valid[:, None], cfg)
        o = common.merge_heads(o)
        x = x + jnp.einsum("bsf,fd->bsd", o, lp["attn"]["wo"])
        h2 = common.rmsnorm(x, lp["ln2"])
        x = x + _ffn(h2, lp, cfg)
        return x, rec_l.astype(jnp.float32), frac_l.astype(jnp.float32)

    recs, fracs = [], []
    ids = jnp.asarray(block_ids, jnp.int32)
    for l in range(cfg.num_layers):
        lp = (jax.tree.map(lambda t: t[l], layers) if stacked
              else layers[l])
        x, rec_l, frac_l = layer_fn(
            x, lp, cache[l], None if scales is None else scales[l],
            l, ids[l])
        recs.append(rec_l)
        fracs.append(frac_l)
    if with_health:
        fin = jnp.isfinite(x).all(axis=(1, 2))            # [B]
        return jnp.stack(recs), jnp.stack(fracs), fin
    return jnp.stack(recs), jnp.stack(fracs)
