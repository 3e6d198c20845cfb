"""Quantized KV-cache block math (DESIGN.md §2.12).

The paged pool stores KV blocks in int8 (or fp8 e4m3 where the backend
supports it) with ONE f32 scale per (block, kv-head) tile — the same
granularity as sparsity selection and head-parallel balance, so a scale
travels with its block through every gather the engine performs (swap to
host, epoch re-permute, stripe merge).  Quantization is symmetric
absmax:

    scale = max(|x|) / qmax          over the [block, Dh] tile
    codes = round(x / scale)         (int8)  |  (x / scale).astype(f8)

Dequantization is LINEAR in the codes, so the flash-decode executors
never materialize a dequantized pool: the per-tile scale multiplies the
QK^T logits and the p·V partial AFTER the dot (``(q·k) * s == q·(k*s)``
up to f32 rounding), and the jnp references feed the int8/fp8 tiles to
``lax.dot_general`` directly (mixed-dtype dot, f32 accumulate) — the
convert-of-slice hoist that would silently rebuild a full-precision pool
copy cannot happen because no convert of the pool ever appears.

Decode appends one token per tick into a partially-filled block, which
needs a REQUANTIZE-in-place: the block's scale only ever grows within a
sequence (``max(old_scale, token_absmax/qmax)``), existing codes are
rescaled by ``old/new`` (an exact no-op while the scale is unchanged),
and the first token of a block (``offset == 0``) resets the scale so a
reused block never inherits a freed sequence's range.

Everything here is layout-free math on ``[..., block, Dh]`` tiles; the
pool/scales layouts live in ``serving.kv_cache`` and
``models.transformer``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# engine-facing names -> storage dtypes; "bf16" is the unquantized
# default (no scales tensor exists, every code path is pre-§2.12)
KV_DTYPES = {
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
    "fp8": jnp.float8_e4m3fn,
}
# symmetric range of the code dtype (e4m3fn max finite = 448)
QMAX = {"int8": 127.0, "fp8": 448.0}


def is_quantized(kv_dtype: str) -> bool:
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {sorted(KV_DTYPES)}, got {kv_dtype!r}")
    return kv_dtype != "bf16"


def kv_cache_dtype(kv_dtype: str, default=None):
    """Storage dtype for the pool; ``default`` (model dtype) for bf16."""
    if is_quantized(kv_dtype):
        return KV_DTYPES[kv_dtype]
    return default


def kv_dtype_bytes(kv_dtype: str, *, block: int = 128,
                   head_dim: int = 64) -> float:
    """True bytes per cached element INCLUDING the amortized per-(block,
    kv-head) f32 scale — what the byte-true cost model charges per token
    streamed (``launch/costs.py``) and what the packer weighs."""
    if not is_quantized(kv_dtype):
        return float(jnp.dtype(jnp.bfloat16).itemsize)
    payload = float(jnp.dtype(KV_DTYPES[kv_dtype]).itemsize)
    return payload + 4.0 / (block * head_dim)


def _encode(x: jnp.ndarray, kv_dtype: str) -> jnp.ndarray:
    """f32 values already divided by scale -> storage codes."""
    if kv_dtype == "int8":
        return jnp.clip(jnp.round(x), -QMAX["int8"],
                        QMAX["int8"]).astype(jnp.int8)
    return x.astype(jnp.float8_e4m3fn)


def quantize_tiles(x: jnp.ndarray, kv_dtype: str):
    """Quantize ``[..., block, Dh]`` tiles; one scale per leading index.

    Returns ``(codes [..., block, Dh], scales [...] f32)``.  All-zero
    tiles get scale 1.0 (codes are zero either way), so dequantization
    never divides by or multiplies with zero scales.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / QMAX[kv_dtype], 1.0)
    codes = _encode(xf / scale[..., None, None], kv_dtype)
    return codes, scale


def dequantize_tiles(codes: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """``[..., block, Dh]`` codes + ``[...]`` scales -> f32 values.  For
    telemetry / dense fallbacks only — the flash executors fold the scale
    into the post-dot rescale instead of materializing this."""
    return codes.astype(jnp.float32) * scales[..., None, None]


def insert_token_requant(blk: jnp.ndarray, scale: jnp.ndarray,
                         tok: jnp.ndarray, offs: jnp.ndarray,
                         kv_dtype: str):
    """Insert one decode token into a quantized block, rescaling in place.

    ``blk [B, Hkv, block, Dh]`` gathered codes, ``scale [B, Hkv]`` their
    current scales, ``tok [B, Hkv, Dh]`` the new token's full-precision
    K (or V) vectors, ``offs [B]`` in-block write offsets.  Returns the
    updated ``(codes, scales)``:

    - ``offs == 0`` starts a fresh block: prior codes are a freed
      sequence's garbage (attention masks them by position, but their
      absmax must not leak into the new scale) — content zeroed, scale
      reset to the token's own range;
    - ``offs > 0`` grows the scale monotonically
      (``max(old, token_absmax/qmax)``) and rescales existing codes by
      ``old/new`` — an exact identity while the scale is unchanged
      (``round(c * 1.0) == c``), at most 1/2 LSB drift when it grows.
    """
    qmax = QMAX[kv_dtype]
    B, hkv = scale.shape
    tokf = tok.astype(jnp.float32)
    tmax = jnp.abs(tokf).max(axis=-1)                       # [B, Hkv]
    tok_scale = jnp.where(tmax > 0, tmax / qmax, 1.0)
    fresh = (offs == 0)[:, None]                            # [B, 1]
    new_scale = jnp.where(fresh, tok_scale,
                          jnp.maximum(scale, tok_scale))
    ratio = scale / new_scale
    vals = blk.astype(jnp.float32) * ratio[..., None, None]
    vals = jnp.where(fresh[..., None, None], 0.0, vals)
    codes = _encode(vals, kv_dtype)
    tok_codes = _encode(tokf / new_scale[..., None], kv_dtype)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    heads = jnp.arange(hkv, dtype=jnp.int32)[None, :]
    codes = codes.at[rows, heads, offs[:, None]].set(tok_codes)
    return codes, new_scale


def quantize_pool_blocks(blocks: jnp.ndarray, kv_dtype: str):
    """Quantize pool-layout blocks ``[..., Hkv, block, Dh]`` -> codes of
    the same shape + scales ``[..., Hkv]`` (one per (block, kv-head))."""
    return quantize_tiles(blocks, kv_dtype)


def roundtrip_error_bound(kv_dtype: str) -> float:
    """Worst-case elementwise |dequant(quant(x)) - x| / tile_absmax.

    int8: half an LSB of the absmax/127 grid.  fp8 e4m3: 2^-3 relative
    mantissa step at the top binade of the 448-scaled range."""
    if kv_dtype == "int8":
        return 0.5 / QMAX["int8"]
    return 2.0 ** -4 + 1e-6   # e4m3: 3 mantissa bits -> rel err <= 2^-4


__all__ = [
    "KV_DTYPES",
    "QMAX",
    "dequantize_tiles",
    "insert_token_requant",
    "is_quantized",
    "kv_cache_dtype",
    "kv_dtype_bytes",
    "quantize_pool_blocks",
    "quantize_tiles",
    "roundtrip_error_bound",
]
