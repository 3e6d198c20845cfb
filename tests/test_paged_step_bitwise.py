"""The paged decode and chunk steps against outputs recorded from an
earlier implementation, at a few layers and a small pool.

Each case runs ``decode_step_paged`` or ``prefill_chunk_paged`` on inputs
made from a fixed numpy seed (weights too, so nothing depends on JAX's
PRNG) and checks two things:

(a) the logits, the pool positions the step writes, and the scales are
    bitwise equal to ``tests/data/paged_step_bitwise.npz``;
(b) the returned pool (and scales) equal the input everywhere else, so
    the step writes exactly the positions its contract names: one token
    row per (layer, K|V, row, kv head) for a bf16 decode, whole block
    tiles for a quantized decode, the chunk's blocks for a chunk, with
    inactive rows and unmapped blocks landing on the trash block N-1.

The data file was written by running this module as a script
(``PYTHONPATH=src python tests/test_paged_step_bitwise.py``) on the
implementation that stacked each layer's pool through the scan's outputs.
Regenerate it only for a change that is meant to alter the math.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.worklist import (
    DEC_FIELDS,
    build_worklist,
    chunk_items,
    extend_packed_items,
    pack_decode_items,
    pack_decode_items_2d,
    pow2_bucket,
)
from repro.kernels import ops
from repro.models import transformer as tfm

DATA = Path(__file__).parent / "data" / "paged_step_bitwise.npz"

BLOCK = 8
N = 11                          # 10 usable blocks + the trash block
STRIPE = 5                      # two stripes of 5 blocks
CFG = tfm.TransformerConfig(num_layers=3, d_model=64, num_heads=4,
                            num_kv_heads=2, head_dim=16, d_ff=128,
                            vocab_size=128, block_q=BLOCK, block_kv=BLOCK)
L, HKV, DH = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim_

# decode: three rows; row 2 is inactive, so its K/V go to the trash block.
# Physical ids keep to one stripe per logical block range either way.
TABLE = np.array([[2, 7, -1, -1],
                  [0, 6, 3, -1],
                  [8, 4, 1, -1]], np.int32)
POS = np.array([13, 22, 17], np.int32)
ACTIVE = np.array([True, True, False])
# chunk: q blocks 1 and 2 of a 14-token prompt; its second block is
# bucket padding (table -1), so it lands on the trash block
CHUNK_TABLE = np.array([3, 9, -1, -1], np.int32)
CHUNK_OFF, CHUNK_C, CHUNK_KV_LEN = 8, 16, 14

DECODE_CASES = [f"decode-{sel}-s{s}-{kvd}-{loop}"
                for sel in ("packed", "padded", "dense")
                for s in (1, 2)
                for kvd in ("bf16", "int8")
                for loop in ("scan", "unroll")]
CHUNK_CASES = [f"chunk-{kind}-{kvd}-{loop}"
               for kind in ("sparse", "dense")
               for kvd in ("bf16", "int8")
               for loop in ("scan", "unroll")]


def _params(rng):
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    dataclasses.replace(
                                                        CFG,
                                                        layer_loop="scan")))
    return jax.tree.map(lambda s: jnp.asarray(
        0.1 * rng.standard_normal(s.shape), s.dtype), shapes)


def _pool(rng, kvd):
    shape = (L, 2, N, HKV, BLOCK, DH)
    if kvd == "bf16":
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16), None
    codes = rng.integers(-127, 128, size=shape).astype(np.int8)
    scales = rng.uniform(0.01, 0.05, size=(L, 2, N, HKV)).astype(np.float32)
    return jnp.asarray(codes), jnp.asarray(scales)


def _block_ids(rng):
    """[L, B, Hkv, 3] logical selections, sorted, -1 trailing."""
    nb = 3
    ids = np.full((L, len(POS), HKV, nb), -1, np.int32)
    for l in range(L):
        for b, p in enumerate(POS):
            resident = p // BLOCK + 1
            for h in range(HKV):
                k = rng.integers(1, min(nb, resident) + 1)
                ids[l, b, h, :k] = np.sort(
                    rng.choice(resident, size=k, replace=False))
    return ids


def _packed(ids, stripes):
    if stripes == 1:
        wls = [pack_decode_items(ids[l], block=BLOCK) for l in range(L)]
        bucket = pow2_bucket(max(wl.padded_length for wl in wls))
        return np.stack([extend_packed_items(wl.items, bucket)
                         .reshape(-1, DEC_FIELDS) for wl in wls])
    stripe_of = np.where(TABLE >= 0, TABLE // STRIPE, -1).astype(np.int32)
    wls = [pack_decode_items_2d(ids[l], stripe_of, num_stripes=stripes,
                                block=BLOCK) for l in range(L)]
    bucket = pow2_bucket(max(wl.padded_length for wl in wls))
    return np.stack([extend_packed_items(
        wl.items.reshape(stripes, wl.padded_length, DEC_FIELDS), bucket)
        for wl in wls])                     # [L, S, bucket, DEC_FIELDS]


def _chunk_items(rng):
    """[L, P, 7] chunk work-lists: every head's q block sees block 0 and
    its own block, plus a random choice of the blocks between."""
    nq = (CHUNK_OFF + CHUNK_C) // BLOCK
    G = CFG.num_heads // HKV
    full = []
    for _ in range(L):
        sel = [[np.unique(np.concatenate(
            [[0, qb], rng.choice(qb + 1, size=rng.integers(0, qb + 1),
                                 replace=False)])).astype(np.int64)
            for qb in range(nq)] for _ in range(CFG.num_heads)]
        full.append(build_worklist(
            sel, np.zeros(CFG.num_heads, np.int64), 1, nq, nq, BLOCK,
            kv_head_of_head=np.arange(CFG.num_heads) // G, kv_local=False))
    return np.stack([chunk_items(wl.items[0], CHUNK_OFF // BLOCK,
                                 CHUNK_C // BLOCK, pad_to=64) for wl in full])


def _decode_written(kvd):
    """Pool (and scales) mask of the positions a decode step writes."""
    mask = np.zeros((L, 2, N, HKV, BLOCK, DH), bool)
    smask = np.zeros((L, 2, N, HKV), bool)
    for b, p in enumerate(POS):
        phys = TABLE[b, p // BLOCK]
        gid = phys if ACTIVE[b] and phys >= 0 else N - 1
        if kvd == "bf16":
            mask[:, :, gid, :, p % BLOCK, :] = True
        else:
            mask[:, :, gid] = True
            smask[:, :, gid] = True
    return mask, smask


def _chunk_written():
    mask = np.zeros((L, 2, N, HKV, BLOCK, DH), bool)
    smask = np.zeros((L, 2, N, HKV), bool)
    t = CHUNK_TABLE[CHUNK_OFF // BLOCK:(CHUNK_OFF + CHUNK_C) // BLOCK]
    gids = np.where(t >= 0, t, N - 1)
    mask[:, :, gids] = True
    smask[:, :, gids] = True
    return mask, smask


def run_case(case: str):
    """Inputs and outputs of one case, as numpy:
    ``(pool_in, scales_in, logits, pool_out, scales_out, mask, smask)``
    (the scales are None for a bf16 pool)."""
    parts = case.split("-")
    kind, loop = parts[0], parts[-1]
    kvd = parts[-2]
    cfg = dataclasses.replace(CFG, layer_loop=loop)
    rng = np.random.default_rng(sum(map(ord, case)))
    params = _params(rng)
    if loop == "unroll":
        params = {**params, "layers": [
            jax.tree.map(lambda a: a[l], params["layers"])
            for l in range(L)]}
    pool, scales = _pool(rng, kvd)
    # arrays enter the jitted step as arguments, the rest as constants
    qkw = {} if scales is None else {"scales": scales}
    static = {} if scales is None else {"kv_dtype": kvd}
    if kind == "decode":
        sel, stripes = parts[1], int(parts[2][1:])
        token = jnp.asarray(rng.integers(0, CFG.vocab_size, len(POS)),
                            jnp.int32)
        ids = _block_ids(rng)
        kw = dict(cache_len=jnp.asarray(POS + 1), active=jnp.asarray(ACTIVE))
        if stripes > 1:
            static.update(seq_stripes=stripes, stripe_size=STRIPE)
        if sel == "packed":
            kw["packed_items"] = jnp.asarray(_packed(ids, stripes))
        elif sel == "padded":
            kw["block_ids"] = jnp.asarray(ids)
        out = jax.jit(lambda p, c, k, q: tfm.decode_step_paged(
            p, c, token, jnp.asarray(POS), jnp.asarray(TABLE), cfg, **k,
            **q, **static))(params, pool, kw, qkw)
        mask, smask = _decode_written(kvd)
    else:
        tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, CHUNK_C)),
                             jnp.int32)
        items = (jnp.asarray(_chunk_items(rng)) if parts[1] == "sparse"
                 else None)
        kw = dict(kv_len=jnp.int32(CHUNK_KV_LEN), sparse_items=items,
                  last_index=jnp.int32(CHUNK_KV_LEN - 1 - CHUNK_OFF))
        out = jax.jit(lambda p, c, k, q: tfm.prefill_chunk_paged(
            p, c, tokens, jnp.asarray(CHUNK_TABLE), jnp.int32(CHUNK_OFF),
            cfg, **k, **q, **static))(params, pool, kw, qkw)
        mask, smask = _chunk_written()
    as_np = lambda a: None if a is None else np.asarray(a)
    return (as_np(pool), as_np(scales), as_np(out[0]), as_np(out[1]),
            as_np(out[2]) if len(out) > 2 else None, mask, smask)


def _bits(a):
    return np.ascontiguousarray(a).view(f"u{a.dtype.itemsize}")


def _record(out):
    _, _, logits, pool, scales, mask, smask = out
    rec = {"logits": _bits(logits), "pool": _bits(pool[mask])}
    if scales is not None:
        rec["scales"] = _bits(scales[smask])
    return rec


@pytest.fixture(scope="module")
def recorded():
    with np.load(DATA) as f:
        return {k: f[k] for k in f.files}


@pytest.mark.parametrize("case", DECODE_CASES + CHUNK_CASES)
def test_paged_step_matches_recording_and_writes_only_its_positions(
        case, recorded):
    out = run_case(case)
    pool_in, scales_in, _, pool, scales, mask, smask = out
    for name, got in _record(out).items():
        np.testing.assert_array_equal(got, recorded[f"{case}__{name}"],
                                      err_msg=f"{case}: {name}")
    np.testing.assert_array_equal(_bits(pool[~mask]), _bits(pool_in[~mask]),
                                  err_msg=f"{case}: pool outside the writes")
    if scales is not None:
        np.testing.assert_array_equal(scales[~smask], scales_in[~smask],
                                      err_msg=f"{case}: scales outside "
                                              "the writes")


# SmolLM's attention shape (9 query heads over 3 KV heads of 64) at
# 128-token blocks, where the step hands the kernel the whole pool and the
# kernel reads its [Dh, block] tiles; a small model around it
SMOL = tfm.TransformerConfig(num_layers=3, d_model=64, num_heads=9,
                             num_kv_heads=3, head_dim=64, d_ff=128,
                             vocab_size=128, block_q=128, block_kv=128,
                             layer_loop="scan")
SMOL_TABLE = np.array([[3, 0, -1],
                       [5, 1, 2],
                       [4, -1, -1]], np.int32)
SMOL_POS = np.array([200, 300, 60], np.int32)
SMOL_ACTIVE = np.array([True, True, False])
PAGED_ENTRIES = {name: getattr(ops, name)
                 for name in ("flash_decode_paged", "flash_decode_packed_paged")}


def _route_to_kernel(monkeypatch, per_layer):
    """Send the step's paged decode calls to the Pallas kernel in
    interpret mode.  ``per_layer``: hand it layer ``l``'s K and V (the
    padded per-layer form) where the step passes the whole pool.  Returns
    the list of the forms the step asked for (True: whole pool)."""
    asked = []
    for name, real in PAGED_ENTRIES.items():
        def call(q, k_pool, v_pool, *a, layer=None, _real=real, **k):
            asked.append(layer is not None)
            if per_layer and layer is not None:
                k_pool, v_pool, layer = k_pool[layer, 0], k_pool[layer, 1], None
            return _real(q, k_pool, v_pool, *a, layer=layer, use_kernel=True,
                         interpret=True, **k)
        monkeypatch.setattr(ops, name, call)
    return asked


@pytest.mark.parametrize("kvd", ["bf16", "int8"])
@pytest.mark.parametrize("sel", ["packed", "padded"])
def test_decode_step_transposed_tiles_match_per_layer(monkeypatch, sel, kvd):
    """Three greedy decode steps at SmolLM's head_dim: the kernel reading
    the whole pool's token-on-lanes tiles gives the same tokens, logits
    and pool, bit for bit, as the per-layer padded form."""
    rng = np.random.default_rng(19)
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    SMOL))
    params = jax.tree.map(lambda a: jnp.asarray(
        0.1 * rng.standard_normal(a.shape), a.dtype), shapes)
    L, hkv, n = SMOL.num_layers, SMOL.num_kv_heads, 6
    shape = (L, 2, n, hkv, 128, SMOL.head_dim_)
    qkw = {}
    if kvd == "bf16":
        pool = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    else:
        pool = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        qkw = dict(scales=jnp.asarray(rng.uniform(0.01, 0.05, shape[:4]),
                                      jnp.float32), kv_dtype=kvd)
    # each layer's selection: block 0 and the newest block of each row
    ids = np.zeros((L, 3, hkv, 2), np.int32)
    ids[..., 1] = (SMOL_POS // 128)[None, :, None]
    ids[..., 1] = np.where(ids[..., 1] == 0, -1, ids[..., 1])
    if sel == "packed":
        items = np.stack([pack_decode_items(ids[l], block=128, bucket=32)
                          .flat() for l in range(L)])
        kw = {"packed_items": jnp.asarray(items)}
    else:
        kw = {"block_ids": jnp.asarray(ids)}
    token = jnp.asarray(rng.integers(0, SMOL.vocab_size, 3), jnp.int32)

    def run(per_layer):
        asked = _route_to_kernel(monkeypatch, per_layer)
        c, q, t, pos, outs = pool, dict(qkw), token, jnp.asarray(SMOL_POS), []
        for _ in range(3):
            out = tfm.decode_step_paged(
                params, c, t, pos, jnp.asarray(SMOL_TABLE), SMOL, **kw,
                cache_len=pos + 1, active=jnp.asarray(SMOL_ACTIVE), **q)
            logits, c = out[0], out[1]
            if len(out) > 2:
                q["scales"] = out[2]
            t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            outs.append((np.asarray(logits), np.asarray(t)))
            pos = pos + 1
        return asked, outs, np.asarray(c)

    asked, whole, pool_whole = run(per_layer=False)
    assert asked and all(asked), "the step did not pass the whole pool"
    _, per_layer, pool_per_layer = run(per_layer=True)
    for (lw, tw), (lp, tp) in zip(whole, per_layer):
        np.testing.assert_array_equal(_bits(lw), _bits(lp))
        np.testing.assert_array_equal(tw, tp)
    np.testing.assert_array_equal(_bits(pool_whole), _bits(pool_per_layer))


if __name__ == "__main__":
    DATA.parent.mkdir(exist_ok=True)
    np.savez_compressed(DATA, **{
        f"{case}__{k}": v for case in DECODE_CASES + CHUNK_CASES
        for k, v in _record(run_case(case)).items()})
    print(f"wrote {DATA}")
