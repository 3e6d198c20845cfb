"""Chunked prefill: model-layer partial-prefill equivalence through the
paged pool, chunk work-list slicing, and the decode active-slot write
mask."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.attention.worklist_jnp import causal_items, worklist_attention
from repro.core.worklist import (
    F_KVBLK,
    F_QBLK,
    F_VALID,
    chunk_item_counts,
    chunk_items,
)
from repro.models import transformer as tfm
from repro.models.transformer import TransformerConfig

CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, d_ff=128, vocab_size=256,
                        layer_loop="unroll", dtype=jnp.float32,
                        block_q=16, block_kv=16)

BLOCK = 16
SMAX = 128
NBLK = SMAX // BLOCK            # table width
POOL = 12                       # usable pool blocks (+1 trash block)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(1), CFG)


def _full_causal_items(cfg, seq_len):
    nq = -(-seq_len // BLOCK)
    kv_of = np.arange(cfg.num_heads) // cfg.group_size
    return causal_items(cfg.num_heads, nq, kv_of)


def _pool(cfg):
    return tfm.init_paged_cache(cfg, POOL + 1, BLOCK)


def _table(seq_len, seed=0):
    """[NBLK] table mapping the sequence's blocks to scattered pool ids."""
    nb = -(-seq_len // BLOCK)
    row = np.full((NBLK,), -1, np.int32)
    row[:nb] = np.random.default_rng(seed).permutation(POOL)[:nb]
    return row


def _run_chunks(params, cfg, tokens, table, chunk_lens, sparse=False,
                pool=None):
    """Drive tfm.prefill_chunk_paged over a chunk split, each chunk padded
    to whole blocks as the engine pads it; returns (logits, pool)."""
    pool = _pool(cfg) if pool is None else pool
    S = sum(chunk_lens)
    full = _full_causal_items(cfg, S) if sparse else None
    off = 0
    logits = None
    for c in chunk_lens:
        nqc = -(-c // BLOCK)
        toks = np.zeros((1, nqc * BLOCK), np.int32)
        toks[0, :c] = tokens[off:off + c]
        items = None
        if sparse:
            it = chunk_items(full, off // BLOCK, nqc, pad_to=len(full))
            items = np.stack([it] * cfg.num_layers)
        logits, pool = tfm.prefill_chunk_paged(
            params, pool, jnp.asarray(toks), jnp.asarray(table), off, cfg,
            kv_len=off + c, sparse_items=items, last_index=c - 1)
        off += c
    return logits, pool


def _seq_rows(pool, table, seq_len):
    """[L, 2, Hkv, seq_len, Dh] rows of one sequence, read through its
    table."""
    nb = -(-seq_len // BLOCK)
    blocks = np.asarray(pool)[:, :, table[:nb]]      # [L, 2, nb, Hkv, blk, D]
    L, two, _, hkv, _, dh = blocks.shape
    rows = np.moveaxis(blocks, 2, 3).reshape(L, two, hkv, nb * BLOCK, dh)
    return rows[:, :, :, :seq_len]


@pytest.mark.parametrize("sparse", [False, True])
@pytest.mark.parametrize("chunk_lens", [(80,), (32, 48), (16, 32, 32), (32, 33)])
def test_prefill_chunk_matches_monolithic(params, sparse, chunk_lens):
    """Any block-aligned chunk split reproduces the monolithic prefill:
    same last-token logits, same K/V rows in the sequence's blocks."""
    S = sum(chunk_lens)
    tokens = np.random.default_rng(0).integers(0, 256, size=(S,)).astype(
        np.int32)
    items = ([_full_causal_items(CFG, S)] * CFG.num_layers) if sparse else None
    ref_logits, ref_cache = tfm.prefill(
        params, jnp.asarray(tokens[None]), CFG, cache_len=SMAX,
        sparse_items=items)
    table = _table(S)
    logits, pool = _run_chunks(params, CFG, tokens, table, chunk_lens,
                               sparse=sparse)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
    got = _seq_rows(pool, table, S)
    want = np.asarray(ref_cache)[:, :, 0, :, :S]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_prefill_chunk_untouched_slots(params):
    """Chunked prefill writes only its own blocks: every other pool block,
    the trash block included, stays exactly as it was."""
    tokens = np.random.default_rng(1).integers(0, 256, size=(48,))
    pool0 = _pool(CFG) + 3.0
    table = _table(48, seed=1)
    _, pool = _run_chunks(params, CFG, tokens, table, (16, 32), pool=pool0)
    got = np.asarray(pool)
    want = np.asarray(pool0)
    own = set(table[table >= 0].tolist())
    assert len(own) == 3
    for b in range(POOL + 1):
        if b in own:
            assert not np.array_equal(got[:, :, b], want[:, :, b])
        else:
            np.testing.assert_array_equal(got[:, :, b], want[:, :, b])


def test_prefill_chunk_scan_loop_mode(params):
    """The lax.scan layer loop lowers the same chunked math as unroll."""
    cfg_scan = dataclasses.replace(CFG, layer_loop="scan")
    params_scan = tfm.init_params(jax.random.PRNGKey(1), cfg_scan)
    tokens = np.random.default_rng(2).integers(0, 256, size=(64,)).astype(
        np.int32)
    ref_logits, _ = tfm.prefill(params_scan, jnp.asarray(tokens[None]),
                                cfg_scan, cache_len=SMAX)
    logits, _ = _run_chunks(params_scan, cfg_scan, tokens, _table(64),
                            (32, 32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


def test_prefill_chunk_local_window(params):
    """Sliding-window layers mask by GLOBAL position across chunks."""
    cfg_l = dataclasses.replace(CFG, attn_pattern="GL", local_window=24)
    params_l = tfm.init_params(jax.random.PRNGKey(1), cfg_l)
    tokens = np.random.default_rng(3).integers(0, 256, size=(64,)).astype(
        np.int32)
    ref_logits, _ = tfm.prefill(params_l, jnp.asarray(tokens[None]), cfg_l,
                                cache_len=SMAX)
    logits, _ = _run_chunks(params_l, cfg_l, tokens, _table(64), (16, 48))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


class TestWorklistChunkView:
    def test_chunk_items_slices_and_remaps(self):
        it = causal_items(2, 4)  # 2 heads, 4 q blocks, full causal
        sl = chunk_items(it, 2, 2)
        assert (sl[:, F_VALID] == 1).all()
        assert set(sl[:, F_QBLK].tolist()) == {0, 1}     # chunk-local
        assert sl[:, F_KVBLK].max() == 3                 # kv stays global
        # q_blk 2 has 3 causal kv blocks, q_blk 3 has 4; two heads
        assert len(sl) == 2 * (3 + 4)

    def test_chunk_items_padding_convention(self):
        it = causal_items(1, 4)
        sl = chunk_items(it, 1, 1, pad_to=8)
        assert sl.shape == (8, it.shape[-1])
        assert (sl[:2, F_VALID] == 1).all()
        assert (sl[2:, F_VALID] == 0).all()
        # padding replicates the last real item's indices
        assert (sl[2:, F_QBLK] == sl[1, F_QBLK]).all()

    def test_chunk_items_cap_overflow_raises(self):
        it = causal_items(1, 4)
        with pytest.raises(ValueError):
            chunk_items(it, 0, 4, pad_to=2)

    def test_chunk_item_counts(self):
        it = causal_items(2, 4)
        counts = chunk_item_counts(it, 4)
        assert counts.tolist() == [2, 4, 6, 8]

    def test_worklist_q_offset_matches_full(self):
        """Executing the chunk slice at q_offset reproduces the full
        work-list rows for that chunk."""
        rng = np.random.default_rng(0)
        H, Hkv, S, D = 2, 1, 64, 8
        q = jnp.asarray(rng.normal(size=(H, S, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(Hkv, S, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(Hkv, S, D)), jnp.float32)
        items = causal_items(H, S // 16, np.zeros(H, np.int64))
        full = worklist_attention(q, k, v, jnp.asarray(items),
                                  block_q=16, block_kv=16)
        off, c = 32, 32
        sl = chunk_items(items, off // 16, c // 16, pad_to=len(items))
        part = worklist_attention(q[:, off:off + c], k, v, jnp.asarray(sl),
                                  block_q=16, block_kv=16,
                                  q_offset=off, kv_len=S)
        np.testing.assert_allclose(np.asarray(part),
                                   np.asarray(full)[:, off:off + c],
                                   rtol=1e-6, atol=1e-6)


SLOTS = 3


def test_decode_step_active_mask_protects_slots(params):
    """A batched decode step must not mutate cache rows of slots marked
    inactive (freed, or mid-chunked-prefill in a mixed tick).  Checks the
    contiguous ``decode_step`` the pod dry-run still lowers."""
    cache = tfm.init_cache(CFG, SLOTS, SMAX) + 1.0
    token = jnp.asarray(np.arange(SLOTS), jnp.int32)
    pos = jnp.asarray([5, 0, 9], jnp.int32)
    active = jnp.asarray([True, False, False])
    _, new_cache = tfm.decode_step(params, cache, token, pos, CFG,
                                   cache_len=pos + 1, active=active)
    got = np.asarray(new_cache)
    want = np.asarray(cache)
    # inactive slots bit-identical everywhere
    np.testing.assert_array_equal(got[:, :, 1], want[:, :, 1])
    np.testing.assert_array_equal(got[:, :, 2], want[:, :, 2])
    # the active slot DID write its row
    assert not np.array_equal(got[:, :, 0, :, 5], want[:, :, 0, :, 5])
