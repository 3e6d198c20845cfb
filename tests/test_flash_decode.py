"""Fused budgeted flash-decode: kernel/reference parity vs the dense
oracle and the legacy gather path, zero-copy jaxpr guarantees, cross-shard
partial merging, per-slot position masking — and the PAGED twins
(block-pool + block-table indirection, DESIGN.md §2.7): paged executors
must match the contiguous ones bit-for-bit on equal cache contents."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.attention.worklist_jnp import (
    causal_items,
    packed_decode_attention,
    packed_decode_attention_paged,
    worklist_attention,
    worklist_attention_paged,
)
from repro.core.worklist import (
    pack_decode_items,
    padded_decode_items,
    pow2_bucket,
    extend_packed_items,
)
from repro.kernels.flash_decode import (
    decode_items_from_ids,
    flash_decode_kernel,
    flash_decode_paged_kernel,
    flash_decode_paged_reference,
    flash_decode_reference,
    merge_partials,
)
from repro.kernels import ops
from repro.kernels.ops import flash_decode
from repro.kernels.ref import gather_decode_reference, gather_output_sizes

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
BLK = 128


def _rand_case(B, Hkv, G, Smax, D, dtype, seed=0, max_nb=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, D), dtype)
    kc = jax.random.normal(ks[1], (B, Hkv, Smax, D), dtype)
    vc = jax.random.normal(ks[2], (B, Hkv, Smax, D), dtype)
    nkv = -(-Smax // BLK)
    width = max_nb or nkv
    rng = np.random.default_rng(seed + 1)
    ids = np.full((B, Hkv, width), -1, np.int32)
    for b in range(B):
        for h in range(Hkv):
            n = int(rng.integers(1, min(nkv, width) + 1))   # ragged budgets
            sel = rng.choice(nkv - 1, n - 1, replace=False) + 1
            ids[b, h, :n] = np.sort(np.append(sel, 0))  # sink always in
    pos = rng.integers(0, Smax, size=B).astype(np.int32)
    return q, kc, vc, ids, pos


def _dense_oracle(q, kc, vc, ids, pos, window=None):
    """Token-level masked softmax in f64-ish numpy — the ground truth."""
    q, kc, vc = (np.asarray(x, np.float32) for x in (q, kc, vc))
    B, Hkv, G, D = q.shape
    Smax = kc.shape[2]
    out = np.zeros((B, Hkv, G, D), np.float32)
    for b in range(B):
        for h in range(Hkv):
            mask = np.zeros(Smax, bool)
            for blk_id in ids[b, h]:
                if blk_id >= 0:
                    lo = blk_id * BLK
                    mask[lo:min(lo + BLK, Smax)] = True
            mask &= np.arange(Smax) <= pos[b]
            if window is not None:
                mask &= np.arange(Smax) > pos[b] - window
            if not mask.any():
                continue                       # fused contract: zeros
            s = q[b, h] @ kc[b, h].T * D ** -0.5
            s = np.where(mask[None], s, -1e30)
            w = np.exp(s - s.max(-1, keepdims=True))
            w = w / w.sum(-1, keepdims=True)
            out[b, h] = w @ vc[b, h]
    return out


def _gather_path(q, kc, vc, ids, pos):
    """Legacy dense-gather decode in this file's [B, Hkv, G, D] layout
    (shared implementation lives in ``kernels.ref``)."""
    B, Hkv, G, D = q.shape
    o = gather_decode_reference(q.reshape(B, Hkv * G, 1, D), kc, vc,
                                ids, pos, block_kv=BLK)
    return o.reshape(B, Hkv, G, D).astype(jnp.float32)


class TestParity:
    @pytest.mark.parametrize("B,Hkv,G,Smax,D", [
        (2, 2, 4, 512, 64),      # GQA 4
        (1, 4, 1, 384, 128),     # MQA-per-kv (G=1)
        (3, 1, 8, 256, 32),      # single kv head, G=8
        (2, 2, 2, 320, 64),      # cache len NOT divisible by block_kv
        (1, 3, 5, 200, 48),      # ragged everything
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kernel_and_reference_vs_oracle(self, B, Hkv, G, Smax, D, dtype):
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, dtype)
        ref = _dense_oracle(q, kc, vc, ids, pos)
        items = decode_items_from_ids(jnp.asarray(ids))
        ko, km, kl = flash_decode_kernel(
            q, kc, vc, items, jnp.asarray(pos), block_kv=BLK, interpret=True)
        ro, rm, rl = flash_decode_reference(
            q, kc, vc, jnp.asarray(ids), jnp.asarray(pos), block_kv=BLK)
        tol = TOL[dtype]
        np.testing.assert_allclose(np.asarray(ko, np.float32), ref,
                                   atol=tol, rtol=tol)
        np.testing.assert_allclose(np.asarray(ro, np.float32), ref,
                                   atol=tol, rtol=tol)
        # the two executors share softmax statistics exactly (same carry)
        np.testing.assert_allclose(np.asarray(km), np.asarray(rm),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(kl), np.asarray(rl),
                                   atol=1e-5, rtol=1e-5)

    def test_matches_legacy_gather_path(self):
        B, Hkv, G, Smax, D = 2, 2, 4, 512, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=7)
        old = np.asarray(_gather_path(q, kc, vc, ids, pos))
        new = flash_decode(q.reshape(B, Hkv * G, 1, D), kc, vc,
                           jnp.asarray(ids), jnp.asarray(pos), block_kv=BLK)
        np.testing.assert_allclose(
            np.asarray(new).reshape(B, Hkv, G, D), old, atol=2e-5, rtol=2e-5)

    def test_empty_selection_yields_zeros(self):
        q, kc, vc, ids, pos = _rand_case(1, 2, 2, 256, 32, jnp.float32)
        ids[0, 1, :] = -1                      # one head selects nothing
        items = decode_items_from_ids(jnp.asarray(ids))
        ko, _, kl = flash_decode_kernel(
            q, kc, vc, items, jnp.asarray(pos), block_kv=BLK, interpret=True)
        ro, _, rl = flash_decode_reference(
            q, kc, vc, jnp.asarray(ids), jnp.asarray(pos), block_kv=BLK)
        for o, l in ((ko, kl), (ro, rl)):
            assert float(jnp.abs(o[0, 1]).max()) == 0.0
            assert float(l[0, 1].max()) == 0.0

    def test_window_masking(self):
        B, Hkv, G, Smax, D = 2, 2, 2, 512, 32
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=3)
        ids[:] = np.arange(Smax // BLK)[None, None]   # all blocks
        pos[:] = Smax - 1
        win = 192
        ref = _dense_oracle(q, kc, vc, ids, pos, window=win)
        ro, _, _ = flash_decode_reference(
            q, kc, vc, jnp.asarray(ids), jnp.asarray(pos), block_kv=BLK,
            window=win)
        ko, _, _ = flash_decode_kernel(
            q, kc, vc, decode_items_from_ids(jnp.asarray(ids)),
            jnp.asarray(pos), block_kv=BLK, window=win, interpret=True)
        np.testing.assert_allclose(np.asarray(ro), ref, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(ko), ref, atol=2e-5, rtol=2e-5)

    def test_per_slot_positions(self):
        """Every slot masks at ITS OWN position (continuous batching)."""
        B, Hkv, G, Smax, D = 4, 2, 2, 512, 32
        q, kc, vc, ids, _ = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                       seed=5)
        pos = np.array([10, 150, 300, 511], np.int32)
        ref = _dense_oracle(q, kc, vc, ids, pos)
        ro, _, _ = flash_decode_reference(
            q, kc, vc, jnp.asarray(ids), jnp.asarray(pos), block_kv=BLK)
        np.testing.assert_allclose(np.asarray(ro), ref, atol=2e-5, rtol=2e-5)


def _paginate(kc, vc, seed=0, extra_blocks=2):
    """Scatter a contiguous cache [B, Hkv, Smax, D] into a block pool
    [N, Hkv, BLK, D] under a random per-slot logical->physical table.
    Returns (k_pool, v_pool, table [B, T])."""
    B, Hkv, Smax, D = kc.shape
    T = Smax // BLK
    N = B * T + extra_blocks
    rng = np.random.default_rng(seed)
    perm = rng.permutation(N)[:B * T].reshape(B, T).astype(np.int32)
    k_pool = np.zeros((N, Hkv, BLK, D), np.asarray(kc).dtype)
    v_pool = np.zeros((N, Hkv, BLK, D), np.asarray(vc).dtype)
    for b in range(B):
        for j in range(T):
            k_pool[perm[b, j]] = np.asarray(
                kc)[b, :, j * BLK:(j + 1) * BLK, :]
            v_pool[perm[b, j]] = np.asarray(
                vc)[b, :, j * BLK:(j + 1) * BLK, :]
    return jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(perm)


class TestPagedParity:
    """Paged pool + block-table executors vs the contiguous twins."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_paged_reference_matches_contiguous_bitwise(self, dtype):
        B, Hkv, G, Smax, D = 3, 2, 4, 512, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, dtype, seed=11)
        kp, vp, tbl = _paginate(kc, vc, seed=12)
        co, cm, cl = flash_decode_reference(
            q, kc, vc, jnp.asarray(ids), jnp.asarray(pos), block_kv=BLK)
        po, pm, plg = flash_decode_paged_reference(
            q, kp, vp, jnp.asarray(ids), tbl, jnp.asarray(pos),
            block_kv=BLK)
        # identical tiles, identical accumulation order -> identical bits
        assert np.array_equal(np.asarray(co), np.asarray(po))
        assert np.array_equal(np.asarray(cm), np.asarray(pm))
        assert np.array_equal(np.asarray(cl), np.asarray(plg))

    @pytest.mark.parametrize("window", [None, 192])
    def test_paged_kernel_matches_reference(self, window):
        B, Hkv, G, Smax, D = 2, 2, 4, 384, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=21)
        kp, vp, tbl = _paginate(kc, vc, seed=22)
        ref = _dense_oracle(q, kc, vc, ids, pos, window=window)
        items = decode_items_from_ids(jnp.asarray(ids))
        ko, km, kl = flash_decode_paged_kernel(
            q, kp, vp, items, tbl, jnp.asarray(pos), block_kv=BLK,
            window=window, interpret=True)
        ro, rm, rl = flash_decode_paged_reference(
            q, kp, vp, jnp.asarray(ids), tbl, jnp.asarray(pos),
            block_kv=BLK, window=window)
        np.testing.assert_allclose(np.asarray(ko), ref, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(ro), ref, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(km), np.asarray(rm),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(kl), np.asarray(rl),
                                   atol=1e-5, rtol=1e-5)

    def test_unmapped_table_entries_are_masked(self):
        """A -1 table entry (unmapped logical block / foreign shard's
        block) contributes nothing, in reference and kernel alike."""
        B, Hkv, G, Smax, D = 2, 2, 2, 512, 32
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=31)
        kp, vp, tbl = _paginate(kc, vc, seed=32)
        # drop logical block 1 everywhere; the oracle sees its selection
        # removed instead
        tbl_mask = np.asarray(tbl).copy()
        tbl_mask[:, 1] = -1
        ids_removed = np.where(ids == 1, -1, ids)
        ref = _dense_oracle(q, kc, vc, ids_removed, pos)
        ro, _, _ = flash_decode_paged_reference(
            q, kp, vp, jnp.asarray(ids), jnp.asarray(tbl_mask),
            jnp.asarray(pos), block_kv=BLK)
        ko, _, _ = flash_decode_paged_kernel(
            q, kp, vp, decode_items_from_ids(jnp.asarray(ids)),
            jnp.asarray(tbl_mask), jnp.asarray(pos), block_kv=BLK,
            interpret=True)
        np.testing.assert_allclose(np.asarray(ro), ref, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(ko), ref, atol=2e-5, rtol=2e-5)

    def test_paged_shard_merge_matches_global(self):
        """Block-sharded pool: each shard remaps the GLOBAL table to its
        local block range (-1 elsewhere); merged partials equal the global
        softmax — the paged flash-decode island's algebra."""
        B, Hkv, G, Smax, D = 2, 3, 4, 512, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=41)
        kp, vp, tbl = _paginate(kc, vc, seed=42, extra_blocks=4)
        ref = _dense_oracle(q, kc, vc, ids, pos)
        N = kp.shape[0]
        n_sh = 2
        n_loc = -(-N // n_sh)
        outs, ms, ls = [], [], []
        for s in range(n_sh):
            lo, hi = s * n_loc, min((s + 1) * n_loc, N)
            local = np.asarray(tbl) - lo
            ok = (np.asarray(tbl) >= lo) & (np.asarray(tbl) < hi)
            tbl_local = np.where(ok, local, -1).astype(np.int32)
            o, m, l = flash_decode_paged_reference(
                q, kp[lo:hi], vp[lo:hi], jnp.asarray(ids),
                jnp.asarray(tbl_local), jnp.asarray(pos), block_kv=BLK)
            outs.append(o), ms.append(m), ls.append(l)
        merged = merge_partials(jnp.stack(outs), jnp.stack(ms),
                                jnp.stack(ls))
        np.testing.assert_allclose(np.asarray(merged), ref,
                                   atol=2e-5, rtol=2e-5)

    def test_stripe_holding_no_blocks_merges_exact(self):
        """Regression (§2.11): a stripe that holds NONE of any row's
        blocks emits (m = NEG_INF, l = 0) partials; the merge must return
        the contributing stripe's output BITWISE — no exp(nan), no
        x*l/l renormalization ulp, no 0/0."""
        B, Hkv, G, Smax, D = 2, 2, 2, 256, 32
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=77)
        kp, vp, tbl = _paginate(kc, vc, seed=78, extra_blocks=0)
        N, pad = kp.shape[0], 4
        zeros = jnp.zeros((pad,) + kp.shape[1:], kp.dtype)
        kp2, vp2 = (jnp.concatenate([p, zeros]) for p in (kp, vp))
        # stripe 0 = [0, N) holds every mapped block; stripe 1 = [N, N+4)
        # holds none — its local table is all -1
        full = flash_decode_paged_reference(
            q, kp2, vp2, jnp.asarray(ids), tbl, jnp.asarray(pos),
            block_kv=BLK)
        o0, m0, l0 = flash_decode_paged_reference(
            q, kp2[:N], vp2[:N], jnp.asarray(ids), tbl, jnp.asarray(pos),
            block_kv=BLK)
        empty = jnp.full(tbl.shape, -1, jnp.int32)
        o1, m1, l1 = flash_decode_paged_reference(
            q, kp2[N:], vp2[N:], jnp.asarray(ids), empty,
            jnp.asarray(pos), block_kv=BLK)
        assert np.all(np.asarray(l1) == 0.0)
        merged = merge_partials(jnp.stack([o0, o1]), jnp.stack([m0, m1]),
                                jnp.stack([l0, l1]))
        assert np.isfinite(np.asarray(merged)).all()
        assert np.array_equal(np.asarray(merged), np.asarray(full[0]))

    def test_worklist_paged_matches_contiguous_bitwise(self):
        """Chunked-prefill executor: the paged work-list twin reproduces
        the contiguous one bit-for-bit (same tiles, same order) through a
        scrambled block table."""
        H, Hkv, S, D = 4, 2, 384, 32
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        qx = jax.random.normal(ks[0], (H, S, D), jnp.float32)
        kx = jax.random.normal(ks[1], (Hkv, S, D), jnp.float32)
        vx = jax.random.normal(ks[2], (Hkv, S, D), jnp.float32)
        kp, vp, tbl = _paginate(kx[None], vx[None], seed=6)
        kv_of_head = np.arange(H) // (H // Hkv)
        items = causal_items(H, S // BLK, kv_of_head)
        base = worklist_attention(qx, kx, vx, jnp.asarray(items),
                                  block_q=BLK, block_kv=BLK,
                                  q_offset=0, kv_len=S)
        pool = jnp.stack([kp, vp])[None]      # one layer: [1, 2, N, ...]
        paged = worklist_attention_paged(qx, pool, jnp.asarray(items),
                                         tbl[0], 0, block_q=BLK, block_kv=BLK,
                                         q_offset=0, kv_len=S)
        assert np.array_equal(np.asarray(base), np.asarray(paged))


class TestPackedExecutor:
    """Cost-packed ragged decode worklists (DESIGN.md §2.8): the packed
    executor must be BITWISE-identical to the padded reference — the grid
    gets shorter, the arithmetic per (row, head) run does not change."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("window", [None, 192])
    def test_packed_matches_padded_reference_bitwise(self, dtype, window):
        B, Hkv, G, Smax, D = 3, 2, 4, 512, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, dtype, seed=51)
        ro, rm, rl = flash_decode_reference(
            q, kc, vc, jnp.asarray(ids), jnp.asarray(pos), block_kv=BLK,
            window=window)
        wl = pack_decode_items(ids, block=BLK)
        po, pm, pl = packed_decode_attention(
            q, kc, vc, jnp.asarray(wl.flat()), jnp.asarray(pos),
            block_kv=BLK, window=window)
        assert np.array_equal(np.asarray(ro), np.asarray(po))
        assert np.array_equal(np.asarray(rm), np.asarray(pm))
        assert np.array_equal(np.asarray(rl), np.asarray(pl))

    def test_padded_table_through_packed_executor_bitwise(self):
        """Grid equivalence: the SAME executor on the padded fixed-stride
        table and on the packed ragged table produces identical bits — so
        any measured latency delta between the two is purely grid length
        (what benchmarks/decode_pack.py reports)."""
        B, Hkv, G, Smax, D = 2, 3, 2, 384, 32
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=52)
        padded = padded_decode_items(ids)
        wl = pack_decode_items(ids, block=BLK)
        packed = extend_packed_items(wl.items,
                                     pow2_bucket(wl.padded_length))
        a = packed_decode_attention(q, kc, vc, jnp.asarray(padded),
                                    jnp.asarray(pos), block_kv=BLK)
        b = packed_decode_attention(q, kc, vc,
                                    jnp.asarray(packed.reshape(-1, 6)),
                                    jnp.asarray(pos), block_kv=BLK)
        for x, y in zip(a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        # the packed grid is never longer than the padded one
        assert packed.shape[0] * packed.shape[1] <= len(padded) + 8

    def test_packed_sharded_concat_matches_single_list(self):
        """best_partition reorders runs across shards; runs stay
        self-contained, so the concatenated multi-shard list still equals
        the 1-shard (and padded-reference) bits."""
        B, Hkv, G, Smax, D = 4, 4, 2, 512, 32
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=53)
        ro, _, _ = flash_decode_reference(
            q, kc, vc, jnp.asarray(ids), jnp.asarray(pos), block_kv=BLK)
        for shards in (1, 2, 4):
            wl = pack_decode_items(ids, num_shards=shards, block=BLK)
            po, _, _ = packed_decode_attention(
                q, kc, vc, jnp.asarray(wl.flat()), jnp.asarray(pos),
                block_kv=BLK)
            assert np.array_equal(np.asarray(ro), np.asarray(po)), shards

    @pytest.mark.parametrize("window", [None, 192])
    def test_packed_paged_matches_padded_paged_bitwise(self, window):
        B, Hkv, G, Smax, D = 3, 2, 4, 512, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=54)
        kp, vp, tbl = _paginate(kc, vc, seed=55)
        ro, rm, rl = flash_decode_paged_reference(
            q, kp, vp, jnp.asarray(ids), tbl, jnp.asarray(pos),
            block_kv=BLK, window=window)
        wl = pack_decode_items(ids, block=BLK)
        po, pm, pl = packed_decode_attention_paged(
            q, kp, vp, jnp.asarray(wl.flat()), tbl, jnp.asarray(pos),
            block_kv=BLK, window=window)
        assert np.array_equal(np.asarray(ro), np.asarray(po))
        assert np.array_equal(np.asarray(rm), np.asarray(pm))
        assert np.array_equal(np.asarray(rl), np.asarray(pl))

    def test_packed_kernel_matches_oracle(self):
        """The Pallas kernel (interpret) consumes packed ragged tables
        as-is — the grid shrinks, the math stays the oracle's."""
        B, Hkv, G, Smax, D = 2, 2, 4, 384, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=56)
        ref = _dense_oracle(q, kc, vc, ids, pos)
        wl = pack_decode_items(ids, block=BLK)
        ko, _, _ = flash_decode_kernel(
            q, kc, vc, jnp.asarray(wl.flat()), jnp.asarray(pos),
            block_kv=BLK, interpret=True)
        np.testing.assert_allclose(np.asarray(ko), ref, atol=2e-5,
                                   rtol=2e-5)
        kpp, vpp, tbl = _paginate(kc, vc, seed=57)
        kpo, _, _ = flash_decode_paged_kernel(
            q, kpp, vpp, jnp.asarray(wl.flat()), tbl, jnp.asarray(pos),
            block_kv=BLK, interpret=True)
        np.testing.assert_allclose(np.asarray(kpo), ref, atol=2e-5,
                                   rtol=2e-5)


def _whole_pool_case(dtype, L=3, seed=61, D=128):
    """A ``[L, 2, N, Hkv, BLK, D]`` pool (int8 codes with ``[L, 2, N,
    Hkv]`` scales, or bf16), queries, selections and a table in which
    logical block 1 is unmapped and slot 1's last block is the trash block
    N-1, as an inactive row's is."""
    B, Hkv, G, Smax = 2, 2, 4, 384
    q, _, _, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.bfloat16,
                                    seed=seed)
    T = Smax // BLK
    N = B * T + 3
    rng = np.random.default_rng(seed + 2)
    shape = (L, 2, N, Hkv, BLK, D)
    scales = None
    if dtype == "int8":
        pool = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        scales = jnp.asarray(rng.uniform(0.005, 0.02, shape[:4]),
                             jnp.float32)
    else:
        pool = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    tbl = rng.permutation(N - 1)[:B * T].reshape(B, T).astype(np.int32)
    tbl[:, 1] = -1
    tbl[1, -1] = N - 1
    return q.reshape(B, Hkv * G, 1, D), pool, scales, ids, tbl, pos


class TestWholePoolOperand:
    """The paged kernel reading layer ``l``'s tiles from the whole pool at
    a layer index computes what it computes from that layer's K and V
    pools, bit for bit: the same tiles in the same item order."""

    @pytest.mark.parametrize("use_kernel", [True, False],
                             ids=["kernel", "reference"])
    @pytest.mark.parametrize("entry", ["paged", "packed"])
    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("dtype", ["bf16", "int8"])
    def test_layer_index_matches_layer_operand_bitwise(self, dtype, layer,
                                                       entry, use_kernel):
        q, pool, scales, ids, tbl, pos = _whole_pool_case(dtype)
        kw = dict(block_kv=BLK, partials=True, use_kernel=use_kernel,
                  interpret=True)
        if scales is not None:
            kw.update(k_scales=scales[layer, 0], v_scales=scales[layer, 1])
        if entry == "paged":
            fn, sel = ops.flash_decode_paged, jnp.asarray(ids)
        else:
            fn = ops.flash_decode_packed_paged
            sel = jnp.asarray(pack_decode_items(ids, block=BLK).flat())
        args = (sel, jnp.asarray(tbl), jnp.asarray(pos))
        per_layer = fn(q, pool[layer, 0], pool[layer, 1], *args, **kw)
        whole = fn(q, pool, None, *args, layer=jnp.int32(layer), **kw)
        assert float(jnp.abs(per_layer[2]).max()) > 0.0
        for a, b in zip(per_layer, whole):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("head_dim,whole", [(64, False), (128, True)])
    def test_decode_step_operand_follows_head_dim(self, monkeypatch,
                                                  head_dim, whole):
        """At 8-token blocks ``decode_step_paged`` hands the kernel the
        whole pool and the layer index only where head_dim is whole
        128-lane tiles; at 64 it keeps the per-layer K and V operands
        (at 128-token blocks head_dim 64 takes the whole pool too:
        ``test_decode_step_transposed_tiles_match_per_layer``)."""
        from repro.models import transformer as tfm

        seen = []
        real = ops.flash_decode_packed_paged

        def spy(q, k_pool, v_pool, *a, **k):
            seen.append((k_pool.ndim, v_pool is None, "layer" in k))
            return real(q, k_pool, v_pool, *a, **k)

        monkeypatch.setattr(ops, "flash_decode_packed_paged", spy)
        cfg = tfm.TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                                    num_kv_heads=2, head_dim=head_dim,
                                    d_ff=128, vocab_size=64, block_q=8,
                                    block_kv=8, layer_loop="scan")
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        pool = jnp.zeros((2, 2, 5, 2, 8, head_dim), jnp.bfloat16)
        ids = np.zeros((2, 2, 1), np.int32)
        items = pack_decode_items(ids, block=8).flat()
        logits, _ = tfm.decode_step_paged(
            params, pool, jnp.zeros((2,), jnp.int32), jnp.int32(3),
            jnp.asarray([[0, -1], [1, -1]], jnp.int32), cfg,
            packed_items=jnp.asarray(np.stack([items, items])))
        assert np.isfinite(np.asarray(logits)).all()
        assert seen == [(6, True, True) if whole else (4, False, False)]


class TestTransposedTiles:
    """At head_dim 64 and 128-token blocks the kernel reads layer ``l``'s
    tiles from the whole pool as the TPU stores them, ``[Dh, block]``
    (token on lanes): it computes bit for bit what the per-layer form
    computes from the layer's K and V padded to 128 lanes (the same
    tiles, the same f32 products in the same order), and the reference's
    numbers to within ``RTOL`` / ``ATOL`` (the reference takes its QK dot
    in bf16 operands, the kernel in f32)."""

    RTOL = ATOL = 2e-5

    @pytest.mark.parametrize("window", [None, 192])
    @pytest.mark.parametrize("entry", ["paged", "packed"])
    @pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "mid", "last"])
    @pytest.mark.parametrize("dtype", ["bf16", "int8"])
    def test_whole_pool_matches_per_layer_and_reference(self, dtype, layer,
                                                        entry, window):
        q, pool, scales, ids, tbl, pos = _whole_pool_case(dtype, D=64)
        kw = dict(block_kv=BLK, partials=True, window=window)
        if scales is not None:
            kw.update(k_scales=scales[layer, 0], v_scales=scales[layer, 1])
        if entry == "paged":
            fn, sel = ops.flash_decode_paged, jnp.asarray(ids)
        else:
            # a 64-row bucket: the real runs, then valid=0 padding rows
            fn = ops.flash_decode_packed_paged
            wl = pack_decode_items(ids, block=BLK, bucket=64)
            assert wl.total_real_items < wl.padded_length
            sel = jnp.asarray(wl.flat())
        args = (sel, jnp.asarray(tbl), jnp.asarray(pos))
        k_l, v_l = pool[layer, 0], pool[layer, 1]
        per_layer = fn(q, k_l, v_l, *args, use_kernel=True, interpret=True,
                       **kw)
        whole = fn(q, pool, None, *args, layer=jnp.int32(layer),
                   use_kernel=True, interpret=True, **kw)
        ref = fn(q, k_l, v_l, *args, use_kernel=False, **kw)
        assert float(jnp.abs(whole[2]).max()) > 0.0
        for a, b, r in zip(per_layer, whole, ref):
            assert np.array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_allclose(np.asarray(b), np.asarray(r),
                                       rtol=self.RTOL, atol=self.ATOL)


class TestPackedGreedyParity:
    """End-to-end: the engine's packed-ragged decode produces bitwise-
    identical greedy tokens to the padded baseline across policy x pool
    (the §2.8 acceptance matrix): a fresh pool, and a pool exactly as
    large as the prompts need whose blocks were recycled out of order."""

    @pytest.mark.parametrize("recycled", [
        pytest.param(False, id="paged"), pytest.param(True, id="recycled")])
    @pytest.mark.parametrize("policy", ["dense", "sparse", "windowed"])
    def test_packed_tokens_equal_padded(self, policy, recycled,
                                        recycle_pool):
        from repro.core.sparsity import synthetic_head_curves
        from repro.models.transformer import TransformerConfig, init_params
        from repro.serving import Engine, EngineConfig, SamplingParams

        cfg = TransformerConfig(
            num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            d_ff=128, vocab_size=256, layer_loop="unroll",
            attn_pattern="GL" if policy == "windowed" else "G",
            local_window=160)
        params = init_params(jax.random.PRNGKey(0), cfg)
        profile = synthetic_head_curves(cfg.num_layers, cfg.num_heads)
        attention = "dense" if policy == "dense" else "sparse"
        prompts = [np.random.default_rng(i).integers(0, 256, size=(n,))
                   for i, n in enumerate((40, 77, 150))]
        sp = SamplingParams(max_tokens=8)  # greedy
        outs = {}
        for mode in ("padded", "packed"):
            eng = Engine(cfg, params,
                         EngineConfig(attention=attention,
                                      budget_per_head=128, max_seq_len=512,
                                      num_slots=4, decode_worklist=mode,
                                      # the prompts' 1 + 1 + 2 blocks
                                      num_kv_blocks=4 if recycled else None),
                         profile=profile if attention == "sparse" else None)
            if recycled:
                recycle_pool(eng)
            outs[mode] = [r.generated for r in eng.serve(prompts, sp)]
        assert outs["packed"] == outs["padded"]


class TestZeroCopy:
    def test_fused_path_has_no_dense_gather(self):
        """The fused decode streams ONE [block_kv, D] tile per scan step
        (vmapped dynamic_slice — a per-block gather at most [B, Hkv, blk,
        D]); the dense [B, Hkv, nb*blk, D] buffer of the old path never
        appears in the jaxpr."""
        B, Hkv, G, Smax, D = 2, 2, 4, 512, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32)
        nb = ids.shape[-1]
        jaxpr = jax.make_jaxpr(
            lambda *a: flash_decode_reference(*a, block_kv=BLK))(
                q, kc, vc, jnp.asarray(ids), jnp.asarray(pos))
        dense = B * Hkv * nb * BLK * D
        per_block = B * Hkv * BLK * D
        sizes = gather_output_sizes(jaxpr.jaxpr)
        assert all(s <= per_block for s in sizes), (sizes, per_block)
        assert max(sizes, default=0) < dense

    def test_legacy_path_does_gather(self):
        """Sanity of the detector: the old path materializes the dense
        [B, Hkv, nb*blk, D] buffer."""
        B, Hkv, G, Smax, D = 2, 2, 4, 512, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32)
        nb = ids.shape[-1]
        jaxpr = jax.make_jaxpr(_gather_path)(
            q, kc, vc, jnp.asarray(ids), jnp.asarray(pos))
        sizes = gather_output_sizes(jaxpr.jaxpr)
        assert max(sizes, default=0) >= B * Hkv * nb * BLK * D


class TestShardMerge:
    def test_partials_merge_to_global_softmax(self):
        B, Hkv, G, Smax, D = 2, 3, 4, 512, 64
        q, kc, vc, ids, pos = _rand_case(B, Hkv, G, Smax, D, jnp.float32,
                                         seed=9)
        ref = _dense_oracle(q, kc, vc, ids, pos)
        n_sh = 4
        nloc = (Smax // BLK) // n_sh
        sloc = Smax // n_sh
        outs, ms, ls = [], [], []
        for s in range(n_sh):
            local = ids - s * nloc
            local = np.where((ids >= 0) & (local >= 0) & (local < nloc),
                             local, -1)
            o, m, l = flash_decode_reference(
                q, kc[:, :, s * sloc:(s + 1) * sloc],
                vc[:, :, s * sloc:(s + 1) * sloc],
                jnp.asarray(local), jnp.asarray(pos - s * sloc),
                block_kv=BLK)
            outs.append(o), ms.append(m), ls.append(l)
        merged = merge_partials(jnp.stack(outs), jnp.stack(ms),
                                jnp.stack(ls))
        np.testing.assert_allclose(np.asarray(merged), ref,
                                   atol=2e-5, rtol=2e-5)

    def _real_partial(self, seed, shape=(2, 3, 4), D=8):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        out = jax.random.normal(ks[0], shape + (D,), jnp.float32)
        m = jax.random.normal(ks[1], shape, jnp.float32)
        l = jax.random.uniform(ks[2], shape, jnp.float32,
                               minval=0.5, maxval=2.0)
        return out, m, l

    @pytest.mark.parametrize("neg", [-1e30, -np.inf])
    def test_single_real_shard_is_bitwise_identity(self, neg):
        """One real shard + one fully-masked shard: the merge returns the
        real shard's output BITWISE (regression: the x*l/l renorm
        perturbed it by ulps; a true -inf max produced exp(nan))."""
        out, m, l = self._real_partial(0)
        merged = merge_partials(
            jnp.stack([out, jnp.zeros_like(out)]),
            jnp.stack([m, jnp.full_like(m, neg)]),
            jnp.stack([l, jnp.zeros_like(l)]))
        assert np.array_equal(np.asarray(merged), np.asarray(out))

    @pytest.mark.parametrize("neg", [-1e30, -np.inf])
    def test_all_shards_masked_is_finite_zero(self, neg):
        out, m, l = self._real_partial(1)
        z = jnp.zeros_like
        merged = merge_partials(
            jnp.stack([z(out), z(out)]),
            jnp.stack([jnp.full_like(m, neg)] * 2),
            jnp.stack([z(l), z(l)]))
        got = np.asarray(merged)
        assert np.isfinite(got).all() and np.all(got == 0.0)

    def test_masked_shard_drops_out_of_multi_merge(self):
        """With >= 2 contributing shards, adding a fully-masked shard
        changes nothing — bitwise."""
        a = self._real_partial(2)
        b = self._real_partial(3)
        two = merge_partials(*[jnp.stack(x) for x in zip(a, b)])
        masked = (jnp.zeros_like(a[0]), jnp.full_like(a[1], -jnp.inf),
                  jnp.zeros_like(a[2]))
        three = merge_partials(*[jnp.stack(x) for x in zip(a, b, masked)])
        assert np.array_equal(np.asarray(two), np.asarray(three))
