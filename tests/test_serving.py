"""Serving: engine fidelity, continuous batching, cache bookkeeping,
sampler."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.sparsity import synthetic_head_curves
from repro.models.transformer import TransformerConfig, init_params
from repro.serving import Engine, EngineConfig, SamplingParams
from repro.serving.kv_cache import BlockAllocator
from repro.serving.sampler import sample
from repro.serving.scheduler import ContinuousBatcher, Request

CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, d_ff=128, vocab_size=256,
                        layer_loop="unroll")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def profile():
    return synthetic_head_curves(CFG.num_layers, CFG.num_heads)


class TestEngineFidelity:
    def test_sparse_full_budget_matches_dense(self, params, profile):
        """Budget = seq_len => S-HPLB sparse serving reproduces the dense
        engine's greedy outputs exactly (permutation is a no-op on the
        function; work-lists cover the full causal set)."""
        prompts = [np.random.default_rng(i).integers(0, 256, size=(40,))
                   for i in range(3)]
        dense = Engine(CFG, params,
                       EngineConfig(attention="dense", max_seq_len=256,
                                    num_slots=4))
        sparse = Engine(CFG, params,
                        EngineConfig(attention="sparse",
                                     budget_per_head=256,  # == max_seq_len
                                     max_seq_len=256, num_slots=4),
                        profile=profile)
        sp = SamplingParams(max_tokens=8)  # greedy
        da = dense.serve(prompts, sp)
        sa = sparse.serve(prompts, sp)
        for a, b in zip(da, sa):
            assert a.generated == b.generated

    def test_sparse_low_budget_still_generates(self, params, profile):
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=128,
                                  max_seq_len=256, num_slots=2),
                     profile=profile)
        done = eng.serve([np.arange(50) % 256], SamplingParams(max_tokens=5))
        assert len(done) == 1 and len(done[0].generated) == 5


class TestEngineHotPath:
    def test_prefill_bucketing_bounds_compiles(self, params, profile):
        """Distinct prompt lengths map onto pow2 chunk buckets: compile
        count is O(log chunk_tokens), not O(#lengths)."""
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=256,
                                  max_seq_len=256, num_slots=4),
                     profile=profile)
        prompts = [np.arange(n) % 256 for n in (10, 23, 40, 100, 129, 200)]
        done = eng.serve(prompts, SamplingParams(max_tokens=3))
        assert len(done) == len(prompts)
        # 6 lengths -> at most {128, 256} chunk buckets
        assert set(eng._prefill_chunk_jit) <= {128, 256}

    def test_bucketed_matches_exact_prefill(self, params, profile):
        """Padding a prompt up to its bucket changes nothing downstream."""
        mk = lambda mode: Engine(
            CFG, params,
            EngineConfig(attention="sparse", budget_per_head=256,
                         max_seq_len=256, num_slots=2,
                         prefill_buckets=mode, prefill_mode="monolithic"),
            profile=profile)
        prompts = [np.random.default_rng(3).integers(0, 256, size=(37,))]
        sp = SamplingParams(max_tokens=6)  # greedy
        a = mk("pow2").serve(prompts, sp)
        b = mk("exact").serve(prompts, sp)
        assert a[0].generated == b[0].generated

    @pytest.mark.parametrize("attn", ["sparse", "dense"])
    def test_chunked_matches_monolithic_serve(self, params, profile, attn):
        """Greedy generations are IDENTICAL between chunked and monolithic
        prefill — chunk work-lists are slices of the monolithic lists and
        the chunk executor accumulates the same tiles in the same order."""
        prompts = [np.random.default_rng(i).integers(0, 256, size=(n,))
                   for i, n in enumerate((40, 300, 130, 70))]
        sp = SamplingParams(max_tokens=8)  # greedy
        outs = {}
        for mode in ("monolithic", "chunked"):
            eng = Engine(
                CFG, params,
                EngineConfig(attention=attn, budget_per_head=512,
                             max_seq_len=512, num_slots=4,
                             prefill_mode=mode, prefill_chunk_tokens=128),
                profile=profile if attn == "sparse" else None)
            outs[mode] = [r.generated for r in eng.serve(prompts, sp)]
        assert outs["chunked"] == outs["monolithic"]

    @pytest.mark.parametrize("attn,max_seq,chunk,plen", [
        # final chunk's pow2 bucket exceeds the cache rows left after
        # q_offset (regression: the K/V write clamped and overwrote
        # earlier rows)
        ("dense", 896, 512, 880),
        ("sparse", 896, 512, 880),
        # chunk budget NOT a pow2 multiple of block (regression: the
        # bucket spanned more q-blocks than the work-list slice covered)
        ("sparse", 512, 192, 300),
    ])
    def test_chunked_matches_monolithic_odd_geometry(self, params, profile,
                                                     attn, max_seq, chunk,
                                                     plen):
        prompts = [np.random.default_rng(7).integers(0, 256, size=(plen,)),
                   np.random.default_rng(8).integers(0, 256, size=(70,))]
        sp = SamplingParams(max_tokens=8)  # greedy
        outs = {}
        for mode in ("monolithic", "chunked"):
            eng = Engine(
                CFG, params,
                EngineConfig(attention=attn, budget_per_head=max_seq,
                             max_seq_len=max_seq, num_slots=2,
                             prefill_mode=mode, prefill_chunk_tokens=chunk),
                profile=profile if attn == "sparse" else None)
            outs[mode] = [r.generated for r in eng.serve(prompts, sp)]
        assert outs["chunked"] == outs["monolithic"]

    def test_mixed_ticks_interleave_prefill_and_decode(self, params,
                                                       profile):
        """A long admission no longer stalls the decode batch: while the
        long prompt chunk-prefills, earlier requests keep decoding."""
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=512,
                                  max_seq_len=512, num_slots=4,
                                  prefill_chunk_tokens=128),
                     profile=profile)
        sp = SamplingParams(max_tokens=12)
        batcher = eng.make_batcher()
        pf, df = eng.step_fns(sp)
        batcher.submit(Request(rid=0, prompt=np.arange(30) % 256,
                               sampling=sp))
        batcher.tick(pf, df)          # rid 0 prefilled + first decode
        assert 0 in batcher.active
        batcher.submit(Request(rid=1, prompt=np.arange(400) % 256,
                               sampling=sp))
        n0 = len(batcher.active[0].generated)
        ticks_while_prefilling = 0
        while batcher.prefilling is not None or batcher.pending:
            batcher.tick(pf, df)
            ticks_while_prefilling += 1
        # the 400-token prompt needed multiple chunk ticks, and rid 0
        # decoded through every one of them
        assert ticks_while_prefilling >= 3
        assert len(batcher.active[0].generated) >= n0 + 3
        batcher.run(pf, df)
        assert batcher.stats.completed == 2

    def test_decode_selection_tracks_position(self, params, profile):
        """Block selection is recomputed as slots cross block boundaries
        instead of being frozen at max_seq_len."""
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=128,
                                  max_seq_len=512, num_slots=1),
                     profile=profile)
        eng.serve([np.arange(250) % 256], SamplingParams(max_tokens=12))
        # crossed the 256-token boundary mid-generation: ids for both block
        # counts were materialized (under epoch 0), at the capped width
        assert {(0, 2), (0, 3)} <= set(eng._decode_ids_by_nblocks)
        widths = {a.shape[-1] for a in eng._decode_ids_by_nblocks.values()}
        assert widths == {eng._nb_cap[0]}

    def test_decode_newest_block_at_floor_budget(self, params, profile):
        """Regression: at the minimum budget (floor == block -> exactly one
        block per kv head) decode must attend the block holding the token
        just written.  The old `[0] + recent(n-1)` selection attended ONLY
        the sink at n == 1, silently losing recency/causality."""
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=128,
                                  allocator="uniform", max_seq_len=512,
                                  num_slots=1),
                     profile=profile)
        for nkv in (1, 2, 3, 4):
            ids = eng.decode_block_ids(nkv * 128)
            assert ((ids >= 0).sum(-1) == 1).all()    # floor budget: 1 block
            assert (ids[..., 0] == nkv - 1).all()     # ...and it's the newest
        # at any budget, the newest block is in every head's selection
        eng2 = Engine(CFG, params,
                      EngineConfig(attention="sparse", budget_per_head=256,
                                   max_seq_len=512, num_slots=1),
                      profile=profile)
        ids = eng2.decode_block_ids(512)
        assert (ids == 512 // 128 - 1).any(-1).all()


class TestPagedLayout:
    """cache_layout="paged" (the default) vs the contiguous parity
    baseline: identical greedy tokens on the chunked-prefill + decode
    serving path, and token-granular admission."""

    @pytest.mark.parametrize("attn,pattern,mode", [
        ("sparse", "G", "chunked"),      # S-HPLB budgeted decode
        ("dense", "G", "chunked"),       # dense baseline
        ("dense", "GL", "chunked"),      # windowed (local) layers
        ("sparse", "G", "monolithic"),   # whole-prompt scatter merge
    ])
    def test_paged_matches_contiguous_serve(self, params, profile, attn,
                                            pattern, mode):
        cfg = (CFG if pattern == "G"
               else TransformerConfig(
                   num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                   d_ff=128, vocab_size=256, layer_loop="unroll",
                   attn_pattern=pattern, local_window=160))
        p = params if pattern == "G" else init_params(
            jax.random.PRNGKey(0), cfg)
        prompts = [np.random.default_rng(i).integers(0, 256, size=(n,))
                   for i, n in enumerate((40, 300, 130, 70))]
        sp = SamplingParams(max_tokens=8)  # greedy
        outs = {}
        for layout in ("contiguous", "paged"):
            eng = Engine(
                cfg, p,
                EngineConfig(attention=attn, budget_per_head=512,
                             max_seq_len=512, num_slots=4,
                             prefill_mode=mode, cache_layout=layout),
                profile=profile if attn == "sparse" else None)
            outs[layout] = [r.generated for r in eng.serve(prompts, sp)]
        assert outs["paged"] == outs["contiguous"]

    def test_paged_admission_is_block_granular(self, params, profile):
        """With a pool smaller than num_slots * max_seq_len, admission is
        bounded by BLOCKS (token-granular), not slots — and everything
        still drains with blocks conserved."""
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=256,
                                  max_seq_len=512, num_slots=4,
                                  num_kv_blocks=6),  # 768 tokens of HBM
                     profile=profile)
        prompts = [np.random.default_rng(i).integers(0, 256, size=(300,))
                   for i in range(4)]
        done = eng.serve(prompts, SamplingParams(max_tokens=4))
        assert len(done) == 4 and all(len(r.generated) == 4 for r in done)
        alloc = eng.kv.alloc
        assert alloc.free_blocks == alloc.num_blocks == 6
        assert alloc.conserves()

    def test_paged_pool_is_token_not_slot_bound(self, params, profile):
        """The same pool bytes hold MORE short sequences than the
        contiguous layout's slot count — the capacity headline, at engine
        granularity (benchmarks/serving.py measures the full curve)."""
        # contiguous: 2 slots x 512 tokens = 8 blocks of HBM, 2 sequences
        # paged: the same 8 blocks hold 4 x (70 + 8) -> 4 x 1 block
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=256,
                                  max_seq_len=512, num_slots=4,
                                  num_kv_blocks=8),
                     profile=profile)
        b = eng.make_batcher()
        pf, df = eng.step_fns(SamplingParams(max_tokens=8))
        for i in range(4):
            b.submit(Request(rid=i,
                             prompt=np.arange(70 + i) % 256,
                             sampling=SamplingParams(max_tokens=8)))
        peak = 0
        while b.busy:
            b.tick(pf, df)
            peak = max(peak, len(b._slot_of))   # sequences resident at once
        assert b.stats.completed == 4
        # all four were resident at once on 2-contiguous-slots' bytes
        assert peak == 4


def _fake_fns(first_token=1, decode_token=1):
    calls = {"prefill": 0, "decode": 0}

    def prefill(toks, slot, q_offset, is_final, prompt_len):
        calls["prefill"] += 1
        return first_token if is_final else None

    def decode(slots, toks, pos):
        calls["decode"] += 1
        return np.full(len(slots), decode_token, np.int32)

    return prefill, decode, calls


class TestPackedDecodePath:
    """Engine-side contracts of the cost-packed ragged decode worklists
    (DESIGN.md §2.8): bounded host caches, pow2 item buckets, plan reuse
    across ticks, pipelined prefetch, and bubble telemetry."""

    def _engine(self, params, profile, **kw):
        base = dict(attention="sparse", budget_per_head=256,
                    max_seq_len=512, num_slots=4)
        base.update(kw)
        return Engine(CFG, params, EngineConfig(**base), profile=profile)

    def test_worklists_cache_keyed_by_bucket(self, params, profile):
        """Raw seq_len keys grew unboundedly under varied traffic; bucket
        keys cap the cache at the pow2 bucket set."""
        eng = self._engine(params, profile)
        for n in (10, 23, 40, 100, 129, 129, 200, 255):
            eng.worklists_for(n)
        assert set(eng._worklists_cache) <= {(0, 128), (0, 256), (0, 512)}

    def test_decode_ids_memo_is_bounded(self, params, profile):
        eng = self._engine(params, profile)
        cap = eng.ecfg.max_seq_len // eng.ecfg.block
        for nb in list(range(1, 20)) + [500, 10_000]:
            eng._decode_ids_for_nblocks(nb)
        assert len(eng._decode_ids_by_nblocks) <= cap

    def test_plan_cache_reused_between_boundaries_and_bounded(
            self, params, profile):
        eng = self._engine(params, profile)
        done = eng.serve([np.arange(40) % 256],
                         SamplingParams(max_tokens=12))
        assert len(done[0].generated) == 12
        s = eng.decode_stats
        # selections change only at block boundaries: nearly every tick
        # hits the memoized plan
        assert s["plan_hits"] > 0
        assert s["plan_misses"] + s["plan_prefetches"] <= 3
        assert len(eng._packed_plan_cache) <= eng._packed_plan_cap

    def test_item_buckets_are_pow2_and_few(self, params, profile):
        eng = self._engine(params, profile)
        prompts = [np.arange(n) % 256 for n in (30, 80, 150, 260)]
        eng.serve(prompts, SamplingParams(max_tokens=6))
        for flat_len in eng._decode_packed_jit:
            per_shard = flat_len // eng.ecfg.num_model_shards
            assert per_shard & (per_shard - 1) == 0, flat_len
        assert len(eng._decode_packed_jit) <= 4

    def test_bubble_stats_emitted(self, params, profile):
        eng = self._engine(params, profile)
        eng.serve([np.arange(60) % 256, np.arange(30) % 256],
                  SamplingParams(max_tokens=8))
        st = eng.decode_bubble_stats
        assert st["ticks"] > 0
        assert 0.0 <= st["padding_waste"] < 1.0
        assert 0.0 <= st["padded_path_waste"] < 1.0
        # the packed grid never exceeds the padded baseline's
        assert st["grid_vs_padded"] <= 1.0 + 1e-9
        assert st["mean_imbalance"] >= 1.0
        assert st["last_tick"]["real_items"] > 0

    def test_prefetch_plans_next_tick(self, params, profile):
        """The engine's pipelined host planning builds the next tick's
        worklist from the scheduler preview while the device step is in
        flight — observable as prefetch builds at block boundaries."""
        eng = self._engine(params, profile)
        # 124-token prompt: decode crosses the 128 boundary on tick ~4, so
        # the preview sees the new block count one tick early
        eng.serve([np.arange(124) % 256], SamplingParams(max_tokens=10))
        s = eng.decode_stats
        assert s["plan_prefetches"] >= 1
        # prefetched signatures must then HIT (the preview was correct)
        assert s["plan_misses"] <= 1


class TestSchedulerPreview:
    def test_preview_matches_next_tick_positions(self):
        prefill, decode, calls = _fake_fns()
        b = ContinuousBatcher(num_slots=2, num_blocks=64, max_seq_len=256)
        seen = []

        def decode_with_preview(slots, toks, pos):
            seen.append((tuple(slots), tuple(int(p) for p in pos),
                         b.preview_next_decode()))
            return decode(slots, toks, pos)

        for i in range(2):
            b.submit(Request(rid=i, prompt=np.arange(10),
                             sampling=SamplingParams(max_tokens=4)))
        b.run(prefill, decode_with_preview)
        for i in range(len(seen) - 1):
            _, _, preview = seen[i]
            nxt_slots, nxt_pos, _ = seen[i + 1]
            if preview is None:
                continue
            pslots, ppos = preview
            # the preview predicts the next tick exactly whenever no
            # completion/admission changed the batch in between
            if tuple(pslots) == nxt_slots:
                assert tuple(ppos)[:len(nxt_pos)] == nxt_pos

    def test_preview_none_when_idle(self):
        b = ContinuousBatcher(num_slots=2, num_blocks=64, max_seq_len=256)
        assert b.preview_next_decode() is None


class TestScheduler:
    def test_admission_respects_slots(self):
        prefill, decode, calls = _fake_fns()
        b = ContinuousBatcher(num_slots=2, num_blocks=64, max_seq_len=256)
        for i in range(5):
            b.submit(Request(rid=i, prompt=np.arange(10),
                             sampling=SamplingParams(max_tokens=3)))
        done = b.run(prefill, decode)
        assert len(done) == 5
        assert calls["prefill"] == 5
        assert b.stats.completed == 5
        assert not b.busy

    def test_rejected_requests_are_returned(self):
        """Over-length requests are refused but NOT dropped: they come back
        flagged, so completed + rejected == submitted and result lists zip
        with the inputs."""
        prefill, decode, _ = _fake_fns()
        b = ContinuousBatcher(num_slots=2, num_blocks=64, max_seq_len=64)
        b.submit(Request(rid=0, prompt=np.arange(100),
                         sampling=SamplingParams(max_tokens=10)))
        b.submit(Request(rid=1, prompt=np.arange(10),
                         sampling=SamplingParams(max_tokens=3)))
        done = b.run(prefill, decode)
        assert len(done) == 2 and not b.busy
        by_rid = {r.rid: r for r in done}
        assert by_rid[0].rejected and by_rid[0].done
        assert by_rid[0].generated == []
        assert not by_rid[1].rejected
        assert b.stats.completed + b.stats.rejected == 2

    def test_stop_token_at_prefill_ends_request(self):
        """A prefill that samples the stop token must finish the request —
        the completion check is shared with the decode path."""
        stop = 7
        prefill, decode, calls = _fake_fns(first_token=stop)
        b = ContinuousBatcher(num_slots=2, num_blocks=64, max_seq_len=256)
        b.submit(Request(rid=0, prompt=np.arange(10),
                         sampling=SamplingParams(max_tokens=50,
                                                 stop_token=stop)))
        done = b.run(prefill, decode)
        assert len(done) == 1
        assert done[0].generated == [stop]
        assert calls["decode"] == 0  # never decoded past the stop

    def test_max_tokens_one_samples_exactly_one(self):
        prefill, decode, calls = _fake_fns()
        b = ContinuousBatcher(num_slots=2, num_blocks=64, max_seq_len=256)
        b.submit(Request(rid=0, prompt=np.arange(10),
                         sampling=SamplingParams(max_tokens=1)))
        done = b.run(prefill, decode)
        assert done[0].generated == [1]
        assert calls["decode"] == 0

    def test_chunked_prefill_covers_prompt_block_aligned(self):
        """Token-budget ticks split the prompt into block-aligned chunks
        (only the final chunk may be partial) that exactly cover it."""
        chunks = []

        def prefill(toks, slot, q_offset, is_final, prompt_len):
            chunks.append((q_offset, toks.shape[-1], is_final))
            return 1 if is_final else None

        def decode(slots, toks, pos):
            return np.ones(len(slots), np.int32)

        b = ContinuousBatcher(num_slots=2, num_blocks=64, max_seq_len=1024,
                              block=128, token_budget=256)
        b.submit(Request(rid=0, prompt=np.arange(700),
                         sampling=SamplingParams(max_tokens=2)))
        b.run(prefill, decode)
        assert sum(c for _, c, _ in chunks) == 700
        pos = 0
        for off, c, final in chunks:
            assert off == pos and off % 128 == 0
            if not final:
                assert c % 128 == 0
            pos += c
        assert chunks[-1][2] and b.stats.prefill_chunks == len(chunks)

    @pytest.mark.parametrize("rows,size", [(0, 256), (1, 256), (6, 256),
                                           (63, 256), (64, 128)])
    def test_decoding_rows_take_whole_blocks_of_the_budget(self, rows, size):
        """With ``rows`` sequences decoding, a long prompt's non-final
        chunks are the budget less the rows rounded to the nearest block:
        a few rows leave a 256-token chunk whole, 64 take a block."""
        chunks = []

        def prefill(toks, slot, q_offset, is_final, prompt_len):
            if prompt_len > 1:
                chunks.append((toks.shape[-1], is_final))
            return 1 if is_final else None

        def decode(slots, toks, pos):
            return np.ones(len(slots), np.int32)

        b = ContinuousBatcher(num_slots=rows + 1, num_blocks=4 * rows + 16,
                              max_seq_len=2048, block=128, token_budget=256)
        for r in range(rows):
            b.submit(Request(rid=r, prompt=np.arange(1),
                             sampling=SamplingParams(max_tokens=400)))
        while len(b.active) < rows:
            b.tick(prefill, decode)
        b.submit(Request(rid=rows, prompt=np.arange(1000),
                         sampling=SamplingParams(max_tokens=1)))
        while b.pending or b.prefilling is not None:
            b.tick(prefill, decode)
        assert len(b.active) == rows
        assert sum(c for c, _ in chunks) == 1000
        assert {c for c, final in chunks if not final} == {size}
    def test_admit_free_cycle(self):
        a = BlockAllocator(num_blocks=10, block=128)
        a.admit(1, 500)   # 4 blocks
        a.admit(2, 700)   # 6 blocks
        assert a.free_blocks == 0
        assert not a.can_admit(1)
        a.free(1)
        assert a.free_blocks == 4
        a.admit(3, 512)
        assert a.free_blocks == 0
        assert a.conserves()

    def test_append_token_grows_at_boundary(self):
        a = BlockAllocator(num_blocks=4, block=128)
        a.admit(1, 128, max_new_tokens=2)
        assert len(a.table(1)) == 1
        a.append_token(1)   # token 129 crosses into block 2
        assert len(a.table(1)) == 2
        a.append_token(1)   # token 130: no growth mid-block
        assert len(a.table(1)) == 2
        assert a.seq_tokens(1) == 130 and a.conserves()

    def test_reservation_guards_decode_growth(self):
        """Admission headroom counts reserved-but-unmapped blocks, so a
        later arrival can never steal the blocks an active sequence's
        generation is entitled to."""
        a = BlockAllocator(num_blocks=3, block=128)
        a.admit(1, 128, max_new_tokens=128)  # maps 1, reserves 2
        assert a.free_blocks == 2            # physically free...
        assert a.available_blocks == 1       # ...but one is spoken for
        assert not a.can_admit(200)          # 2 blocks > 1 available
        a.admit(2, 128)
        with pytest.raises(MemoryError):
            a.admit(3, 1)
        a.append_token(1)                    # the reserved block maps fine
        assert len(a.table(1)) == 2

    def test_exhaustion_raises(self):
        a = BlockAllocator(num_blocks=2, block=128)
        with pytest.raises(MemoryError):
            a.admit(1, 1000)


class TestSampler:
    def test_greedy_is_argmax(self):
        logits = jnp.asarray([[1.0, 5.0, 2.0], [3.0, 0.0, 9.0]])
        t = sample(logits, jax.random.PRNGKey(0),
                   SamplingParams(temperature=0.0))
        assert t.tolist() == [1, 2]

    def test_topk_restricts_support(self):
        logits = jnp.asarray([[0.0, 10.0, 9.0, -5.0]])
        for seed in range(20):
            t = sample(logits, jax.random.PRNGKey(seed),
                       SamplingParams(temperature=1.0, top_k=2))
            assert int(t[0]) in (1, 2)

    def test_top_p_restricts_support(self):
        logits = jnp.asarray([[10.0, 1.0, 0.5, 0.2]])
        for seed in range(20):
            t = sample(logits, jax.random.PRNGKey(seed),
                       SamplingParams(temperature=1.0, top_p=0.5))
            assert int(t[0]) == 0
