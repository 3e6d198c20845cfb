"""Plan epochs (DESIGN.md §2.9): online sparsity telemetry, drift
detection, composable plan deltas, and in-flight engine replanning."""
import dataclasses

import numpy as np
import jax
import pytest

from repro.core.planner import LayerPlan, make_plan, plan_delta, plans_equal
from repro.core.sparsity import (
    HeadSparsityProfile,
    OnlineSparsityEstimator,
    SCHEMA_VERSION,
    synthetic_head_curves,
)
from repro.models.transformer import TransformerConfig, init_params
from repro.serving import Engine, EngineConfig, SamplingParams
from repro.serving.scheduler import ContinuousBatcher, Request

# the epoch swap re-gathers the pool's codes and, quantized, its scales
KV_DTYPES = [pytest.param("bf16", id="paged"),
             pytest.param("int8", id="paged-int8")]

CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, d_ff=128, vocab_size=256,
                        layer_loop="unroll")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def profile():
    return synthetic_head_curves(CFG.num_layers, CFG.num_heads)


def _shuffled(profile, seed=3):
    """Same curves, head identities permuted per layer — a maximally
    'drifted' profile with identical marginal statistics."""
    p = HeadSparsityProfile(profile.curves.copy(), profile.grid.copy(),
                            profile.num_samples, dict(profile.meta))
    rng = np.random.default_rng(seed)
    for l in range(p.num_layers):
        p.curves[l] = p.curves[l][rng.permutation(p.num_heads)]
    return p


class TestOnlineEstimator:
    def test_power_law_samples_recover_curves(self):
        """Feeding (frac, rec) samples drawn from known power laws yields
        a profile whose budgets correlate ~1 with the ground truth."""
        truth = synthetic_head_curves(2, 4)
        est = OnlineSparsityEstimator(2, 4, min_samples=4)
        rng = np.random.default_rng(0)
        for _ in range(16):
            frac = rng.uniform(0.05, 0.6, size=(2, 4))
            rec = np.stack([
                [np.interp(frac[l, h], truth.grid, truth.curves[l, h])
                 for h in range(4)] for l in range(2)])
            est.update(rec, frac)
        online = est.to_profile(grid=truth.grid)
        assert online.stability_vs(truth) > 0.9
        d = est.drift_vs(truth)
        assert d["drift"] < 0.35
        assert d["heads_observed"] == 8

    def test_drift_flags_shuffled_profile(self):
        truth = synthetic_head_curves(2, 4)
        est = OnlineSparsityEstimator(2, 4, min_samples=4)
        rng = np.random.default_rng(0)
        for _ in range(16):
            frac = rng.uniform(0.05, 0.6, size=(2, 4))
            rec = np.stack([
                [np.interp(frac[l, h], truth.grid, truth.curves[l, h])
                 for h in range(4)] for l in range(2)])
            est.update(rec, frac)
        drifted = est.drift_vs(_shuffled(truth))
        matched = est.drift_vs(truth)
        assert drifted["drift"] > matched["drift"]
        assert drifted["drift"] > 0.5

    def test_full_budget_samples_carry_no_signal(self):
        """rec ~ 1 at frac ~ 1 must NOT fabricate sparsity evidence."""
        est = OnlineSparsityEstimator(1, 4)
        for _ in range(8):
            est.update(np.ones((1, 4)), np.ones((1, 4)))
        assert np.isnan(est.head_betas()).all()
        truth = synthetic_head_curves(1, 4)
        assert est.drift_vs(truth)["drift"] == 0.0
        # unobserved heads fall back to the offline curves exactly
        online = est.to_profile(fallback=truth)
        assert np.allclose(online.curves, truth.curves)

    def test_under_sampled_heads_excluded(self):
        est = OnlineSparsityEstimator(1, 2, min_samples=4)
        est.update(np.array([[0.9, 0.9]]), np.array([[0.2, 0.2]]))
        assert np.isnan(est.head_betas()).all()
        assert est.total_samples == 2


class TestProfileSchema:
    def test_save_load_round_trip(self, tmp_path):
        p = synthetic_head_curves(3, 4, seed=7)
        path = str(tmp_path / "prof.npz")
        p.save(path)
        q = HeadSparsityProfile.load(path)
        assert np.allclose(p.curves, q.curves)
        assert np.allclose(p.grid, q.grid)
        assert q.num_samples == p.num_samples
        assert q.meta["schema_version"] == SCHEMA_VERSION
        for k, v in p.meta.items():
            assert q.meta[k] == v

    def test_v1_files_still_load(self, tmp_path):
        """Files written before the schema field read as version 1."""
        p = synthetic_head_curves(1, 2)
        path = str(tmp_path / "v1.npz")
        np.savez_compressed(path, curves=p.curves, grid=p.grid,
                            num_samples=np.int64(1))
        q = HeadSparsityProfile.load(path)
        assert q.meta["schema_version"] == 1
        assert np.allclose(q.curves, p.curves)

    def test_online_snapshot_round_trips(self, tmp_path):
        """Epoch snapshots written by the telemetry layer carry the schema
        version and survive a round trip."""
        est = OnlineSparsityEstimator(1, 4, min_samples=1)
        est.update(np.full((1, 4), 0.8), np.full((1, 4), 0.3))
        snap = est.to_profile(meta={"epoch": 3})
        path = str(tmp_path / "epoch3.npz")
        snap.save(path)
        back = HeadSparsityProfile.load(path)
        assert back.meta["epoch"] == 3
        assert back.meta["online"] is True
        assert back.meta["schema_version"] == SCHEMA_VERSION
        assert np.allclose(back.curves, snap.curves)


def _swapped_plan(plan):
    """Same per-original-head budgets, kv groups swapped across shards —
    a pure head MOVE (function-preserving at any budget)."""
    layers = []
    H = plan.num_heads
    for lp in plan.layers:
        perm = np.array([2, 3, 0, 1], np.int64)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(H)
        borig = np.zeros_like(lp.budgets)
        borig[lp.perm] = lp.budgets
        layers.append(LayerPlan(
            perm=perm, inv_perm=inv, budgets=borig[perm],
            kv_perm=np.array([1, 0], np.int64),
            device_loads=lp.device_loads.copy(),
            assignment=lp.assignment))
    return dataclasses.replace(plan, layers=layers)


class TestEngineEpochSwap:
    def _drive(self, eng, sp, lens, swap_tick=None, new_plan=None,
               new_profile=None):
        b = eng.make_batcher()
        pf, df = eng.step_fns(sp)
        for i, n in enumerate(lens):
            b.submit(Request(rid=i, prompt=np.arange(n) % 256, sampling=sp))
        done, ticks, tokens_before_swap = [], 0, None
        while b.busy and ticks < 10_000:
            done.extend(b.tick(pf, df))
            ticks += 1
            if swap_tick is not None and ticks == swap_tick:
                assert b.replan_safe
                changed = eng.replan_now(profile=new_profile, plan=new_plan)
                assert changed, "swap plan was a no-op"
                tokens_before_swap = {
                    r.rid: list(r.generated)
                    for r in list(done) + list(b.active.values())}
        assert not b.busy
        return {r.rid: r.generated for r in done}, tokens_before_swap

    @pytest.mark.parametrize("kv_dtype", KV_DTYPES)
    def test_head_move_swap_is_bitwise_invisible(self, params, profile,
                                                 kv_dtype):
        """Full budgets + a forced head MOVE mid-run: params re-permute,
        the resident cache's kv-head axis re-gathers, and greedy tokens
        stay bitwise identical to the frozen engine — the swap machinery
        is function-preserving end to end."""
        sp = SamplingParams(max_tokens=16)
        mk = lambda: Engine(
            CFG, params,
            EngineConfig(attention="sparse", budget_per_head=512,
                         max_seq_len=512, num_slots=4, num_model_shards=2,
                         kv_dtype=kv_dtype), profile=profile)
        frozen, _ = self._drive(mk(), sp, (50, 90, 130))
        eng = mk()
        swapped, _ = self._drive(eng, sp, (50, 90, 130), swap_tick=5,
                                 new_plan=_swapped_plan(eng.plan))
        assert swapped == frozen
        assert eng.epoch == 1 and eng.replans == 1
        assert eng.decode_stats["last"]["epoch"] == 1

    @pytest.mark.parametrize("kv_dtype", KV_DTYPES)
    def test_budget_swap_mid_batch(self, params, profile, kv_dtype):
        """The acceptance path: a mixed batch swaps onto NEW BUDGETS mid
        run.  Tokens sampled before the swap are bitwise identical to the
        frozen engine's; afterwards decode runs under the new epoch
        (epoch-tagged worklists) and every sequence completes."""
        sp = SamplingParams(max_tokens=20)
        lens = (50, 90, 200)
        mk = lambda: Engine(
            CFG, params,
            EngineConfig(attention="sparse", budget_per_head=256,
                         max_seq_len=512, num_slots=4,
                         kv_dtype=kv_dtype), profile=profile)
        frozen, _ = self._drive(mk(), sp, lens)
        eng = mk()
        out, before = self._drive(eng, sp, lens, swap_tick=6,
                                  new_profile=_shuffled(profile))
        assert eng.epoch == 1
        # no dropped/corrupted sequences
        assert sorted(out) == list(range(len(lens)))
        assert all(len(t) == sp.max_tokens for t in out.values())
        # pre-swap prefix identical to the frozen engine, bitwise
        for rid, toks in before.items():
            assert toks == frozen[rid][:len(toks)], f"rid {rid} diverged"
        # post-swap ticks executed the NEW epoch's worklists
        assert eng.decode_stats["last"]["epoch"] == 1
        new_budgets = eng.plan.layers[0].budgets
        assert not np.array_equal(
            new_budgets,
            make_plan(profile, num_devices=1,
                      num_kv_heads=CFG.num_kv_heads, seq_len=512,
                      total_budget_per_head=256).layers[0].budgets)

    def test_swap_purges_dead_epoch_artifacts(self, params, profile):
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=256,
                                  max_seq_len=512, num_slots=2),
                     profile=profile)
        eng.serve([np.arange(150) % 256], SamplingParams(max_tokens=6))
        assert all(k[0] == 0 for k in eng._worklists_cache)
        assert eng.replan_now(profile=_shuffled(profile))
        eng.serve([np.arange(150) % 256], SamplingParams(max_tokens=6))
        for d in (eng._worklists_cache, eng._chunk_cap,
                  eng._chunk_wl_cache, eng._decode_ids_by_nblocks):
            assert all(k[0] == 1 for k in d), "dead epoch survived the purge"
        assert set(eng._nb_cap) == {1}
        # packed-plan LRU keys are epoch-tagged: stale plans cannot be hit
        assert all(k[0] in (0, 1) for k in eng._packed_plan_cache)

    def test_prefill_jit_memos_are_lru_bounded(self, params, profile):
        """Repeated epoch swaps cannot leak compiled prefill entries: the
        (epoch, bucket) memo is LRU-capped."""
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=256,
                                  max_seq_len=512, num_slots=2,
                                  prefill_mode="monolithic",
                                  prefill_jit_cap=3, chunk_jit_cap=2),
                     profile=profile)
        sp = SamplingParams(max_tokens=2)
        for e in range(3):
            eng.serve([np.arange(40) % 256, np.arange(150) % 256], sp)
            eng.replan_now(plan=_swapped_plan(eng.plan))
        assert len(eng._prefill_jit) <= 3
        assert len(eng._prefill_chunk_jit) <= 2
        # most-recent epoch's entries are the survivors
        assert any(k[0] == eng.epoch for k in eng._prefill_jit)

    def test_telemetry_driven_replan_policy(self, params, profile):
        """serve() with a replan policy: telemetry accumulates, the policy
        fires at the cadence, and the engine finishes on a consistent
        epoch with per-epoch recovery aggregates."""
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=256,
                                  max_seq_len=512, num_slots=4,
                                  telemetry_every=2, replan_every=8),
                     profile=_shuffled(profile, seed=11))
        done = eng.serve([np.arange(n) % 256 for n in (60, 120, 220)],
                         SamplingParams(max_tokens=24))
        assert all(len(r.generated) == 24 for r in done)
        assert eng.telemetry.total_samples > 0
        st = eng.decode_bubble_stats
        assert st["epochs"][0]["telemetry_samples"] > 0
        assert st["epochs"][0]["realized_recovery"] is not None
        assert st["epoch"] == eng.epoch
        # the policy ran: either it swapped, or every attempt was a no-op
        # on an already-converged plan — both leave the tick counter reset
        assert eng._ticks_since_replan < 8

    def test_telemetry_lands_in_original_head_space(self, params, profile):
        """Regression: the probe sees PERMUTED (slot-order) heads; the
        estimator, drift profiles, and replanner live in ORIGINAL head
        order.  With a 2-shard plan (non-identity perm) each head's
        observed budget fraction must track its ORIGINAL-head budget, not
        its slot's."""
        # heads 0/1 sparse, heads 2/3 diffuse: group 1 carries more
        # budget, so the 2-shard LPT placement puts it FIRST — perm
        # [2, 3, 0, 1], kv_perm [1, 0] (non-identity by construction)
        from repro.core.sparsity import DEFAULT_BUDGET_GRID
        grid = DEFAULT_BUDGET_GRID
        betas = np.array([0.05, 0.06, 0.85, 0.9])
        curves = np.stack([np.stack([grid ** b for b in betas])] * 2)
        skewed = HeadSparsityProfile(curves, grid)
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=256,
                                  max_seq_len=512, num_slots=2,
                                  num_model_shards=2, telemetry_every=1),
                     profile=skewed)
        perms = np.stack([lp.perm for lp in eng.plan.layers])
        assert not np.array_equal(
            perms, np.tile(np.arange(CFG.num_heads), (CFG.num_layers, 1))
        ), "fixture plan must have a non-identity permutation"
        eng.serve([np.arange(500) % 256], SamplingParams(max_tokens=8))
        est = eng.telemetry
        assert est.total_samples > 0
        blk = eng.ecfg.block
        gsz = CFG.num_heads // CFG.num_kv_heads
        for l in range(CFG.num_layers):
            budgets = eng.plan.budgets_by_original_head(l)
            # decode selection is per ORIGINAL kv group: max over its
            # q heads' budgets, block-quantized
            gb = budgets.reshape(CFG.num_kv_heads, gsz).max(axis=1)
            sel_blocks = np.repeat(np.maximum(-(-gb // blk), 1), gsz)
            # observed budget fraction per ORIGINAL head must be ordered
            # like the original-head budgets (scatter through the perm)
            f = est.frac_ema[l]
            for a in range(CFG.num_heads):
                for b in range(CFG.num_heads):
                    if sel_blocks[a] < sel_blocks[b]:
                        assert f[a] < f[b] + 1e-6, (l, a, b, f, sel_blocks)

    def test_drift_threshold_gate(self, params, profile):
        """drift_threshold=inf never replans; the drift reading is still
        recorded into the epoch stats."""
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=256,
                                  max_seq_len=512, num_slots=2,
                                  telemetry_every=2, drift_threshold=9.9),
                     profile=profile)
        eng.serve([np.arange(180) % 256], SamplingParams(max_tokens=16))
        assert eng.epoch == 0 and eng.replans == 0
        assert eng.decode_bubble_stats["drift"] is not None


class TestSchedulerSafePoint:
    def test_replan_safe_tracks_prefilling(self):
        chunks = []

        def prefill(toks, slot, q_offset, is_final, prompt_len):
            chunks.append(q_offset)
            return 1 if is_final else None

        def decode(slots, toks, pos):
            return np.ones(len(slots), np.int32)

        b = ContinuousBatcher(num_slots=2, num_blocks=64, max_seq_len=1024,
                              block=128, token_budget=128)
        assert b.replan_safe            # idle
        b.submit(Request(rid=0, prompt=np.arange(500),
                         sampling=SamplingParams(max_tokens=2)))
        b.tick(prefill, decode)
        assert not b.replan_safe        # mid-chunk prefill in flight
        while b.prefilling is not None:
            b.tick(prefill, decode)
        assert b.replan_safe            # chunks done -> safe again
        b.run(prefill, decode)
        assert b.replan_safe

    def test_engine_policy_defers_to_safe_point(self, params, profile):
        """_maybe_replan never swaps while a prefill chunk sequence is in
        flight, even when the cadence is due."""
        eng = Engine(CFG, params,
                     EngineConfig(attention="sparse", budget_per_head=256,
                                  max_seq_len=512, num_slots=2,
                                  prefill_chunk_tokens=128,
                                  replan_every=1),
                     profile=profile)
        b = eng.make_batcher()
        pf, df = eng.step_fns(SamplingParams(max_tokens=4))
        b.submit(Request(rid=0, prompt=np.arange(400) % 256,
                         sampling=SamplingParams(max_tokens=4)))
        b.tick(pf, df)
        assert b.prefilling is not None
        eng._ticks_since_replan = 99
        assert eng._maybe_replan(b) is False
        assert eng.epoch == 0
