"""Sequence-parallel long context (DESIGN.md §2.11): striped KV pools +
2D packed decode must be OUTPUT-IDENTICAL to the 1D head-parallel path.

The load-bearing contract: at any ``seq_shards`` factor, greedy tokens
match the unstriped engine exactly — dense, sparse (packed AND padded
worklists), sliding-window layers, both layer-loop modes, across a
mid-run plan-epoch swap, and across a preempt/swap-to-host/resume cycle.
Striping changes WHERE blocks live and HOW partials combine, never the
math: the per-stripe flash-decoding merge is exact.
"""
import dataclasses

import numpy as np
import jax
import pytest

from repro.core.planner import LayerPlan
from repro.core.sparsity import synthetic_head_curves
from repro.models.transformer import TransformerConfig, init_params
from repro.serving import Engine, EngineConfig, SamplingParams
from repro.serving.scheduler import Request

CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, d_ff=128, vocab_size=256,
                        layer_loop="unroll")
WCFG = dataclasses.replace(CFG, attn_pattern="GL", local_window=128)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def profile():
    return synthetic_head_curves(CFG.num_layers, CFG.num_heads)


def _prompts(lens=(100, 150, 70)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG.vocab_size, size=(n,)) for n in lens]


def _mk(params, profile, *, attention="sparse", seq_shards=1, cfg=CFG,
        **kw):
    base = dict(attention=attention, budget_per_head=256, max_seq_len=256,
                num_slots=4, seq_shards=seq_shards)
    base.update(kw)
    return Engine(cfg, params, EngineConfig(**base),
                  profile=profile if attention == "sparse" else None)


class TestStripedParity:
    @pytest.mark.parametrize("loop", ["unroll", "scan"])
    @pytest.mark.parametrize("attention", ["sparse", "dense"])
    def test_greedy_tokens_identical_at_any_stripe_factor(
            self, params, profile, attention, loop):
        cfg = dataclasses.replace(CFG, layer_loop=loop)
        if loop == "scan":   # scan mode stacks per-layer params
            params = init_params(jax.random.PRNGKey(0), cfg)
        prompts = _prompts()
        sp = SamplingParams(max_tokens=8)  # greedy
        outs = {}
        for S in (1, 2, 4):
            eng = _mk(params, profile, attention=attention, seq_shards=S,
                      cfg=cfg)
            outs[S] = [r.generated for r in eng.serve(prompts, sp)]
        assert outs[2] == outs[1]
        assert outs[4] == outs[1]

    def test_padded_worklist_striped_matches_unstriped(self, params,
                                                       profile):
        """The ``decode_worklist="padded"`` baseline path stripes via
        table masking (no 2D packer) — same outputs."""
        prompts = _prompts()
        sp = SamplingParams(max_tokens=8)
        outs = {}
        for S in (1, 3):
            eng = _mk(params, profile, seq_shards=S,
                      decode_worklist="padded",
                      num_kv_blocks=15)   # rounds up to 15 (S=3 divides)
            outs[S] = [r.generated for r in eng.serve(prompts, sp)]
        assert outs[3] == outs[1]

    def test_windowed_layers_striped_matches_unstriped(self, params):
        """Sliding-window (local) layers mask by POSITION, which striping
        must not disturb — blocks of the window can land on any stripe."""
        wparams = init_params(jax.random.PRNGKey(1), WCFG)
        wprofile = synthetic_head_curves(WCFG.num_layers, WCFG.num_heads)
        prompts = _prompts((200, 90))
        sp = SamplingParams(max_tokens=8)
        outs = {}
        for attention in ("sparse", "dense"):
            for S in (1, 2):
                eng = _mk(wparams, wprofile, attention=attention,
                          seq_shards=S, cfg=WCFG)
                outs[(attention, S)] = [r.generated
                                        for r in eng.serve(prompts, sp)]
        assert outs[("sparse", 2)] == outs[("sparse", 1)]
        assert outs[("dense", 2)] == outs[("dense", 1)]


def _swapped_plan(plan):
    """Pure head MOVE (same per-original-head budgets, kv groups traded
    across shards) — function-preserving, so bitwise-invisible."""
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


def _drive_with_replan(eng, prompts, sp, replan_tick=4):
    """Serve via the batcher, injecting a function-preserving plan-epoch
    swap at a safe point mid-decode."""
    b = eng.make_batcher()
    pf, df = eng.step_fns(sp)
    for i, p in enumerate(prompts):
        b.submit(Request(rid=i, prompt=np.asarray(p, np.int32),
                         sampling=sp))
    done, ticks, replanned = [], 0, False
    while b.busy and ticks < 10_000:
        done.extend(b.tick(pf, df))
        ticks += 1
        if ticks >= replan_tick and not replanned and b.replan_safe:
            assert eng.replan_now(plan=_swapped_plan(eng.plan))
            replanned = True
    assert replanned and not b.busy
    return {r.rid: list(r.generated) for r in done}


class TestStripedReplanAndPreempt:
    def test_mid_run_replan_striped_matches_unstriped(self, params,
                                                      profile):
        """§2.9 epoch swap under striping: the kv-head re-permute gathers
        along the HEAD axis only — stripes never move — and the plan
        memos key on (epoch, stripe signature), so post-swap striped
        outputs still match the unstriped engine through the same swap."""
        prompts = _prompts()
        sp = SamplingParams(max_tokens=10)
        got = {}
        for S in (1, 2):
            eng = _mk(params, profile, seq_shards=S)
            got[S] = _drive_with_replan(eng, prompts, sp)
            assert eng.replans == 1 and eng.epoch == 1
        assert got[2] == got[1]

    def test_preempt_swap_resume_striped_matches_uninterrupted(
            self, params, profile):
        """§2.10 preemption under striping: swap-out returns each block to
        its owning stripe, swap-in maps FRESH blocks (possibly on other
        stripes) — greedy tokens still match an uninterrupted run."""
        prompts = _prompts((100, 90, 80))
        sp = SamplingParams(max_tokens=30)
        mk = lambda S, tight: _mk(
            params, profile, seq_shards=S, block=64, floor=64,
            budget_per_head=256, max_seq_len=512,
            prefill_chunk_tokens=128, preemption=tight,
            num_kv_blocks=6 if tight else None)
        frozen = {r.rid: list(r.generated)
                  for r in mk(1, False).serve(prompts, sp)}
        eng = mk(2, True)
        b = eng.make_batcher()
        pf, df = eng.step_fns(sp)
        for i, p in enumerate(prompts[:2]):
            b.submit(Request(rid=i, prompt=np.asarray(p, np.int32),
                             sampling=sp, priority="batch"))
        done, ticks = [], 0
        while ticks < 6 and b.busy:
            done.extend(b.tick(pf, df))
            ticks += 1
        b.submit(Request(rid=2, prompt=np.asarray(prompts[2], np.int32),
                         sampling=sp, priority="interactive"))
        while b.busy and ticks < 10_000:
            done.extend(b.tick(pf, df))
            ticks += 1
        assert not b.busy
        assert b.stats.preempted >= 1 and b.stats.resumed >= 1
        got = {r.rid: list(r.generated) for r in done}
        assert got == frozen
        assert b.alloc.conserves()
        assert b.alloc.free_blocks == b.alloc.num_blocks


class TestStripedEngineConfig:
    def test_pool_rounds_up_to_stripe_multiple(self, params, profile):
        eng = _mk(params, profile, seq_shards=3, num_kv_blocks=10)
        assert eng.kv.num_blocks == 12
        assert eng.kv.stripes == 3 and eng.kv.stripe_size == 4

    def test_contiguous_layout_is_rejected(self, params, profile):
        """The paged pool is the only KV layout: naming the removed
        contiguous one fails at construction, striped or not."""
        for ss in (1, 2):
            with pytest.raises(ValueError, match="removed"):
                _mk(params, profile, seq_shards=ss, cache_layout="contiguous")

    def test_stats_expose_per_axis_imbalance(self, params, profile):
        eng = _mk(params, profile, seq_shards=2)
        eng.serve(_prompts(), SamplingParams(max_tokens=6))
        bs = eng.decode_bubble_stats
        assert bs["seq_shards"] == 2
        assert bs["merge_collectives"] == CFG.num_layers * bs["ticks"]
        assert bs["mean_head_imbalance"] >= 1.0
        assert bs["mean_stripe_imbalance"] >= 1.0
        last = bs["last_tick"]
        assert {"model_imbalance", "stripe_imbalance"} <= set(last)
