"""Graceful degradation under overload (DESIGN.md §2.10): priority-class
scheduling, decode preemption with KV block swap-to-host, and bitwise
continuation on resume.

The load-bearing contract: a request that is preempted mid-decode, has its
KV blocks swapped to the pinned-host tier, and is later resumed must emit
EXACTLY the greedy tokens of an uninterrupted run — on a fresh pool and on
one whose blocks were recycled out of order, in both prefill modes, and
across a plan-epoch head move that lands between its swap-out and swap-in
(the host copy must be re-arranged exactly once).
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

# a fresh pool, or one whose free list a warm-up serve left out of order
POOLS = [pytest.param(False, id="paged"), pytest.param(True, id="recycled")]

CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, d_ff=128, vocab_size=256,
                        layer_loop="unroll")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def profile():
    return synthetic_head_curves(CFG.num_layers, CFG.num_heads)


def _prompts(lens=(100, 90, 80)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG.vocab_size, size=(n,)) for n in lens]


def _mk(params, profile, prefill_mode, *, preemption=True, tight=True,
        shards=1):
    """Tight geometry forces preemption when the third request arrives;
    ample geometry (tight=False) is the uninterrupted baseline."""
    kw = dict(attention="sparse", budget_per_head=256, block=64, floor=64,
              max_seq_len=512, prefill_mode=prefill_mode,
              prefill_chunk_tokens=128, admission="fifo",
              preemption=preemption, num_model_shards=shards, num_slots=4,
              num_kv_blocks=5 if tight else None)
    return Engine(CFG, params, EngineConfig(**kw), profile=profile)


def _baseline_tokens(params, profile, prefill_mode, prompts, sp, shards=1):
    """Greedy tokens from an uninterrupted run on ample capacity."""
    eng = _mk(params, profile, prefill_mode, preemption=False, tight=False,
              shards=shards)
    done = eng.serve(prompts, sp)
    return {r.rid: list(r.generated) for r in done}


def _swapped_plan(plan):
    """Pure head MOVE (same per-original-head budgets, kv groups traded
    across the 2 shards) — function-preserving, so bitwise-invisible."""
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


def _drive_interrupt(eng, prompts, sp, *, interrupt_tick=6,
                     straddle_plan_fn=None):
    """Two batch-class requests decode until an interactive arrival forces
    preemption; optionally inject a plan-epoch swap in the window between
    the victim's swap-out and its swap-in."""
    b = eng.make_batcher()
    pf, df = eng.step_fns(sp)
    for i, p in enumerate(prompts[:2]):
        b.submit(Request(rid=i, prompt=np.asarray(p, np.int32),
                         sampling=sp, priority="batch"))
    done, ticks = [], 0
    while ticks < interrupt_tick and b.busy:
        done.extend(b.tick(pf, df))
        ticks += 1
    b.submit(Request(rid=2, prompt=np.asarray(prompts[2], np.int32),
                     sampling=sp, priority="interactive"))
    replanned = False
    while b.busy and ticks < 10_000:
        done.extend(b.tick(pf, df))
        ticks += 1
        if (straddle_plan_fn is not None and not replanned
                and eng.swap_stats["swapped_out"]
                and not eng.swap_stats["swapped_in"] and b.replan_safe):
            assert eng.replan_now(plan=straddle_plan_fn(eng.plan))
            replanned = True
    assert not b.busy
    if straddle_plan_fn is not None:
        assert replanned, "plan swap never straddled the host residency"
    return {r.rid: list(r.generated) for r in done}, b


class TestPreemptResumeParity:
    @pytest.mark.parametrize("prefill_mode", ["chunked", "monolithic"])
    @pytest.mark.parametrize("recycled", POOLS)
    def test_bitwise_parity_after_swap_roundtrip(self, params, profile,
                                                 recycled, prefill_mode,
                                                 recycle_pool):
        """Preempt a decoding batch request, swap its KV to host, resume:
        every request's greedy tokens match an uninterrupted run."""
        prompts = _prompts()
        sp = SamplingParams(max_tokens=12)
        frozen = _baseline_tokens(params, profile, prefill_mode, prompts, sp)
        eng = _mk(params, profile, prefill_mode)
        if recycled:
            recycle_pool(eng)
        got, b = _drive_interrupt(eng, prompts, sp)
        assert b.stats.preempted >= 1, "tight pool never forced preemption"
        assert b.stats.resumed >= 1, "swapped victim never resumed"
        st = eng.swap_stats
        assert st["swapped_out"] >= 1 and st["blocks_out"] > 0
        assert st["blocks_in"] == st["blocks_out"]
        assert st["bytes_in"] == st["bytes_out"] > 0
        assert got == frozen, "preempt/resume diverged from frozen run"
        # full teardown: device pool and host tier both restored
        assert b.alloc.conserves()
        assert b.alloc.free_blocks == b.alloc.num_blocks
        assert b.alloc.host_allocated_blocks == 0
        assert b.alloc.swapped_seqs == ()
        assert eng._host_swaps == {}
        # per-class accounting saw the round trip
        pc = b.stats.per_class["batch"]
        assert pc["preempted"] >= 1 and pc["resumed"] >= 1
        assert pc["swapped_out_blocks"] == st["blocks_out"]

    def test_mid_prefill_preemption_discards_and_restarts(self, params,
                                                          profile):
        """A victim caught mid-prefill is DISCARDED (partial chunks are
        cheaper to redo than to swap): its blocks free immediately, no
        host traffic, and the restarted prefill still yields bitwise the
        uninterrupted tokens."""
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, CFG.vocab_size, size=(n,))
                   for n in (300, 80)]
        sp = SamplingParams(max_tokens=16)
        frozen = _baseline_tokens(params, profile, "chunked", prompts, sp)
        eng = _mk(params, profile, "chunked")
        b = eng.make_batcher()
        pf, df = eng.step_fns(sp)
        # 300-token prompt = 3 chunks, reserving the whole 5-block pool
        b.submit(Request(rid=0, prompt=np.asarray(prompts[0], np.int32),
                         sampling=sp, priority="batch"))
        done = list(b.tick(pf, df))     # one chunk in: mid-prefill
        assert b.prefilling is not None
        b.submit(Request(rid=1, prompt=np.asarray(prompts[1], np.int32),
                         sampling=sp, priority="interactive"))
        done.extend(b.run(pf, df))
        got = {r.rid: list(r.generated) for r in done}
        assert b.stats.preempted >= 1
        victim = next(r for r in done if r.rid == 0)
        assert victim.preemptions >= 1
        # discard path, not swap: zero host traffic
        assert eng.swap_stats["swapped_out"] == 0
        assert b.stats.per_class["batch"]["swapped_out_blocks"] == 0
        assert got == frozen, "restarted prefill diverged"
        assert b.alloc.free_blocks == b.alloc.num_blocks

    def test_swap_straddling_plan_epoch_remaps_exactly_once(self, params,
                                                            profile):
        """A head-move replan lands while a victim's KV sits in the host
        tier: swap-in must re-arrange the host copy into the new epoch's
        kv order exactly once, keeping resume bitwise-identical."""
        prompts = _prompts()
        sp = SamplingParams(max_tokens=12)
        frozen = _baseline_tokens(params, profile, "chunked", prompts, sp,
                                  shards=2)
        eng = _mk(params, profile, "chunked", shards=2)
        got, b = _drive_interrupt(eng, prompts, sp,
                                  straddle_plan_fn=_swapped_plan)
        assert eng.epoch == 1 and eng.replans == 1
        assert eng.swap_stats["epoch_remaps"] == 1
        assert b.stats.resumed >= 1
        assert got == frozen, "epoch-straddling swap diverged"

    @pytest.mark.parametrize("recycled", POOLS)
    def test_no_epoch_change_means_no_remap(self, params, profile, recycled,
                                            recycle_pool):
        """Without a replan in the residency window the host copy must be
        scattered back untouched (remap is not a no-op re-gather)."""
        prompts = _prompts()
        sp = SamplingParams(max_tokens=12)
        eng = _mk(params, profile, "chunked")
        if recycled:
            recycle_pool(eng)
        _, b = _drive_interrupt(eng, prompts, sp)
        assert b.stats.resumed >= 1
        assert eng.swap_stats["epoch_remaps"] == 0


class TestSchedulerOverloadPaths:
    def test_slo_admission_defers_lower_class(self, params, profile):
        """Under slo admission with measured EMAs, lower-class work that
        the cost model predicts would break a higher class's ITL target
        is deferred, not rejected — it completes once pressure clears."""
        eng = Engine(CFG, params, EngineConfig(
            attention="sparse", budget_per_head=256, block=64, floor=64,
            max_seq_len=512, num_slots=4, prefill_mode="chunked",
            prefill_chunk_tokens=128, admission="slo", preemption=True),
            profile=profile)
        prompts = _prompts((100, 90, 80, 70))
        done = eng.serve(prompts, SamplingParams(max_tokens=12),
                         priorities=["interactive", "batch", "batch",
                                     "interactive"])
        assert all(not r.rejected for r in done)
        assert all(len(r.generated) == 12 for r in done)
        assert eng._batcher.stats.completed == 4

    def test_host_tier_capacity_bounds_swap(self, params, profile):
        """host_swap_blocks=0 disables the swap tier: preemption of a
        decoding victim is impossible, so the interactive arrival must
        wait (never deadlock, never corrupt)."""
        eng = Engine(CFG, params, EngineConfig(
            attention="sparse", budget_per_head=256, block=64, floor=64,
            max_seq_len=512, num_slots=4, num_kv_blocks=5,
            prefill_mode="chunked", prefill_chunk_tokens=128,
            admission="fifo", preemption=True, host_swap_blocks=0),
            profile=profile)
        prompts = _prompts()
        sp = SamplingParams(max_tokens=12)
        got, b = _drive_interrupt(eng, prompts, sp)
        assert eng.swap_stats["swapped_out"] == 0
        assert b.stats.completed == 3
        frozen = _baseline_tokens(params, profile, "chunked", prompts, sp)
        assert got == frozen
