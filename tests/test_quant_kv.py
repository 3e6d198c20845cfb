"""Quantized KV-cache pool (DESIGN.md §2.12): round-trip error bounds,
strict bf16 opt-in, and greedy-token parity at int8 across every serving
axis — dense/sparse/windowed x fresh/recycled pool x packed/padded — plus
preempt/swap/resume and a plan-epoch head move straddling host residency
(the scales must travel with their blocks through every gather).

np.random twins of the hypothesis round-trip properties live here so the
bounds are always exercised; the adversarial hypothesis versions are in
tests/test_quant_kv_props.py (skipped where hypothesis is absent).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import quant
from repro.core.planner import LayerPlan
from repro.core.sparsity import synthetic_head_curves
from repro.core.worklist import (
    DEC_FIELDS,
    extend_packed_items,
    pack_decode_items,
    pow2_bucket,
)
from repro.kernels import ops
from repro.models.transformer import TransformerConfig, init_params
from repro.serving import Engine, EngineConfig, SamplingParams
from repro.serving.scheduler import Request

CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, d_ff=128, vocab_size=256,
                        layer_loop="unroll", block_kv=64)
WCFG = dataclasses.replace(CFG, attn_pattern="GL", local_window=160)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def wparams():
    return init_params(jax.random.PRNGKey(0), WCFG)


@pytest.fixture(scope="module")
def profile():
    return synthetic_head_curves(CFG.num_layers, CFG.num_heads)


# ---------------------------------------------------------------------------
# round-trip error bounds (np.random twins of the hypothesis properties)
# ---------------------------------------------------------------------------

class TestRoundTrip:
    @pytest.mark.parametrize("kvd", ["int8", "fp8"])
    def test_error_within_bound_per_tile(self, kvd):
        """|dequant(quant(x)) - x| <= bound * tile_absmax, elementwise,
        across magnitudes spanning subnormal-ish to huge."""
        rng = np.random.default_rng(0)
        bound = quant.roundtrip_error_bound(kvd)
        for mag in (1e-6, 1e-2, 1.0, 37.0, 1e4):
            x = (rng.standard_normal((5, 3, 16, 8)) * mag).astype(np.float32)
            codes, scales = quant.quantize_tiles(jnp.asarray(x), kvd)
            back = np.asarray(quant.dequantize_tiles(codes, scales))
            amax = np.abs(x).max(axis=(-2, -1), keepdims=True)
            assert np.all(np.abs(back - x) <= bound * amax + 1e-12), \
                f"{kvd} round-trip exceeded bound at magnitude {mag}"

    @pytest.mark.parametrize("kvd", ["int8", "fp8"])
    def test_all_zero_tile_is_exact_with_unit_scale(self, kvd):
        codes, scales = quant.quantize_tiles(jnp.zeros((2, 8, 4)), kvd)
        assert np.all(np.asarray(scales) == 1.0)
        assert np.all(np.asarray(quant.dequantize_tiles(codes, scales)) == 0)

    @pytest.mark.parametrize("kvd", ["int8", "fp8"])
    def test_insert_token_requant_invariants(self, kvd):
        """Scale grows monotonically within a block; offs == 0 resets it
        to the token's own range and zeroes inherited garbage; inserting
        a token SMALLER than the current range is exact on old codes."""
        rng = np.random.default_rng(1)
        B, hkv, blk, dh = 2, 2, 8, 4
        x = rng.standard_normal((B, hkv, blk, dh)).astype(np.float32)
        codes, scale = quant.quantize_tiles(jnp.asarray(x), kvd)
        small = jnp.asarray(
            0.01 * rng.standard_normal((B, hkv, dh)).astype(np.float32))
        offs = jnp.array([3, 5], jnp.int32)
        c2, s2 = quant.insert_token_requant(codes, scale, small, offs, kvd)
        # small token never grows the scale -> old codes untouched
        assert np.array_equal(np.asarray(s2), np.asarray(scale))
        keep = np.ones(blk, bool)
        for b, o in enumerate([3, 5]):
            row = np.asarray(c2[b], np.float32)
            old = np.asarray(codes[b], np.float32)
            m = keep.copy()
            m[o] = False
            assert np.array_equal(row[:, m], old[:, m])
        # a big token grows the scale for its (batch, head) only
        big = jnp.asarray(
            100.0 * np.abs(x).max() * np.ones((B, hkv, dh), np.float32))
        _, s3 = quant.insert_token_requant(codes, scale, big, offs, kvd)
        assert np.all(np.asarray(s3) > np.asarray(scale))
        # offs == 0 resets: scale is the token's own, not max(old, token)
        zo = jnp.zeros((B,), jnp.int32)
        c4, s4 = quant.insert_token_requant(codes, scale, small, zo, kvd)
        tmax = np.abs(np.asarray(small)).max(-1)
        np.testing.assert_allclose(np.asarray(s4),
                                   tmax / quant.QMAX[kvd], rtol=1e-6)
        # every non-token row of a fresh block is zeroed
        assert np.all(np.asarray(c4, np.float32)[:, :, 1:] == 0)


# ---------------------------------------------------------------------------
# kernel-level dequant fusion vs an f32 oracle
# ---------------------------------------------------------------------------

class TestKernelDequant:
    @pytest.mark.parametrize("kvd", ["int8", "fp8"])
    def test_packed_decode_matches_dequantized_oracle(self, kvd):
        """flash_decode_packed fed codes + scales == the SAME kernel fed
        the explicitly dequantized pool (post-dot rescale is the linear
        identity (q.k)*s == q.(k*s), up to f32 rounding)."""
        B, Hkv, G, D, blk, smax = 2, 2, 2, 32, 64, 256
        H, nkv = Hkv * G, smax // blk
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (B, H, 1, D), jnp.float32)
        kc = jax.random.normal(ks[1], (B, Hkv, smax, D), jnp.float32)
        vc = jax.random.normal(ks[2], (B, Hkv, smax, D), jnp.float32)
        kq, ksc = quant.quantize_tiles(kc.reshape(B, Hkv, nkv, blk, D), kvd)
        vq, vsc = quant.quantize_tiles(vc.reshape(B, Hkv, nkv, blk, D), kvd)
        kd = quant.dequantize_tiles(kq, ksc).reshape(B, Hkv, smax, D)
        vd = quant.dequantize_tiles(vq, vsc).reshape(B, Hkv, smax, D)
        kq, vq = kq.reshape(B, Hkv, smax, D), vq.reshape(B, Hkv, smax, D)
        ids = np.tile(np.arange(nkv, dtype=np.int32), (B, Hkv, 1))
        pos = np.array([smax - 1, smax // 2 + 3], np.int32)
        wl = pack_decode_items(ids, num_shards=1, block=blk)
        items = jnp.asarray(extend_packed_items(
            wl.items, pow2_bucket(wl.padded_length)).reshape(-1, DEC_FIELDS))
        o_fused = ops.flash_decode_packed(q, kq, vq, items,
                                          jnp.asarray(pos), block_kv=blk,
                                          k_scales=ksc, v_scales=vsc)
        o_oracle = ops.flash_decode_packed(q, kd, vd, items,
                                           jnp.asarray(pos), block_kv=blk)
        np.testing.assert_allclose(np.asarray(o_fused, np.float32),
                                   np.asarray(o_oracle, np.float32),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# bf16 is strictly opt-in
# ---------------------------------------------------------------------------

class TestOptIn:
    def test_bf16_engine_is_structurally_unquantized(self, params, profile):
        """kv_dtype="bf16" must leave every pre-§2.12 invariant intact:
        no scales tensor exists anywhere and the donated cache is the bare
        pool (not a (codes, scales) pair)."""
        eng = Engine(CFG, params, EngineConfig(
            attention="sparse", budget_per_head=128, block=64, floor=64,
            max_seq_len=512, num_slots=2, kv_dtype="bf16"), profile=profile)
        assert eng.quantized is False
        assert eng.kv.scales is None
        assert not isinstance(eng.cache, tuple)

    def test_bf16_flag_tokens_bitwise_match_default(self, params, profile):
        """Passing kv_dtype="bf16" explicitly is bitwise the default
        engine — the §2.12 threading is a no-op unless quantization is
        opted into."""
        prompts = [np.random.default_rng(i).integers(0, 256, size=(n,))
                   for i, n in enumerate((40, 130, 70))]
        sp = SamplingParams(max_tokens=8)  # greedy
        mk = lambda **kw: Engine(CFG, params, EngineConfig(
            attention="sparse", budget_per_head=128, block=64, floor=64,
            max_seq_len=512, num_slots=4, **kw),
            profile=profile)
        a = [r.generated for r in mk().serve(prompts, sp)]
        b = [r.generated for r in mk(kv_dtype="bf16").serve(prompts, sp)]
        assert a == b


# ---------------------------------------------------------------------------
# int8 greedy parity across the full serving matrix
# ---------------------------------------------------------------------------

class TestInt8ParityMatrix:
    @pytest.mark.parametrize("policy", ["dense", "sparse", "windowed"])
    def test_layout_and_worklist_invariance(self, params, wparams, profile,
                                            policy, recycle_pool):
        """At kv_dtype="int8" all four (pool x worklist) engines emit
        IDENTICAL greedy tokens for a given policy: quantization error is
        a property of the stored blocks, not of where they lie or of the
        path that reads them.  One pool is fresh; the other holds exactly
        the prompts' blocks, recycled out of order by a warm-up serve."""
        cfg = WCFG if policy == "windowed" else CFG
        p = wparams if policy == "windowed" else params
        attention = "dense" if policy == "dense" else "sparse"
        prompts = [np.random.default_rng(i).integers(0, 256, size=(n,))
                   for i, n in enumerate((40, 300, 130, 70))]
        sp = SamplingParams(max_tokens=8)  # greedy
        outs = {}
        for pool in ("fresh", "recycled"):
            for wmode in ("packed", "padded"):
                eng = Engine(cfg, p, EngineConfig(
                    attention=attention, budget_per_head=128,
                    block=64, floor=64, max_seq_len=512, num_slots=4,
                    # the prompts' 1 + 5 + 3 + 2 blocks
                    num_kv_blocks=11 if pool == "recycled" else None,
                    decode_worklist=wmode, prefill_mode="monolithic",
                    kv_dtype="int8"),
                    profile=profile if attention == "sparse" else None)
                if pool == "recycled":
                    recycle_pool(eng)
                outs[(pool, wmode)] = [r.generated
                                       for r in eng.serve(prompts, sp)]
        first = outs[("fresh", "packed")]
        assert all(len(t) == 8 for t in first)
        for key, got in outs.items():
            assert got == first, f"{policy}/{key} diverged at int8"


# ---------------------------------------------------------------------------
# int8 preempt / swap / resume, and a replan straddling host residency
# ---------------------------------------------------------------------------

def _prompts(lens=(100, 90, 80)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG.vocab_size, size=(n,)) for n in lens]


def _mk(params, profile, kv_dtype, *, preemption=True, tight=True,
        shards=1):
    kw = dict(attention="sparse", budget_per_head=256, block=64, floor=64,
              max_seq_len=512, prefill_mode="monolithic", admission="fifo",
              preemption=preemption, num_model_shards=shards,
              kv_dtype=kv_dtype, num_slots=4,
              num_kv_blocks=5 if tight else None)
    return Engine(CFG, params, EngineConfig(**kw), profile=profile)


def _baseline_tokens(params, profile, kv_dtype, prompts, sp, *, shards=1):
    eng = _mk(params, profile, kv_dtype, preemption=False, tight=False,
              shards=shards)
    done = eng.serve(prompts, sp)
    return {r.rid: list(r.generated) for r in done}


def _swapped_plan(plan):
    """Pure head MOVE (per-original-head budgets unchanged, kv groups
    traded across the 2 shards) — function-preserving."""
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


class TestInt8PreemptResume:
    @pytest.mark.parametrize("kv_dtype", [
        pytest.param("int8", id="paged"), pytest.param("fp8", id="paged-fp8")])
    def test_swap_roundtrip_parity_at_int8(self, params, profile, kv_dtype):
        """Preempt a decoding int8 (or fp8) request, swap its CODES +
        SCALES to host, resume: greedy tokens match an uninterrupted run at
        the same dtype."""
        prompts = _prompts()
        sp = SamplingParams(max_tokens=12)
        frozen = _baseline_tokens(params, profile, kv_dtype, prompts, sp)
        eng = _mk(params, profile, kv_dtype)
        got, b = _drive_interrupt(eng, prompts, sp)
        assert b.stats.preempted >= 1 and b.stats.resumed >= 1
        st = eng.swap_stats
        assert st["swapped_out"] >= 1
        assert st["bytes_in"] == st["bytes_out"] > 0
        assert got == frozen, f"{kv_dtype} preempt/resume diverged"
        assert b.alloc.conserves()
        assert eng._host_swaps == {}

    def test_quantized_swap_moves_fewer_bytes(self, params, profile):
        """The host tier moves codes + scales, not a dequantized copy:
        bytes per swapped block at int8 land well under bf16's."""
        prompts = _prompts()
        sp = SamplingParams(max_tokens=12)
        per_block = {}
        for kvd in ("bf16", "int8"):
            eng = _mk(params, profile, kvd)
            _, b = _drive_interrupt(eng, prompts, sp)
            st = eng.swap_stats
            assert st["blocks_out"] > 0
            per_block[kvd] = st["bytes_out"] / st["blocks_out"]
        # int8 payload is half of bf16; scales add 4 / (64 * 64) per elem
        assert per_block["int8"] < 0.6 * per_block["bf16"]

    def test_replan_straddling_residency_regathers_scales_once(
            self, params, profile):
        """A head-move replan lands while an int8 victim's KV sits in the
        host tier: swap-in re-arranges codes AND scales into the new
        epoch's kv order exactly once, keeping resume tokens identical to
        the uninterrupted int8 run."""
        prompts = _prompts()
        sp = SamplingParams(max_tokens=12)
        frozen = _baseline_tokens(params, profile, "int8", prompts, sp,
                                  shards=2)
        eng = _mk(params, profile, "int8", shards=2)
        got, b = _drive_interrupt(eng, prompts, sp,
                                  straddle_plan_fn=_swapped_plan)
        assert eng.epoch == 1 and eng.replans == 1
        assert eng.swap_stats["epoch_remaps"] == 1
        assert b.stats.resumed >= 1
        assert got == frozen, "epoch-straddling int8 swap diverged"
