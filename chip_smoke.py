#!/usr/bin/env python3
"""Smoke run of the S-HPLB serving engine on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: head-parallel island only

One chip, SmolLM-135M at its published width (30 layers, d_model 576,
9 heads / 3 KV heads, head_dim 64, vocab 49152), random weights from a
seed.  Phases, in order; any failure exits non-zero:

1. device gate: ``jax.devices()[0].platform`` must be ``tpu``.  Nothing is
   ever served on the CPU.
2. kernel check: the Pallas paged decode kernel (the engine's default
   executor) against the float32 jnp reference, both on the chip, at
   SmolLM widths over a 4096-block pool, for bf16 and int8 pools, in its
   per-layer form and in the whole-pool form the decode step takes.
3. serve, sparse: ``Engine`` + ``ContinuousBatcher`` (paged pool, chunked
   prefill, packed decode) on 16 seeded requests of 1k-6k prompt tokens
   and 32 new tokens each; every request must complete, and one decode
   step's lowering must hold the Pallas kernel (``tpu_custom_call``).
4. serve, dense: the same traffic with dense attention.
5. serve, int8: the sparse engine over an int8 KV pool.

``--chips 4`` runs only the head-parallel packed decode island
(``serving.sharded_attention.hplb_decode_attention_packed``) on a
``(data=1, model=4)`` mesh at Yi-6B attention widths, one KV head per
chip, against ``ops.flash_decode_packed`` on one device.

Earlier lines report what ran (device, set-up seconds, compile seconds
per jitted step by name, wall time per phase, tokens, requests,
decode-grid statistics); they are a record of the run, not a speed
measurement.  The last line of standard output is a JSON object naming
the device.  The persistent compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

BLOCK = 128


class SmokeFailure(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def select_blocks(kv_budgets, pos: int, block: int = BLOCK):
    """Per-kv-head selection as the engine makes it: the sink block plus the
    most recent blocks, ``ceil(budget / block)`` of them, clipped to what
    the sequence holds.  Returns ``[Hkv, nb]`` int32, -1 padded."""
    import numpy as np
    from repro.core.worklist import blocks_for_budget
    nkv = -(-(int(pos) + 1) // block)
    nb = np.minimum(blocks_for_budget(kv_budgets, block), nkv)
    ids = np.full((len(kv_budgets), int(nb.max())), -1, np.int32)
    for h, n in enumerate(nb):
        recent = range(max(0, nkv - max(1, int(n) - 1)), nkv)
        sel = sorted(set(([0] if n > 1 else []) + list(recent)))[:int(n)]
        ids[h, :len(sel)] = sel
    return ids


def stack_ids(per_slot):
    """Pad per-slot ``[Hkv, nb_i]`` selections to one ``[B, Hkv, nb]``."""
    import numpy as np
    nb = max(x.shape[1] for x in per_slot)
    out = np.full((len(per_slot), per_slot[0].shape[0], nb), -1, np.int32)
    for b, x in enumerate(per_slot):
        out[b, :, :x.shape[1]] = x
    return out


def decode_err_bound(v_abs_max: float) -> float:
    """Bound on |kernel - reference| for one decode attention output.

    Both sides read the same tiles (bf16 values, or int8/fp8 codes with
    the same f32 scales), and every such operand is exact in bf16, so the
    logits agree to f32 rounding.  The one coarse term is the kernel's
    p.V pass on the MXU, which may round the softmax weights to bf16
    (relative error 2^-9 per weight); the output is a convex combination
    of V rows, so that moves it by at most 2^-9 max|V|.  A bf16 output
    adds at most one bf16 ulp of |out| <= max|V| (2^-8 relative).  The
    bound allows 2^-8 max|V|, which covers either term alone."""
    return 2.0 ** -8 * v_abs_max


def lowered_has_kernel(jitted, *args) -> int:
    """How many Pallas TPU kernels the lowering of ``jitted(*args)`` holds
    (0 means the jnp reference path was traced instead)."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


def require_kernel(n_kernels: int, what: str) -> None:
    """On the chip the kernel must be there; off it (the CPU tests of
    these phases) the jnp executors run by design."""
    import jax
    if jax.default_backend() == "tpu":
        check(n_kernels >= 1, f"{what} lowered without the Pallas kernel "
              "(jnp reference path)")


# ---------------------------------------------------------------------------
# phase 2: kernel check
# ---------------------------------------------------------------------------

def kernel_check(kv_dtype: str, *, cfg, num_blocks: int, slots: int,
                 max_seq: int, seed: int = 0):
    """Packed paged decode (Pallas on TPU) vs the f32 paged reference."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import quant
    from repro.core.worklist import pack_decode_items
    from repro.kernels import ops
    from repro.kernels.flash_decode import flash_decode_paged_reference

    H, hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    T = max_seq // BLOCK
    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (slots, H, 1, D), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (num_blocks, hkv, BLOCK, D), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (num_blocks, hkv, BLOCK, D), jnp.bfloat16)
    ks = vs = None
    if quant.is_quantized(kv_dtype):
        k_pool, ks = quant.quantize_pool_blocks(k_pool, kv_dtype)
        v_pool, vs = quant.quantize_pool_blocks(v_pool, kv_dtype)
        v_abs = float(jnp.max(jnp.abs(quant.dequantize_tiles(v_pool, vs))))
    else:
        v_abs = float(jnp.max(jnp.abs(v_pool.astype(jnp.float32))))
    # every slot maps its logical blocks to scattered pool blocks
    table = rng.permutation(num_blocks)[:slots * T].reshape(slots, T)
    table = table.astype(np.int32)
    pos = rng.integers(max_seq // 8, max_seq, size=slots).astype(np.int32)
    # uneven per-kv-head budgets, max-min style: 2, 5 and 12 blocks ...
    kv_budgets = np.array([2, 5, 12][:hkv] + [4] * max(0, hkv - 3)) * BLOCK
    ids = stack_ids([select_blocks(kv_budgets, p) for p in pos])
    wl = pack_decode_items(ids, num_shards=1, block=BLOCK)
    items = jnp.asarray(wl.items[0])

    def run_kernel(q, k_pool, v_pool, items, table, pos, ks, vs,
                   layer=None):
        return ops.flash_decode_packed_paged(
            q, k_pool, v_pool, items, table, pos, block_kv=BLOCK,
            partials=True, k_scales=ks, v_scales=vs, layer=layer)[0]

    rest = (items, jnp.asarray(table), jnp.asarray(pos), ks, vs)
    # the decode step's form: layer 1 of a whole two-layer pool, whose
    # layer 0 holds V and K the other way round
    whole = (q, jnp.stack([jnp.stack([v_pool, k_pool]),
                           jnp.stack([k_pool, v_pool])]), None, *rest,
             jnp.int32(1))
    f = jax.jit(run_kernel)
    outs = []
    for form, args in (("per-layer", (q, k_pool, v_pool, *rest)),
                       ("whole-pool", whole)):
        n_kern = lowered_has_kernel(f, *args)
        require_kernel(n_kern, f"kernel check {kv_dtype}: packed paged "
                       f"decode, {form} form")
        outs.append(f(*args))
    with jax.default_matmul_precision("highest"):
        ref = flash_decode_paged_reference(
            q.reshape(slots, hkv, H // hkv, D), k_pool, v_pool,
            jnp.asarray(ids), jnp.asarray(table), jnp.asarray(pos),
            block_kv=BLOCK, k_scales=ks, v_scales=vs)[0]
    ref = np.asarray(ref, np.float32).reshape(slots, H, D)
    bound = decode_err_bound(v_abs)
    errs = []
    for form, out in zip(("per-layer", "whole-pool"), outs):
        out = np.asarray(out, np.float32).reshape(slots, H, D)
        check(bool(np.isfinite(out).all()), f"kernel check {kv_dtype}, "
              f"{form} form: non-finite output")
        errs.append(float(np.abs(out - ref).max()))
        check(errs[-1] <= bound, f"kernel check {kv_dtype}, {form} form: "
              f"max abs err {errs[-1]!r} > bound {bound!r}")
    return {"kv_dtype": kv_dtype, "err": max(errs), "errs": errs,
            "bound": bound, "items": int(wl.total_real_items),
            "kernels": n_kern}


# ---------------------------------------------------------------------------
# phases 3-5: serving
# ---------------------------------------------------------------------------

def make_prompts(cfg, n: int, lo: int, hi: int, seed: int = 0):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=int(rng.integers(lo, hi + 1)),
                         dtype=np.int32) for _ in range(n)]


def decode_step_kernels(eng) -> int:
    """Lower one packed decode step exactly as the engine compiled it and
    count the Pallas kernels in it."""
    import jax.numpy as jnp
    from repro.kernels.sparse_decode import DEC_FIELDS
    check(bool(eng._decode_packed_jit), "no packed decode step was compiled")
    flat_len = next(iter(eng._decode_packed_jit))
    ns = eng.ecfg.num_slots
    zeros = jnp.zeros((ns,), jnp.int32)
    table = jnp.full((ns, eng.kv.table_width), -1, jnp.int32)
    items = jnp.zeros((eng.cfg.num_layers, flat_len, DEC_FIELDS), jnp.int32)
    return lowered_has_kernel(eng._decode_packed_fn(flat_len), eng.params,
                              eng.cache, zeros, zeros, table, items,
                              jnp.zeros((ns,), bool))


def serve_phase(name: str, cfg, params, prompts, *, max_tokens: int,
                engine_kw: dict, meter: CompileMeter | None = None):
    """Serve ``prompts`` through ``Engine.serve`` and check every request
    completed; returns what the run counted."""
    from repro.core.planner import plan_summary
    from repro.core.sparsity import synthetic_head_curves
    from repro.serving import Engine, EngineConfig, SamplingParams

    snap = meter.snapshot() if meter else None
    t0 = time.perf_counter()
    sparse = engine_kw.get("attention", "sparse") == "sparse"
    profile = (synthetic_head_curves(cfg.num_layers, cfg.num_heads)
               if sparse else None)
    eng = Engine(cfg, params, EngineConfig(**engine_kw), profile=profile)
    t_setup = time.perf_counter() - t0
    t1 = time.perf_counter()
    done = eng.serve(prompts, SamplingParams(max_tokens=max_tokens))
    wall = time.perf_counter() - t1
    check(len(done) == len(prompts),
          f"{name}: {len(done)} results for {len(prompts)} requests")
    rejected = [r.rid for r in done if r.rejected]
    failed = [(r.rid, r.fail_reason) for r in done if r.failed]
    check(not rejected, f"{name}: rejected requests {rejected}")
    check(not failed, f"{name}: failed requests {failed}")
    empty = [r.rid for r in done if not r.generated]
    check(not empty, f"{name}: requests with no tokens {empty}")
    bs = eng.decode_bubble_stats
    res = {
        "phase": name,
        "setup_s": t_setup,
        "wall_s": wall,
        "requests": len(done),
        "completed": sum(1 for r in done if r.done and not r.failed),
        "prompt_tokens": int(sum(len(p) for p in prompts)),
        "generated_tokens": int(sum(len(r.generated) for r in done)),
        "decode_ticks": bs["ticks"],
        "padding_waste": bs["padding_waste"],
        "grid_vs_padded": bs["grid_vs_padded"],
        "grid_saving": (plan_summary(eng.plan)["padded_grid_saving"]
                        if eng.plan is not None else None),
        "compile": meter.since(snap) if meter else None,
    }
    if sparse and engine_kw.get("decode_worklist", "packed") == "packed":
        res["decode_kernels"] = decode_step_kernels(eng)
        require_kernel(res["decode_kernels"], f"{name}: packed decode step")
    check(res["completed"] == len(prompts),
          f"{name}: {res['completed']}/{len(prompts)} completed")
    del eng
    return res


def report_serve(res: dict) -> None:
    say(f"[{res['phase']}] {res['completed']}/{res['requests']} requests "
        f"completed, {res['generated_tokens']} tokens generated from "
        f"{res['prompt_tokens']} prompt tokens; engine set-up "
        f"{res['setup_s']:.2f} s, serve wall {res['wall_s']:.2f} s, "
        f"{res['compile']}")
    gs = res["grid_saving"]
    say(f"[{res['phase']}] decode ticks {res['decode_ticks']}, "
        f"padding_waste {res['padding_waste']:.4f}, grid_vs_padded "
        f"{res['grid_vs_padded']:.4f}, plan grid saving "
        f"{'n/a' if gs is None else f'{100 * gs:.1f}%'}"
        + (f", Pallas kernels in decode step {res['decode_kernels']}"
           if "decode_kernels" in res else ""))


SERVE_KW = dict(attention="sparse", budget_per_head=512, max_seq_len=8192,
                num_slots=8, cache_layout="paged", prefill_mode="chunked",
                decode_worklist="packed")


def one_chip(meter: CompileMeter) -> None:
    import jax
    from repro.configs.smollm_135m import FULL as cfg
    from repro.models.transformer import init_params

    snap = meter.snapshot()
    t0 = time.perf_counter()
    for kvd in ("bf16", "int8"):
        r = kernel_check(kvd, cfg=cfg, num_blocks=4096, slots=8,
                         max_seq=SERVE_KW["max_seq_len"])
        say(f"[kernel {kvd}] packed paged decode, {r['items']} tiles over a "
            f"4096-block pool: max abs err per-layer / whole-pool "
            f"{r['errs'][0]!r} / {r['errs'][1]!r} <= bound {r['bound']!r} "
            f"(Pallas kernels in lowering: {r['kernels']})")
    say(f"[kernel] wall {time.perf_counter() - t0:.2f} s, "
        f"{meter.since(snap)}")

    snap = meter.snapshot()
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(jax.random.PRNGKey(0), cfg))
    say(f"[setup] {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads, "
        f"head_dim {cfg.head_dim_}, vocab {cfg.vocab_size}; params "
        f"{time.perf_counter() - t0:.2f} s, {meter.since(snap)}")
    prompts = make_prompts(cfg, 16, 1024, 6144, seed=0)
    for name, kw in (("serve sparse", {}),
                     ("serve dense", {"attention": "dense"}),
                     ("serve int8", {"kv_dtype": "int8"})):
        report_serve(serve_phase(name, cfg, params, prompts, max_tokens=32,
                                 engine_kw={**SERVE_KW, **kw}, meter=meter))


# ---------------------------------------------------------------------------
# --chips 4: the head-parallel packed decode island
# ---------------------------------------------------------------------------

def hplb_island_check(*, cfg, model: int, slots: int, max_seq: int,
                      seed: int = 0):
    """``hplb_decode_attention_packed`` on a (data=1, model) mesh vs
    ``ops.flash_decode_packed`` on one device, same inputs."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.planner import make_plan
    from repro.core.sparsity import synthetic_head_curves
    from repro.core.worklist import pack_decode_items
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.serving.sharded_attention import hplb_decode_attention_packed

    H, hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    check(hkv % model == 0, f"{hkv} kv heads do not split over {model}")
    mesh = make_host_mesh(model=model, data=1)
    # max-min budgets for one layer, planned for `model` devices
    plan = make_plan(synthetic_head_curves(1, H), num_devices=model,
                     num_kv_heads=hkv, seq_len=max_seq,
                     total_budget_per_head=512, block=BLOCK, floor=BLOCK,
                     allocator="maxmin")
    kv_budgets = plan.layers[0].budgets.reshape(hkv, H // hkv).max(axis=1)
    rng = np.random.default_rng(seed)
    pos = rng.integers(max_seq // 8, max_seq, size=slots).astype(np.int32)
    ids = stack_ids([select_blocks(kv_budgets, p) for p in pos])
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    dev0 = jax.devices()[0]
    q = jax.device_put(jax.random.normal(kq, (slots, H, 1, D), jnp.bfloat16),
                       dev0)
    kc = jax.device_put(jax.random.normal(kk, (slots, hkv, max_seq, D),
                                          jnp.bfloat16), dev0)
    vc = jax.device_put(jax.random.normal(kv, (slots, hkv, max_seq, D),
                                          jnp.bfloat16), dev0)
    v_abs = float(jnp.max(jnp.abs(vc.astype(jnp.float32))))

    # one device: one freely packed list with GLOBAL kv-head ids
    flat = pack_decode_items(ids, num_shards=1, block=BLOCK)
    ref = jax.jit(lambda *a: ops.flash_decode_packed(*a, block_kv=BLOCK))(
        q, kc, vc, jnp.asarray(flat.items[0]), jnp.asarray(pos))
    ref = np.asarray(ref, np.float32)

    # head-parallel: each kv head's runs pinned to the shard holding it,
    # kv-head ids shard-local
    per = hkv // model
    wl = pack_decode_items(ids, num_shards=model, block=BLOCK,
                           shard_of_kvhead=np.arange(hkv) // per,
                           kvhead_local=True)
    heads = NamedSharding(mesh, P(None, "model", None, None))
    q_s = jax.device_put(q, heads)
    kc_s = jax.device_put(kc, heads)
    vc_s = jax.device_put(vc, heads)
    items_s = jax.device_put(jnp.asarray(wl.items),
                             NamedSharding(mesh, P("model", None, None)))
    pos_s = jax.device_put(jnp.asarray(pos), NamedSharding(mesh, P()))
    shard_devs = {s.device for s in kc_s.addressable_shards}
    check(len(shard_devs) == model,
          f"cache shards on {len(shard_devs)} devices, expected {model}")
    check(all(s.data.shape[1] == per for s in kc_s.addressable_shards),
          "cache shard does not hold its kv heads")
    attend = hplb_decode_attention_packed(mesh, block_kv=BLOCK)
    f = jax.jit(lambda *a: attend(*a))
    n_kern = lowered_has_kernel(f, q_s, kc_s, vc_s, items_s, pos_s)
    require_kernel(n_kern, "head-parallel island")
    out = f(q_s, kc_s, vc_s, items_s, pos_s)
    out_devs = {s.device for s in out.addressable_shards}
    out = np.asarray(out, np.float32)
    check(bool(np.isfinite(out).all()), "island output is not finite")
    err = float(np.abs(out - ref).max())
    bound = decode_err_bound(v_abs)
    check(err <= bound, f"island max abs err {err!r} > bound {bound!r}")
    return {"err": err, "bound": bound, "devices": sorted(
                f"{d.platform}:{d.id}" for d in shard_devs),
            "out_devices": len(out_devs), "shard_lengths":
            wl.lengths.tolist(), "kernels": n_kern,
            "kv_budgets": kv_budgets.tolist()}


def four_chips(meter: CompileMeter, model: int) -> None:
    from repro.configs.yi_6b import FULL as cfg
    snap = meter.snapshot()
    t0 = time.perf_counter()
    r = hplb_island_check(cfg=cfg, model=model, slots=8, max_seq=8192)
    say(f"[hplb island] {cfg.name} attention widths ({cfg.num_heads} heads, "
        f"{cfg.num_kv_heads} kv heads, head_dim {cfg.head_dim_}), "
        f"mesh (data=1, model={model}); cache shards on {r['devices']}, "
        f"output on {r['out_devices']} devices; kv budgets "
        f"{r['kv_budgets']}, items per shard {r['shard_lengths']}")
    say(f"[hplb island] vs ops.flash_decode_packed on one device: max abs "
        f"err {r['err']!r} <= bound {r['bound']!r} (Pallas kernels in "
        f"lowering: {r['kernels']}); wall {time.perf_counter() - t0:.2f} s, "
        f"{meter.since(snap)}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the head-parallel decode island on a "
                         "(data=1, model=4) mesh")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r} "
              f"({len(devices)} device(s)); nothing was run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.utils.compile_cache import CompileMeter, enable_compile_cache
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    kind = devices[0].device_kind
    say(f"[device] {platform} {kind!r} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(meter, model=4)
        else:
            one_chip(meter)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"[total] wall {time.perf_counter() - t0:.2f} s, "
        f"compile {meter.secs:.2f} s (cache hits {meter.hits}, misses "
        f"{meter.misses})")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
