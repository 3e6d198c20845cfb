"""Production serving launcher (single-host path; production mesh via the
dry-run on this container).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        [--attention sparse|dense] [--budget 512] [--requests 8]
"""
import argparse
import time

import numpy as np
import jax

from repro.configs import ARCHS
from repro.core.sparsity import synthetic_head_curves
from repro.launch.steps import _init_fn_for
from repro.serving import Engine, EngineConfig, SamplingParams
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.logging import get_logger

log = get_logger("serve")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--attention", default="sparse",
                    choices=["sparse", "dense"])
    ap.add_argument("--budget", type=int, default=256)
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=16)
    # adaptive replanning (plan epochs, DESIGN.md §2.9)
    ap.add_argument("--telemetry-every", type=int, default=0,
                    help="probe realized per-head recovery every N decode "
                         "ticks (0 = telemetry off)")
    ap.add_argument("--replan-every", type=int, default=None,
                    help="force a plan-epoch replan every N decode ticks")
    ap.add_argument("--drift-threshold", type=float, default=None,
                    help="replan when online-vs-offline profile drift "
                         "reaches this value (needs --telemetry-every)")
    # overload robustness (DESIGN.md §2.10)
    ap.add_argument("--admission", default="fifo", choices=["fifo", "slo"],
                    help="admission policy: class-blind arrival order "
                         "(fifo) or SLO-aware class scheduling with "
                         "cost-model deferral and deadline shedding (slo)")
    ap.add_argument("--preemption", action="store_true",
                    help="allow preempting strictly-lower-priority decodes "
                         "(KV blocks swap to a pinned-host tier; resume is "
                         "bitwise-identical)")
    ap.add_argument("--host-blocks", type=int, default=None,
                    help="host swap-tier capacity in KV blocks "
                         "(default: unbounded)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="device KV pool size in blocks (default: "
                         "slots * max_seq / block)")
    # quantized KV-cache block pool (DESIGN.md §2.12)
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "int8", "fp8"],
                    help="KV pool storage dtype: bf16 (exact, default) or "
                         "int8/fp8 codes with per-(block, kv-head) scales "
                         "dequantized inside the flash-decode kernels "
                         "(~2x/4x resident tokens at equal HBM)")
    # sequence-parallel long context (DESIGN.md §2.11)
    ap.add_argument("--seq-shards", type=int, default=1,
                    help="stripe the paged KV pool across N seq shards "
                         "(2D head x sequence layout; 1 = head-parallel "
                         "only). Greedy outputs are identical at any "
                         "value.")
    # fault injection + self-healing (DESIGN.md §2.13)
    ap.add_argument("--fault-plan", default=None,
                    help="fault-injection plan: a JSON file path, an "
                         "inline JSON string, or 'random:SEED:RATE' for a "
                         "seeded Bernoulli schedule over all seams")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="run the allocator/pool invariant auditor every "
                         "N decode ticks and at swap/replan boundaries "
                         "(0 = audits off)")
    ap.add_argument("--swap-retries", type=int, default=3,
                    help="bounded retries for host swap transfers before "
                         "falling back to discard-and-requeue")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for crash-consistent serving "
                         "snapshots (written at replan-safe boundaries)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot every N decode ticks into "
                         "--checkpoint-dir (0 = checkpoints off)")
    # radix-tree prefix cache (DESIGN.md §2.14)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share identical prompt-prefix KV blocks by "
                         "refcount through a radix tree (paged layout; "
                         "greedy outputs are identical either way). The "
                         "synthetic workload switches to an 80%%-shared "
                         "agent pattern so hits actually occur.")
    args = ap.parse_args()
    if args.drift_threshold is not None and args.telemetry_every <= 0:
        ap.error("--drift-threshold needs --telemetry-every > 0")
    if args.seq_shards < 1:
        ap.error("--seq-shards must be >= 1")
    if args.checkpoint_every > 0 and not args.checkpoint_dir:
        ap.error("--checkpoint-every needs --checkpoint-dir")
    enable_compile_cache()

    injector = None
    if args.fault_plan:
        import os
        from repro.serving import FaultInjector, FaultPlan
        if args.fault_plan.startswith("random:"):
            _, seed, rate = args.fault_plan.split(":")
            plan = FaultPlan.random(int(seed), float(rate))
        elif os.path.exists(args.fault_plan):
            plan = FaultPlan.load(args.fault_plan)
        else:
            plan = FaultPlan.from_json(args.fault_plan)
        injector = FaultInjector(plan)
        log.info("fault injection armed: %d specs (seed %s)",
                 len(plan.specs), plan.seed)

    spec = ARCHS[args.arch]
    if spec.module not in ("transformer",):
        raise SystemExit(
            f"serve launcher currently drives transformer-family archs; "
            f"{args.arch} is {spec.module}")
    cfg = spec.smoke if args.smoke else spec.full
    init = _init_fn_for(type(spec)(**{**spec.__dict__, "full": cfg}))
    params = init(jax.random.PRNGKey(0))

    profile = None
    if args.attention == "sparse":
        profile = synthetic_head_curves(cfg.num_layers, cfg.num_heads)
    eng = Engine(cfg, params, EngineConfig(
        attention=args.attention, budget_per_head=args.budget,
        max_seq_len=args.max_seq, num_slots=args.slots,
        num_kv_blocks=args.kv_blocks,
        telemetry_every=args.telemetry_every,
        replan_every=args.replan_every,
        drift_threshold=args.drift_threshold,
        admission=args.admission, preemption=args.preemption,
        host_swap_blocks=args.host_blocks,
        seq_shards=args.seq_shards,
        kv_dtype=args.kv_dtype,
        audit_every=args.audit_every,
        swap_retries=args.swap_retries,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        prefix_cache=args.prefix_cache), profile=profile,
        injector=injector)

    rng = np.random.default_rng(0)
    if args.prefix_cache:
        # agent workload: 80% of requests continue one shared system
        # prompt, the rest are unique — the shape prefix sharing serves
        shared = rng.integers(0, min(cfg.vocab_size, 256), size=(256,))
        prompts = []
        for i in range(args.requests):
            tail = rng.integers(0, min(cfg.vocab_size, 256),
                                size=(int(rng.integers(16, 48)),))
            prompts.append(np.concatenate([shared, tail]) if i % 5 else
                           rng.integers(0, min(cfg.vocab_size, 256),
                                        size=(int(rng.integers(32, 128)),)))
    else:
        prompts = [rng.integers(0, min(cfg.vocab_size, 256),
                                size=(int(rng.integers(32, 128)),))
                   for _ in range(args.requests)]
    classes = ("interactive", "standard", "batch")
    priorities = [classes[i % len(classes)] for i in range(len(prompts))]
    t0 = time.time()
    done = eng.serve(prompts, SamplingParams(max_tokens=args.max_tokens),
                     priorities=priorities)
    dt = time.time() - t0
    n_tok = sum(len(r.generated) for r in done)
    log.info("served %d requests, %d tokens in %.1fs (%.1f tok/s)",
             len(done), n_tok, dt, n_tok / dt)
    bs = eng.decode_bubble_stats
    if args.prefix_cache and bs.get("prefix"):
        ps = bs["prefix"]
        log.info("prefix cache: %d/%d lookups hit (%d tokens mapped for "
                 "free), %d blocks in tree, %d evicted",
                 ps["hits"], ps["lookups"], ps["hit_tokens"],
                 ps["nodes"], ps["evicted_blocks"])
    n_failed = sum(1 for r in done if r.failed)
    if injector is not None or args.audit_every or n_failed:
        fs = bs["faults"]
        log.info("fault layer: %d injected events, %d failed requests, "
                 "%d sentinel trips, %d swap retries (%d recovered / %d "
                 "gave up), %d clean audits, %d replan rollbacks, %d "
                 "checkpoints", bs["injected_events"], n_failed,
                 fs["sentinel_trips"], fs["swap_retries"],
                 fs["swap_recoveries"], fs["swap_giveups"], fs["audits"],
                 fs["replan_rollbacks"], fs["checkpoints"])
        for r in done:
            if r.failed:
                log.info("  rid %d failed: %s", r.rid, r.fail_reason)
    if args.seq_shards > 1:
        log.info("2D decode: head imbalance %.3f, stripe imbalance %.3f, "
                 "%d seq-merge collectives", bs["mean_head_imbalance"],
                 bs["mean_stripe_imbalance"], bs["merge_collectives"])
    if bs["swap"]["swapped_out"] or args.preemption:
        log.info("preemption: %d swapped out / %d back in (%d blocks, "
                 "%.1f KiB to host)", bs["swap"]["swapped_out"],
                 bs["swap"]["swapped_in"], bs["swap"]["blocks_out"],
                 bs["swap"]["bytes_out"] / 1024)
    if eng.plan is not None:
        from repro.core.planner import plan_summary
        s = plan_summary(eng.plan)
        log.info("plan imbalance %.3f (naive %.3f), grid saving %.1f%%",
                 s["mean_imbalance_plan"], s["mean_imbalance_naive"],
                 100 * s["padded_grid_saving"])
        bs = eng.decode_bubble_stats
        if bs["realized_recovery"] is not None:
            log.info("epoch %d after %d replan(s); realized recovery %.3f"
                     "%s", eng.epoch, eng.replans, bs["realized_recovery"],
                     (f", drift {bs['drift']['drift']:.3f}"
                      if bs["drift"] else ""))


if __name__ == "__main__":
    main()
