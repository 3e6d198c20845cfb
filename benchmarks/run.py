"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME] [--smoke]

Prints ``benchmark,metric,value`` CSV to stdout; JSON details land in
``artifacts/bench/``.  ``--smoke`` runs the fast CI subset (quick sizes,
hot-path suites only) so PRs catch decode/prefill perf regressions.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

OUT = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")

SUITES = [
    ("sparsity_profile", "paper Fig. 3/4/6"),
    ("budget_alloc", "paper Fig. 7"),
    ("load_balance", "paper Fig. 8"),
    ("accuracy_ruler", "paper Table 1"),
    ("latency_attention", "paper Fig. 9"),
    ("decode_pack", "decode microbench: packed-vs-padded grids (§2.8)"),
    ("skyline", "paper Fig. 10"),
    ("lb_ablation", "paper Fig. 11"),
    ("serving", "chunked-prefill tick loop (TTFT/ITL)"),
    ("adapt_replan", "plan epochs: replanning under workload shift (§2.9)"),
    ("overload", "open-loop Poisson overload: per-class SLO attainment, "
                 "preemption + KV swap-to-host (§2.10)"),
    ("seqpar", "sequence-parallel long-context decode: striped 2D path "
               "latency + per-axis imbalance vs 1D (§2.11)"),
    ("quant_kv", "quantized KV pool: capacity at equal bytes, dequant-"
                 "fused packed decode latency, recovery delta (§2.12)"),
    ("chaos", "fault injection: goodput + recovery latency vs fault "
              "rate, self-healing engine (§2.13)"),
    ("prefix_cache", "radix-tree prefix cache: TTFT + admitted throughput "
                     "vs shared-prefix hit rate (§2.14)"),
]

# fast subset exercising the serving hot paths (CI perf smoke); the decode
# microbench refreshes BENCH_decode.json every PR so the packed-vs-padded
# latency series has a per-commit trajectory, adapt_replan refreshes
# BENCH_adapt.json so epoch-swap recovery/latency regress visibly, and
# overload refreshes BENCH_overload.json (short burst profile) so graceful
# degradation (per-class attainment under preemption) regresses visibly too,
# and seqpar refreshes BENCH_seqpar.json so the striped 2D decode path's
# merge overhead and per-axis imbalance regress visibly (§2.11), and
# quant_kv refreshes BENCH_quant.json so the quantized pool's capacity /
# dequant-fused decode latency / recovery delta regress visibly (§2.12),
# and chaos refreshes BENCH_chaos.json so goodput under injected faults
# and fault-recovery latency regress visibly (§2.13)
SMOKE = ("load_balance", "latency_attention", "decode_pack", "serving",
         "adapt_replan", "overload", "seqpar", "quant_kv", "chaos",
         "prefix_cache")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced example counts (CI mode)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI perf smoke: --quick sizes, hot-path suites only")
    args = ap.parse_args()
    if args.smoke:
        args.quick = True
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    os.makedirs(OUT, exist_ok=True)
    print("benchmark,metric,value")
    errors: list[dict] = []
    for name, paper_ref in SUITES:
        if args.only and name != args.only:
            continue
        if args.smoke and not args.only and name not in SMOKE:
            continue
        t0 = time.time()
        try:
            # import INSIDE the guard: a suite whose module fails to import
            # (missing optional dep, syntax error) must not abort the whole
            # driver — every remaining suite still runs and the failure
            # lands as a structured entry instead of a dead process
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            rows = mod.run(OUT, quick=args.quick)
        except Exception as e:  # noqa: BLE001
            errors.append({
                "suite": name, "paper_ref": paper_ref,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc(),
                "elapsed_s": round(time.time() - t0, 1),
            })
            traceback.print_exc(file=sys.stderr)
            print(f"{name},STATUS,error")
            continue
        for metric, value in rows:
            print(f"{name},{metric},{value:.6g}")
        print(f"{name},elapsed_s,{time.time() - t0:.1f}")
    if errors:
        import json
        with open(os.path.join(OUT, "BENCH_errors.json"), "w") as f:
            json.dump(errors, f, indent=2)
        print(f"driver,failed_suites,{len(errors)}")
    _mirror_headline_json()
    return 1 if errors else 0


def _mirror_headline_json() -> None:
    """Copy every BENCH_*.json produced this run to the repo root so the
    headline numbers ride along with the tree (CI uploads both the
    artifacts dir and the root copies)."""
    import glob
    import shutil
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    for src in sorted(glob.glob(os.path.join(OUT, "BENCH_*.json"))):
        try:
            shutil.copy2(src, os.path.join(root, os.path.basename(src)))
        except OSError as e:  # read-only checkout: report, don't abort
            print(f"driver,mirror_error,{os.path.basename(src)}: {e}",
                  file=sys.stderr)


if __name__ == "__main__":
    raise SystemExit(main())
