#!/usr/bin/env python3
"""Benchmark of the S-HPLB serving engine on a TPU: one cell, one run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is a model configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  A run makes the configuration's weights
on the device from the seed, builds the ``Engine``, compiles every step
program the cell can pick, brings the traffic to its steady state, and
measures ``--seconds`` of it through the engine's batcher.  With ``--trace
1`` the first seconds of the window are traced and the cell's per-layer
metrics are printed instead of its end-to-end ones.

After the window a seeded sample of finished requests is checked against
the plain reference of the configuration's family
(``bench/harness/families``).  In a cell whose check sets logit limits
the basis is ``program_logits``: at every served token's position the
program's own logits, read by the tap (``bench/harness/tap.py``) at a
seeded set of ids and the row's top 8, are compared with the reference's
float32 logits there (``logit_err``, ``logit_err_mean``); a served token
whose logits the tap did not get counts under ``logit_rows_missing``, and
any such token makes the run not correct.  The served token's gap below
the reference's best (``gap_max``) guards against gross token errors.  A
cell that sets no logit limits is decided on ``gap_max`` alone (basis
``served_tokens``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), ``basis``, and last ``check``, each compared number with
its limit.  Anything but a TPU, or fewer chips than the cell asks for,
exits 1 and prints no result.

``--control fp8`` runs the cell with the engine's fp8 KV pool, the step
below the configuration's bfloat16, and ``--fault token`` alters every
row's token on one decode call in seven where it is produced: the comparison has to fail
under both.  The benchmark's own runs never pass them.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

CONTROLS = {"fp8": {"kv_dtype": "fp8"}}


def alter_tokens(drv) -> None:
    """Fault: every seventh decode call returns every row's token moved by
    one id, so each request that decodes seven tokens holds one."""
    inner, calls = drv._decode, [0]

    def decode(slots, toks, pos):
        out = np.array(inner(slots, toks, pos))
        calls[0] += 1
        if calls[0] % 7 == 0:
            out = (out + 1) % drv.mix.vocab
        return out
    drv._decode = decode


FAULTS = {"token": alter_tokens}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class View:
    """What a per-layer metric reader reads: counter deltas over the traced
    window, the trace's reduction, the calls the harness saw in it, the
    configuration, its block selection and the device's peaks."""

    def __init__(self, counters, trace, decode_calls, chunk_calls, cfg, sel,
                 peaks, kv_bytes):
        self.counters = counters
        self.trace = trace
        self.decode_calls = decode_calls
        self.chunk_calls = chunk_calls
        self.cfg = cfg
        self.sel = sel
        self.peaks = peaks
        self.kv_bytes = kv_bytes


def device_gate(chips: int, require_tpu: bool = True):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        log(f"bench: needs a TPU, JAX found {platform!r} ({len(devices)} "
            "device(s)); nothing was run")
        return None
    if len(devices) < chips:
        log(f"bench: the cell needs {chips} chips, JAX found {len(devices)}")
        return None
    return devices


def enable_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(BENCH / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def sample_finished(records, t0, t1, n, seed):
    """A seeded sample of the requests finished in the window, the one with
    the most served tokens always in it."""
    from harness.traffic import rng_for
    done = sorted((r for r in records.values() if r.finished and r.tokens
                   and r.t_done is not None and t0 <= r.t_done <= t1),
                  key=lambda r: (-len(r.tokens), r.rid))
    if not done:
        return []
    rest = done[1:]
    pick = rng_for(seed, "sample").permutation(len(rest))[:max(0, n - 1)]
    return [done[0]] + [rest[i] for i in sorted(pick)]


def logit_err_rows(z_prog, z_ref) -> np.ndarray:
    """Per position: the RMS over the compared ids of the program's error,
    over the RMS spread of the reference's logits about their mean."""
    err = np.sqrt(np.mean((z_prog - z_ref) ** 2, axis=1))
    spread = np.sqrt(np.mean((z_ref - z_ref.mean(1, keepdims=True)) ** 2,
                             axis=1))
    return err / spread


def run(cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, control: str | None = None,
        fault: str | None = None,
        t_start: float = T_START, driver_hook=None,
        peaks: dict | None = None,
        keep_trace: str | None = None) -> dict | None:
    """One run of ``cell``; returns the result object, or None when the
    device gate refuses."""
    devices = device_gate(cell.chips, require_tpu)
    if devices is None:
        return None
    import jax
    from harness import families, model, plan, stats
    from harness.meter import CompileMeter
    from harness.serve import Driver, warm_steps
    from harness.tap import LogitTap, gather, logit_ids
    from harness.traffic import Mix
    from repro.serving import Engine

    cache_dir = enable_cache()
    meter = CompileMeter()
    dev = devices[0]
    kind = dev.device_kind
    peaks = peaks if peaks is not None else spec.peaks(kind)
    cfg, traffic = cell.config, cell.traffic
    fam = families.load(cfg)
    m = fam.dims(cfg)
    log(f"[device] {dev.platform} {kind!r} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")

    marks = {}
    snap = meter.snapshot()
    tcfg = fam.program_config(cfg)
    ecfg = model.engine_config(cfg, traffic, CONTROLS.get(control))
    eng = Engine(tcfg, fam.make_weights(cfg, seed, layers_on_host=True), ecfg,
                 profile=model.head_profile(m["L"], m["H"],
                                            cfg["engine_profile_seed"]))
    marks["engine"] = time.monotonic()
    chk = traffic["check"]
    on_logits = "logit_err_limit" in chk
    ids = logit_ids(seed, m["V"])
    ids_dev = jax.numpy.asarray(ids)
    buckets = warm_steps(eng, on_logits=(lambda lg: gather(lg, ids_dev))
                         if on_logits else None)
    marks["warm"] = time.monotonic()
    mix = Mix(traffic, seed, m["V"])
    drv = Driver(eng, mix)
    tap = LogitTap(eng, drv.batcher, drv.reqs, ids) if on_logits else None
    if fault is not None:
        FAULTS[fault](drv)
    if driver_hook is not None:
        driver_hook(drv)
    if mix.closed:
        drv.start_closed()
    else:
        origin = time.monotonic()
        warm_s = float(traffic.get("warmup_s", 0.0))
        drv.start_open(origin, warm_s + seconds + 5.0)
        drv.run_until(origin + warm_s)
    t0 = time.monotonic()
    setup = meter.since(snap)
    log(f"[setup] {t0 - t_start:.3f} s: engine {marks['engine'] - t_start:.3f}"
        f" s, warm-up of {len(buckets)} decode buckets "
        f"{marks['warm'] - marks['engine']:.3f} s, fill {t0 - marks['warm']:.3f}"
        f" s; compile {setup['compile_s']:.3f} s ({setup['compiles']} "
        f"compiles, cache hits {setup['cache_hits']}, misses "
        f"{setup['cache_misses']})")

    snap = meter.snapshot()
    trace_dir = view_trace = None
    c0 = drv.counters()
    if trace:
        trace_s = min(float(traffic.get("trace_seconds", 5.0)), seconds)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.block_until_ready(eng.cache)
        n_dec, n_chk = len(drv.decode_calls), len(drv.chunk_calls)
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            drv.run_until(t0 + trace_s)
            jax.block_until_ready(eng.cache)
        jax.profiler.stop_trace()
        c1 = drv.counters()
        traced_decode = drv.decode_calls[n_dec:]
        traced_chunks = drv.chunk_calls[n_chk:]
    drv.run_until(t0 + seconds)
    t1 = time.monotonic()
    inwin = meter.since(snap)
    log(f"[queue] at the close: {len(drv.batcher.pending)} waiting, "
        f"{len(drv.batcher.active)} decoding")
    log(f"[window] {t1 - t0:.3f} s, {drv.ticks} ticks in all; compiles in "
        f"the window: {inwin['compiles']} ({inwin['compile_s']:.3f} s, cache "
        f"hits {inwin['cache_hits']}, misses {inwin['cache_misses']})")

    records = drv.records
    wm = stats.window_metrics(list(records.values()), t0, t1)
    due = [r for r in records.values() if t0 <= r.due < t1]
    failed = sum(1 for r in due if r.refused)
    log(f"[traffic] due in window {len(due)}, refused {failed}, TTFT samples "
        f"{wm['ttft_n']}, token gaps {wm['itl_n']}, generator late p50 "
        f"{wm['late_p50_ms']} ms, max {wm['late_max_ms']} ms")
    stats_mem = dev.memory_stats() or {}
    mem_peak = int(stats_mem.get("peak_bytes_in_use", 0))

    result = {"correct": False, "attempted": len(due), "failed": failed}
    sel = plan.Selection(
        plan.maxmin_budgets(
            m["L"], m["H"], budget_per_head=ecfg.budget_per_head,
            seq_len=ecfg.max_seq_len, block=ecfg.block, floor=ecfg.floor,
            profile_seed=cfg["engine_profile_seed"]),
        group=m["H"] // m["Hkv"], block=ecfg.block)
    metrics = {}
    if trace:
        from harness import trace as tr
        view_trace = tr.reduce_dir(trace_dir)
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        delta = {k: c1[k] - c0[k] for k in c0}
        view = View(delta, view_trace, traced_decode, traced_chunks, cfg, sel,
                    peaks, 1 if ecfg.kv_dtype != "bf16" else 2)
        for mt in cell.per_layer:
            val = spec.load_metric(mt["name"]).read(view)
            if val is not None:
                metrics[mt["name"]] = {"value": float(val), "unit": mt["unit"]}
        if view_trace is not None:
            result["breakdown"] = {"device_ops": view_trace["device_ops"],
                                   "idle_gaps": view_trace["idle_gaps"]}
    else:
        for mt in cell.end_to_end:
            if mt["name"] == "setup_s":
                val = t0 - t_start
            else:
                val = wm.get(mt["name"])
            if val is not None:
                metrics[mt["name"]] = {"value": float(val), "unit": mt["unit"]}
    result["metrics"] = metrics
    device = {"platform": dev.platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    if trace and view_trace is not None:
        device["busy_s"] = view_trace["busy_s"]
        device["window_s"] = view_trace["window_s"]
    result["device"] = device

    # correctness: wait past the close (up to a minute, unmeasured) until
    # finished requests hold enough served tokens; free the program's
    # state, then the reference
    t_done = t1
    while (sum(len(r.tokens) for r in sample_finished(records, t0, t_done,
                                                      10 ** 9, seed))
           < int(chk["min_tokens"]) and t_done < t1 + 60.0):
        drv.run_until(time.monotonic() + 1.0)
        t_done = time.monotonic()
    if t_done > t1:
        log(f"[reference] waited {t_done - t1:.3f} s past the close for "
            "finished requests")
    sample = sample_finished(records, t0, t_done, int(chk["samples"]), seed)
    basis = "program_logits" if on_logits else "served_tokens"
    prog, missing = {}, 0
    if on_logits:
        prog = {r.rid: tap.request(r.rid, len(r.tokens)) for r in sample}
        missing = sum(tap.missing(r.rid, len(r.tokens)) for r in sample)
        if missing:
            log(f"[tap] {missing} served tokens have no program logits"
                + (f" ({tap.reason})" if tap.reason else "")
                + ": the run is not correct")
    del drv, eng, tap
    gc.collect()
    t_ref = time.monotonic()
    ref = fam.Reference(m, fam.make_weights(cfg, seed), sel)
    gaps, errs = [], []
    for r in sample:
        if prog.get(r.rid) is not None:
            vals, top_i, top_v = prog[r.rid]
            n = len(r.tokens)
            cmp_ids = np.concatenate([np.broadcast_to(ids, (n, len(ids))),
                                      top_i], axis=1)
            g, z_ref = ref.compare(r.prompt, r.tokens, cmp_ids)
            errs.append(logit_err_rows(np.concatenate([vals, top_v], 1),
                                       z_ref))
        else:
            g, _ = ref.compare(r.prompt, r.tokens)
        gaps.append(g)
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    errs = np.concatenate(errs) if errs else np.zeros(0)
    n_tok = len(gaps)
    finite = bool(np.isfinite(gaps).all() and np.isfinite(errs).all())
    worst = lambda v, f: (float(f(v)) if finite else float("inf"))
    check = {}
    if on_logits:
        got = len(errs) > 0
        check["logit_err"] = {"value": worst(errs, np.max) if got else None,
                              "limit": float(chk["logit_err_limit"])}
        check["logit_err_mean"] = {
            "value": worst(errs, np.mean) if got else None,
            "limit": float(chk["logit_err_mean_limit"])}
        check["logit_rows_missing"] = {"value": missing, "limit": 0}
    check["gap_max"] = {"value": worst(gaps, np.max) if n_tok else None,
                        "limit": float(chk["gap_max_limit"])}
    check["served_tokens_compared"] = {"value": n_tok,
                                       "limit": int(chk["min_tokens"])}
    check["failed"] = {"value": failed, "limit": 0}
    ok = (n_tok >= int(chk["min_tokens"]) and finite
          and all(v["value"] is not None and v["value"] <= v["limit"]
                  for k, v in check.items()
                  if k != "served_tokens_compared"))
    result["correct"] = bool(ok)
    log(f"[readings] basis {basis}; gap_max {check['gap_max']['value']!r} "
        f"gap_mean {float(gaps.mean()) if n_tok else None!r} flips "
        f"{int((gaps > 0).sum())}/{n_tok}" + (
            f"; logit_err {check['logit_err']['value']!r} mean "
            f"{check['logit_err_mean']['value']!r} median "
            f"{float(np.median(errs))!r}" if len(errs) else ""))
    result["basis"] = basis
    result["check"] = check
    log(f"[reference] {len(sample)} requests, {n_tok} served tokens, "
        f"{time.monotonic() - t_ref:.3f} s" + (f" (control {control})"
                                                 if control else "")
        + (f" (fault {fault})" if fault else ""))
    for k, v in check.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=sorted(CONTROLS), default=None)
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace of a --trace 1 run here")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench: the system under test (src/repro) is not in {ROOT}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = spec.load_cell(args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 control=args.control, fault=args.fault,
                 keep_trace=args.keep_trace)
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
