"""Crash-consistent serving checkpoints (DESIGN.md §2.13).

A serving engine's durable state is scattered across five subsystems: the
device KV pool (+ quantized scales), the allocator's two-tier block
accounting, the scheduler's queues/slots/in-flight requests, the host swap
tier, and the plan epoch (placement + cumulative kv arrangement).
:func:`save_serving` snapshots ALL of them at a replan-safe tick boundary
— the same safe point epoch swaps use, so no prefill chunk sequence
straddles the snapshot — into one atomically-renamed ``.npz``
(``training/checkpoint.py``'s crash discipline: a kill mid-save can never
corrupt the previous snapshot).

:func:`restore_serving` rebuilds a fresh engine from the ORIGINAL params +
offline profile, replays the saved plan as one epoch swap (plan deltas are
endpoint-determined, so the restored params match the crashed engine's
bitwise), adopts the saved pool/allocator/scheduler/host-tier state, and
returns an ``(engine, batcher)`` pair that resumes mid-stream decodes with
greedy tokens identical to the uninterrupted run (tests/test_faults.py).

Format: one npz whose arrays carry the device/host tensors (bfloat16
stored as a uint16 view under a ``#bf16`` key suffix — npz cannot hold
ml_dtypes) and whose JSON metadata travels INSIDE the npz as a uint8
array under ``meta#json`` (single-file atomicity; a sidecar could be
renamed independently and torn)."""
from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import deque

import ml_dtypes
import numpy as np
import jax.numpy as jnp

from repro.core.planner import HPLBPlan, plans_equal
from repro.serving.sampler import SamplingParams
from repro.serving.scheduler import Request, SchedulerStats
from repro.utils.logging import get_logger

log = get_logger("snapshot")

FORMAT_VERSION = 2           # v2: prefix-tree state + retained-prefix counts
_COMPAT_VERSIONS = (1, 2)    # v1 snapshots load (no sharing to restore)
_META_KEY = "meta#json"
# npz holds no ml_dtypes arrays: bf16 (and fp8 KV codes) are stored as
# unsigned ints of the same width under a key suffix naming the dtype
_VIEWS = {"#bf16": (ml_dtypes.bfloat16, np.uint16),
          "#fp8": (ml_dtypes.float8_e4m3fn, np.uint8)}


def _enc(arrays: dict, key: str, arr) -> None:
    """Stash one array, viewing an ml_dtypes array as uints (npz-safe)."""
    a = np.asarray(arr)
    for suffix, (dt, as_uint) in _VIEWS.items():
        if a.dtype == dt:
            arrays[key + suffix] = a.view(as_uint)
            return
    arrays[key] = a


def _dec(z, key: str) -> np.ndarray:
    for suffix, (dt, _) in _VIEWS.items():
        if key + suffix in z.files:
            return z[key + suffix].view(dt)
    return z[key]


def _has(z, key: str) -> bool:
    return any(key + suffix in z.files for suffix in ("", *_VIEWS))


def _req_meta(req: Request) -> dict:
    return {
        "priority": req.priority,
        "sampling": dataclasses.asdict(req.sampling),
        "prefill_pos": int(req.prefill_pos),
        "preemptions": int(req.preemptions),
        "arrival": int(getattr(req, "_arrival", 0)),
        "t_submit": req.t_submit,
        "token_times": list(req.token_times),
    }


def save_serving(directory: str, engine, batcher,
                 tag: str | None = None) -> str:
    """Snapshot the full serving state at a safe tick boundary.

    Must be called between ticks with ``batcher.replan_safe`` (no prefill
    chunk sequence mid-flight) — the engine's checkpoint policy hook
    guarantees this; direct callers must too.  Returns the written path
    (``serving_<decode_ticks>.npz``, or ``serving_<tag>.npz``)."""
    assert batcher.replan_safe, \
        "serving snapshots only at replan-safe boundaries (no mid-prefill)"
    os.makedirs(directory, exist_ok=True)
    arrays: dict[str, np.ndarray] = {}

    # -- device cache (codes + scales travel together, like every move) --
    if engine.quantized:
        pool, scales = engine.cache
        _enc(arrays, "cache/pool", pool)
        _enc(arrays, "cache/scales", scales)
    else:
        _enc(arrays, "cache/pool", engine.cache)
    _enc(arrays, "engine/rng", engine._rng)
    _enc(arrays, "engine/kv_arrange", engine._kv_arrange)

    # -- host swap tier --------------------------------------------------
    hswap_meta = {}
    for rid, rec in engine._host_swaps.items():
        _enc(arrays, f"hswap/{rid}/data", rec["data"])
        if rec["scales"] is not None:
            _enc(arrays, f"hswap/{rid}/scales", rec["scales"])
        _enc(arrays, f"hswap/{rid}/arrange", rec["arrange"])
        hswap_meta[str(rid)] = {
            "tokens": int(rec["tokens"]),
            # §2.14: blocks the swapped sequence keeps RESIDENT (shared
            # prefix) — swap-in scatters the host payload past them
            "shared_blocks": int(rec.get("shared_blocks", 0)),
        }

    # -- scheduler: every not-yet-finished request ------------------------
    reqs: dict[int, Request] = {}
    for q in batcher._queues.values():
        for r in q:
            reqs[r.rid] = r
    for q in batcher._preempted.values():
        for r in q:
            reqs[r.rid] = r
    reqs.update(batcher.active)
    req_meta = {}
    for rid, r in reqs.items():
        _enc(arrays, f"req/{rid}/prompt", np.asarray(r.prompt, np.int32))
        _enc(arrays, f"req/{rid}/generated",
             np.asarray(r.generated, np.int32))
        req_meta[str(rid)] = _req_meta(r)

    alloc_state = batcher.alloc.snapshot_state()
    stats = dataclasses.asdict(batcher.stats)
    meta = {
        "version": FORMAT_VERSION,
        "time": time.time(),
        "engine": {
            "epoch": int(engine.epoch),
            "decode_ticks": int(engine._decode_ticks),
            "ticks_since_replan": int(engine._ticks_since_replan),
            "replans": int(engine.replans),
            "plan": (engine.plan.to_json() if engine.plan is not None
                     else None),
        },
        "alloc": alloc_state,
        # radix prefix cache (§2.14): full tree (content keys, block ids,
        # LRU clocks) so a restored server keeps its hits warm and evicts
        # in the same order the uninterrupted one would have
        "prefix_tree": (engine.prefix.snapshot_state()
                        if engine.prefix is not None else None),
        "hswap_tokens": hswap_meta,
        "requests": req_meta,
        "scheduler": {
            "queues": {n: [r.rid for r in q]
                       for n, q in batcher._queues.items()},
            "preempted": {n: [r.rid for r in q]
                          for n, q in batcher._preempted.items()},
            "active": sorted(batcher.active),
            "lengths": {str(k): int(v) for k, v in batcher.lengths.items()},
            "slots_free": list(batcher._slots_free),
            "slot_of": {str(k): int(v)
                        for k, v in batcher._slot_of.items()},
            "arrivals": int(batcher._arrivals),
            "stride": dict(batcher._stride),
            "ema_decode_s": batcher.ema_decode_s,
            "ema_prefill_s_per_tok": batcher.ema_prefill_s_per_tok,
            "stats": stats,
        },
    }
    arrays[_META_KEY] = np.frombuffer(
        json.dumps(meta).encode("utf-8"), dtype=np.uint8)

    name = f"serving_{tag if tag is not None else engine._decode_ticks}.npz"
    path = os.path.join(directory, name)
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.rename(tmp, path)  # atomic: a crash mid-save leaves the old file
    log.info("serving snapshot -> %s (%d arrays, %d in-flight reqs)",
             path, len(arrays), len(reqs))
    return path


def latest_snapshot(directory: str) -> str | None:
    """Most recently written ``serving_*.npz`` in ``directory``."""
    if not os.path.isdir(directory):
        return None
    cands = [os.path.join(directory, f) for f in os.listdir(directory)
             if f.startswith("serving_") and f.endswith(".npz")]
    return max(cands, key=os.path.getmtime) if cands else None


def restore_serving(path: str, cfg, params, engine_cfg, profile=None,
                    classes=None, injector=None):
    """Rebuild a serving engine + batcher from a :func:`save_serving`
    snapshot.  ``cfg`` / ``params`` / ``engine_cfg`` / ``profile`` are the
    SAME artifacts the crashed engine was launched with (params
    un-permuted, profile offline) — the snapshot replays the saved plan on
    top of them.  Returns ``(engine, batcher)`` ready to keep ticking."""
    from repro.serving.engine import Engine

    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(bytes(z[_META_KEY].tobytes()).decode("utf-8"))
        if meta["version"] not in _COMPAT_VERSIONS:
            raise ValueError(
                f"snapshot version {meta['version']} not in "
                f"{_COMPAT_VERSIONS}")
        em = meta["engine"]
        eng = Engine(cfg, params, engine_cfg, profile=profile,
                     injector=injector)

        # -- plan epoch: replay the saved placement as one delta swap ----
        if em["plan"] is not None:
            saved_plan = HPLBPlan.from_json(em["plan"])
            if eng.plan is not None and not plans_equal(eng.plan,
                                                        saved_plan):
                # delta composition is endpoint-determined: permuting the
                # fresh plan's params by delta(fresh -> saved) lands on
                # the crashed engine's arrangement bitwise
                eng.replan_now(plan=saved_plan)
        if eng.plan is not None and eng.epoch != em["epoch"]:
            eng.plan = dataclasses.replace(eng.plan, epoch=em["epoch"])
            eng.epoch = em["epoch"]
            eng._epoch_stats.setdefault(em["epoch"],
                                        eng._fresh_epoch_stats())
            # plan-derived memos were keyed under the replay's interim
            # epoch numbers — drop them so everything re-derives under
            # the restored epoch (correct either way; this keeps the
            # epoch-keyed caches from carrying orphan keys)
            for d in (eng._worklists_cache, eng._chunk_cap,
                      eng._chunk_wl_cache, eng._decode_ids_by_nblocks,
                      eng._nb_cap, eng._packed_plan_cache):
                d.clear()
        eng.replans = em["replans"]
        eng._decode_ticks = em["decode_ticks"]
        eng._ticks_since_replan = em["ticks_since_replan"]
        eng._kv_arrange = np.array(_dec(z, "engine/kv_arrange"))
        eng._rng = jnp.asarray(_dec(z, "engine/rng"))

        # -- device cache ------------------------------------------------
        if eng.quantized:
            eng._set_cache((jnp.asarray(_dec(z, "cache/pool")),
                            jnp.asarray(_dec(z, "cache/scales"))))
        else:
            eng._set_cache(jnp.asarray(_dec(z, "cache/pool")))

        # -- host swap tier ----------------------------------------------
        eng._host_swaps = {}
        for rid_s, hm in meta["hswap_tokens"].items():
            rid = int(rid_s)
            if not isinstance(hm, dict):   # v1: bare token count
                hm = {"tokens": hm, "shared_blocks": 0}
            eng._host_swaps[rid] = {
                "data": np.array(_dec(z, f"hswap/{rid}/data")),
                "scales": (np.array(_dec(z, f"hswap/{rid}/scales"))
                           if _has(z, f"hswap/{rid}/scales") else None),
                "tokens": int(hm["tokens"]),
                "shared_blocks": int(hm.get("shared_blocks", 0)),
                "arrange": np.array(_dec(z, f"hswap/{rid}/arrange")),
            }

        # -- scheduler + allocator ---------------------------------------
        b = eng.make_batcher(classes) if classes is not None \
            else eng.make_batcher()
        b.alloc.load_state(meta["alloc"])  # audits itself on load
        if eng.prefix is not None and meta.get("prefix_tree"):
            # after alloc.load_state: the cache pins are already restored
            # (cache_block is idempotent), so the tree adopts a consistent
            # allocator and the final audit checks their agreement
            eng.prefix.load_state(meta["prefix_tree"])
        reqs: dict[int, Request] = {}
        for rid_s, rm in meta["requests"].items():
            rid = int(rid_s)
            req = Request(
                rid=rid,
                prompt=np.array(_dec(z, f"req/{rid}/prompt")),
                sampling=SamplingParams(**rm["sampling"]),
                priority=rm["priority"])
            req.generated = [int(t)
                             for t in _dec(z, f"req/{rid}/generated")]
            req.prefill_pos = rm["prefill_pos"]
            req.preemptions = rm["preemptions"]
            req.t_submit = rm["t_submit"]
            req.token_times = list(rm["token_times"])
            req._arrival = rm["arrival"]
            reqs[rid] = req
        sm = meta["scheduler"]
        for name, rids in sm["queues"].items():
            b._queues[name] = deque(reqs[r] for r in rids)
        for name, rids in sm["preempted"].items():
            b._preempted[name] = deque(reqs[r] for r in rids)
        b.active = {r: reqs[r] for r in sm["active"]}
        b.prefilling = None  # snapshots only happen at safe points
        b.lengths = {int(k): v for k, v in sm["lengths"].items()}
        b._slots_free = list(sm["slots_free"])
        b._slot_of = {int(k): v for k, v in sm["slot_of"].items()}
        b._rid_of = {v: k for k, v in b._slot_of.items()}
        b._arrivals = sm["arrivals"]
        b._stride = dict(sm["stride"])
        b.ema_decode_s = sm["ema_decode_s"]
        b.ema_prefill_s_per_tok = sm["ema_prefill_s_per_tok"]
        st = sm["stats"]
        per_class = st.pop("per_class", {})
        b.stats = SchedulerStats(**st)
        b.stats.per_class = {k: dict(v) for k, v in per_class.items()}
    eng.audit()  # a torn/corrupt snapshot fails loudly here, not mid-serve
    log.info("restored serving state from %s: epoch=%d tick=%d "
             "(%d active, %d queued, %d swapped)", path, eng.epoch,
             eng._decode_ticks, len(b.active),
             sum(len(q) for q in b._queues.values()),
             len(eng._host_swaps))
    return eng, b
