"""Reduction of a profiler trace to device busy time, step and kernel times,
and the idle gaps with what the host was doing in each.

The harness opens ``TraceAnnotation`` spans around its own calls into the
system: ``bench.window`` (the traced window), ``bench.tick`` (one scheduler
tick), ``bench.prefill`` (a prefill chunk call) and ``bench.decode`` (a
decode call).  The device planes (``/device:TPU:<n>``) hold one event per
program run on the ``XLA Modules`` line and one per operation on the
``XLA Ops`` line.

A program that runs the Pallas paged decode kernel (an operation whose
name holds ``KERNEL_TAG``) is a decode step; a program whose name starts
with ``CHUNK_TAG`` (the engine names its chunk steps ``jit_prefill_chunk``
and ``jit_prefill_chunk_dense``) is a prefill chunk.

Operations nest (a ``while`` loop holds the ops of its body), so the
breakdown ranks operations by self time, their own length less that of the
operations inside them, under their HLO instruction name and kind.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

KERNEL_TAG = "flash_decode"
CHUNK_TAG = "jit_prefill_chunk"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPANS = {"bench.prefill": "prefill call", "bench.decode": "decode call",
         "bench.tick": "schedule", "bench.window": "harness"}


def find_xplane(trace_dir: str) -> str | None:
    got = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True))
    return got[-1] if got else None


def _union(intervals):
    """Total length and merged list of ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load_planes(path: str) -> dict:
    """``{"devices": {plane: {line: events}}, "host": [span events]}`` with
    events as ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            out["devices"][plane.name] = {
                line.name: _events(line) for line in plane.lines
                if line.name in ("XLA Modules", "XLA Ops")}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(ev for ev in _events(line)
                                   if ev[0] in SPANS)
    return out


def reduce_planes(planes: dict) -> dict | None:
    """Busy and idle time, per-program and kernel times, and the top device
    operations and idle gaps of the traced window; None when the trace has
    no window span or no device operation in it."""
    win = [ev for ev in planes["host"] if ev[0] == "bench.window"]
    if not win:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    spans = sorted((s, s + d, SPANS[n]) for n, s, d in planes["host"]
                   if n != "bench.window")
    busy, idle_gaps, op_time = [], [], {}
    decode_ms, prefill_ms, kernel_ns = [], [], 0.0
    for lines in planes["devices"].values():
        ops = [ev for ev in lines.get("XLA Ops", [])
               if ev[1] < w1 and ev[1] + ev[2] > w0]
        if not ops:
            continue
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in ops]
        total, merged = _union(clipped)
        busy.append(total)
        for name, t in self_times(ops).items():
            op_time[short_name(name)] = op_time.get(short_name(name), 0.0) + t
        prev = w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                idle_gaps.append((s - prev, _doing(spans, (prev + s) / 2)))
            prev = max(prev, e)
        mods = sorted((ev for ev in lines.get("XLA Modules", [])
                       if w0 <= ev[1] and ev[1] + ev[2] <= w1),
                      key=lambda ev: ev[1])
        starts = [m[1] for m in mods]
        decode_idx = set()
        for name, s, d in ops:
            if KERNEL_TAG in short_name(name).split(" ")[0]:
                kernel_ns += d
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < mods[i][1] + mods[i][2]:
                    decode_idx.add(i)
        for i, (name, _, d) in enumerate(mods):
            if i in decode_idx:
                decode_ms.append(d / 1e6)
            elif _base(name).startswith(CHUNK_TAG):
                prefill_ms.append(d / 1e6)
    if not busy:
        return None
    n_dev = len(busy)
    idle_gaps.sort(key=lambda g: -g[0])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "devices": n_dev,
        "decode_step_ms": decode_ms,
        "prefill_chunk_ms": prefill_ms,
        "kernel_s": kernel_ns / n_dev / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in top_ops],
        "idle_gaps": [[what, t / 1e9] for t, what in idle_gaps[:10]],
    }


def _base(module: str) -> str:
    return module.split("(")[0]


_KIND = re.compile(r"^%?([^ ]+) = .*?\b([a-z][\w.-]*)\(")


def short_name(op: str) -> str:
    """``%copy.54 = bf16[...] copy(...)`` -> ``copy.54 (copy)``."""
    m = _KIND.match(op)
    return f"{m.group(1)} ({m.group(2)})" if m else op[:80]


def self_times(ops) -> dict:
    """Self time of each operation name over ``(name, start, dur)`` events
    that nest properly."""
    out, stack = {}, []          # stack of [end, name]
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] = out.get(stack[-1][1], 0.0) - d
        out[name] = out.get(name, 0.0) + d
        stack.append([s + d, name])
    return out


def _doing(spans, t: float) -> str:
    """The innermost harness span open at ``t`` (host clock of the trace)."""
    best, width = "harness", float("inf")
    for s, e, what in spans:
        if s > t:
            break
        if e >= t and e - s < width:
            best, width = what, e - s
    return best


def reduce_dir(trace_dir: str) -> dict | None:
    path = find_xplane(trace_dir)
    return None if path is None else reduce_planes(load_planes(path))
