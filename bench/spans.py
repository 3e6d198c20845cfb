#!/usr/bin/env python3
"""The program's own spans in a kept trace, and the device's idle time put
down to the host work that held it.

    python bench/spans.py <trace_dir>
    python bench/spans.py <trace_dir> --fixture out.json.gz --fixture-ms 400

``<trace_dir>`` is what ``bench/run.py --trace 1 --keep-trace <dir>``
keeps.  The scheduler and the engine open ``TraceAnnotation`` spans on the
profiler's own clock (``sched.tick``, ``sched.decode``,
``engine.decode.wait``, ``engine.sentinel``, ...), so they share one
timeline with the device's programs and operations.  On top of the
reduction of ``harness/trace.py`` this reads:

- ``idle_gaps``: the ten longest idle gaps, each named by the innermost
  span open at its midpoint, program or harness;
- ``idle_by_span``: the window's idle seconds split across the innermost
  span open at each instant, so a gap that runs through several spans is
  shared out among them;
- ``decode_gaps_ms``: for each pair of consecutive decode steps with no
  prefill chunk between them, the end of one step's ``engine.decode.wait``
  to the end of the next step's ``engine.decode.dispatch``: the serial host
  path during which the device has nothing queued;
- ``tick_self_ms``: each ``sched.tick`` less the time covered by the
  ``engine.*`` spans inside it: the scheduler's own host work;
- ``bench_tick_ms``: the length of each ``bench.tick`` span.

Prints one JSON object.  ``--fixture`` writes the first ``--fixture-ms``
of the window as a test fixture (``bench/tests/data``).
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import statistics
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import trace  # noqa: E402

PROGRAM = ("sched.", "engine.")
PREFILL_SPANS = ("sched.prefill_step", "engine.prefill.")


def load_planes(path: str) -> dict:
    """As ``trace.load_planes``, with the program's spans kept on the host
    side."""
    from jax.profiler import ProfileData
    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            out["devices"][plane.name] = {
                line.name: trace._events(line) for line in plane.lines
                if line.name in ("XLA Modules", "XLA Ops")}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    ev for ev in trace._events(line)
                    if ev[0] in trace.SPANS or ev[0].startswith(PROGRAM))
    return out


def _idle(ops, w0, w1):
    """Idle ``(start, end)`` intervals of one device inside the window."""
    _, merged = trace._union([(max(s, w0), min(s + d, w1))
                              for _, s, d in ops])
    out, prev = [], w0
    for s, e in merged + [[w1, w1]]:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    return out


def _within(events, w0, w1):
    return [(n, s, s + d) for n, s, d in events if w0 <= s and s + d <= w1]


def reduce(planes: dict) -> dict | None:
    """``trace.reduce_planes`` of the harness's spans, with ``idle_gaps``
    named by the program's spans and the keys of the module docstring
    added; None where that reads nothing."""
    harness = [ev for ev in planes["host"] if ev[0] in trace.SPANS]
    out = trace.reduce_planes({"devices": planes["devices"],
                               "host": harness})
    if out is None:
        return None
    win = [ev for ev in harness if ev[0] == "bench.window"][0]
    w0, w1 = win[1], win[1] + win[2]
    spans = sorted((s, s + d, trace.SPANS.get(n, n))
                   for n, s, d in planes["host"] if n != "bench.window")
    edges = sorted({t for s, e, _ in spans for t in (s, e)})
    gaps, by_span, n_dev = [], {}, 0
    for lines in planes["devices"].values():
        ops = [ev for ev in lines.get("XLA Ops", [])
               if ev[1] < w1 and ev[1] + ev[2] > w0]
        if not ops:
            continue
        n_dev += 1
        for a, b in _idle(ops, w0, w1):
            gaps.append((b - a, trace._doing(spans, (a + b) / 2)))
            i, j = bisect.bisect_right(edges, a), bisect.bisect_left(edges, b)
            cuts = [a] + edges[i:j] + [b]
            for x, y in zip(cuts, cuts[1:]):
                what = trace._doing(spans, (x + y) / 2)
                by_span[what] = by_span.get(what, 0.0) + y - x
    gaps.sort(key=lambda g: -g[0])
    out["idle_gaps"] = [[what, t / 1e9] for t, what in gaps[:10]]
    out["idle_by_span"] = {k: v / n_dev / 1e9 for k, v in sorted(
        by_span.items(), key=lambda kv: -kv[1])}
    host = _within(planes["host"], w0, w1)
    out["decode_gaps_ms"] = decode_gaps_ms(host)
    out["tick_self_ms"] = tick_self_ms(host)
    out["bench_tick_ms"] = [(e - s) / 1e6 for n, s, e in host
                            if n == "bench.tick"]
    return out


def decode_gaps_ms(host) -> list[float]:
    """End of a decode step's ``engine.decode.wait`` to the end of the next
    step's ``engine.decode.dispatch``, for each such pair with no prefill
    span between them; ``host`` holds ``(name, start, end)``."""
    marks = sorted((e, n) for n, s, e in host
                   if n in ("engine.decode.wait", "engine.decode.dispatch"))
    prefill = sorted(s for n, s, e in host if n.startswith(PREFILL_SPANS))
    out, waited = [], None
    for t, name in marks:
        if name == "engine.decode.wait":
            waited = t
        elif waited is not None:
            if bisect.bisect_left(prefill, t) == bisect.bisect_right(
                    prefill, waited):
                out.append((t - waited) / 1e6)
            waited = None
    return out


def tick_self_ms(host) -> list[float]:
    """Each ``sched.tick`` less the union of the ``engine.*`` spans inside
    it."""
    engine = [(s, e) for n, s, e in host if n.startswith("engine.")]
    out = []
    for n, s, e in host:
        if n == "sched.tick":
            inside, _ = trace._union([(a, b) for a, b in engine
                                      if s <= a and b <= e])
            out.append((e - s - inside) / 1e6)
    return out


def fixture(planes: dict, ms: float, source: str) -> dict:
    """The first ``ms`` of the window: events that start and end inside
    it, the window span cut to that length, and what ``reduce`` reads."""
    win = [ev for ev in planes["host"] if ev[0] == "bench.window"][0]
    w0, w1 = win[1], win[1] + ms * 1e6
    keep = lambda evs: [[n, s, d] for n, s, d in evs
                        if w0 <= s and s + d <= w1]
    cut = {"devices": {p: {ln: keep(evs) for ln, evs in lines.items()}
                       for p, lines in planes["devices"].items()},
           "host": [["bench.window", w0, ms * 1e6]]
           + [ev for ev in keep(planes["host"]) if ev[0] != "bench.window"]}
    got = reduce({"devices": cut["devices"],
                  "host": [tuple(e) for e in cut["host"]]})
    return {"source": source, **cut, "reduced": {
        "busy_s": got["busy_s"], "decode_steps": len(got["decode_step_ms"]),
        "idle_by_span": got["idle_by_span"]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--fixture", default=None)
    ap.add_argument("--fixture-ms", type=float, default=400.0)
    ap.add_argument("--source", default="a --trace 1 run of bench/run.py")
    args = ap.parse_args(argv)
    path = trace.find_xplane(args.trace_dir)
    if path is None:
        print(f"spans: no trace under {args.trace_dir}", file=sys.stderr)
        return 1
    planes = load_planes(path)
    got = reduce(planes)
    if got is None:
        print("spans: no window span or no device operation",
              file=sys.stderr)
        return 1
    mean = lambda xs: sum(xs) / len(xs) if xs else None
    idle = got["window_s"] - got["busy_s"]
    print(json.dumps({
        "window_s": got["window_s"], "busy_s": got["busy_s"],
        "decode_step_ms_mean": mean(got["decode_step_ms"]),
        "decode_steps": len(got["decode_step_ms"]),
        "prefill_chunks": len(got["prefill_chunk_ms"]),
        "decode_gap_ms_mean": mean(got["decode_gaps_ms"]),
        "decode_gaps": len(got["decode_gaps_ms"]),
        "tick_self_ms_mean": mean(got["tick_self_ms"]),
        "bench_tick_ms_mean": mean(got["bench_tick_ms"]),
        "bench_tick_ms_median": (statistics.median(got["bench_tick_ms"])
                                 if got["bench_tick_ms"] else None),
        "bench_ticks": len(got["bench_tick_ms"]),
        "idle_by_span": got["idle_by_span"],
        "idle_share_by_span": {k: v / idle for k, v in
                               got["idle_by_span"].items()} if idle else {},
        "idle_gaps": got["idle_gaps"]}), flush=True)
    if args.fixture:
        with gzip.open(args.fixture, "wt") as f:
            json.dump(fixture(planes, args.fixture_ms, args.source), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
