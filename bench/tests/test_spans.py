"""The program's spans read beside the device trace (``bench/spans.py``):
idle time put down to spans, the host path between decode steps and the
scheduler's own time, on hand-made traces and on traces recorded on a
v5e."""
import gzip
import json

import pytest

import spans
from harness import trace
from conftest import DATA

MS = 1e6
OLD = DATA / "v5e-decode-8k-trace.json.gz"
NEW = DATA / "v5e-decode-8k-spans.json.gz"


def planes(ops, modules, host):
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": modules}},
            "host": host}


def ms(name, a, b):
    return (name, a * MS, (b - a) * MS)


def two_decode_ticks():
    """Two decode ticks, 0-50 and 50-100 ms, then 10 ms outside any
    program span; the device runs a decode step from the end of each
    tick's dispatch to shortly before its wait ends."""
    host = [ms("bench.window", 0, 110),
            ms("sched.tick", 0, 50), ms("sched.decode", 9, 50),
            ms("engine.decode.inputs", 9, 12),
            ms("engine.decode.plan", 12, 14),
            ms("engine.decode.dispatch", 14, 20),
            ms("engine.decode.sample", 20, 21),
            ms("engine.decode.wait", 21, 40),
            ms("engine.sentinel", 40, 48),
            ms("sched.tick", 50, 100), ms("sched.decode", 52, 100),
            ms("engine.decode.inputs", 52, 54),
            ms("engine.decode.dispatch", 54, 60),
            ms("engine.decode.wait", 60, 90),
            ms("engine.sentinel", 90, 98)]
    modules = [ms("jit_decode_packed(1)", 20, 38),
               ms("jit_decode_packed(1)", 60, 88)]
    ops = [ms("fusion.1", 20, 30), ms("_flash_decode_paged_kernel", 30, 38),
           ms("fusion.1", 60, 70), ms("_flash_decode_paged_kernel", 70, 88)]
    return planes(ops, modules, host)


def test_idle_time_put_down_to_program_spans():
    r = spans.reduce(two_decode_ticks())
    assert r["busy_s"] == pytest.approx(0.046)
    assert r["decode_step_ms"] == [pytest.approx(18.0), pytest.approx(28.0)]
    # idle 0-20, 38-60 and 88-110 ms, split at every span edge
    want = {"sched.tick": 11, "engine.decode.inputs": 5,
            "engine.decode.plan": 2, "engine.decode.dispatch": 12,
            "engine.decode.wait": 4, "engine.sentinel": 16,
            "sched.decode": 4, "harness": 10}
    got = {k: round(v * 1e3, 6) for k, v in r["idle_by_span"].items()}
    assert got == want
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # each gap named by the innermost span open at its midpoint (10, 49
    # and 99 ms)
    assert [(what, round(t * 1e3)) for what, t in r["idle_gaps"]] == [
        ("sched.decode", 22), ("sched.decode", 22),
        ("engine.decode.inputs", 20)]


def test_decode_gap_and_tick_self_time():
    r = spans.reduce(two_decode_ticks())
    # end of the first wait (40 ms) to the end of the next dispatch (60)
    assert r["decode_gaps_ms"] == [pytest.approx(20.0)]
    # ticks less their engine spans: 50 - 39 and 50 - 46 ms
    assert r["tick_self_ms"] == [pytest.approx(11.0), pytest.approx(4.0)]


def test_decode_gap_skips_a_prefill_chunk_between_steps():
    host = [("engine.decode.dispatch", 0, 5), ("engine.decode.wait", 5, 15),
            ("sched.prefill_step", 16, 22), ("engine.decode.dispatch", 20, 25),
            ("engine.decode.wait", 25, 35), ("engine.decode.dispatch", 40, 42)]
    # 15 -> 25 holds a prefill chunk; 35 -> 42 does not
    assert spans.decode_gaps_ms(
        [(n, a * MS, b * MS) for n, a, b in host]) == [pytest.approx(7.0)]


def test_prefill_chunk_counted_by_program_name():
    host = [ms("bench.window", 0, 100)]
    modules = [ms("jit_decode_packed(1)", 10, 30),
               ms("jit_prefill_chunk(2)", 40, 60),
               ms("jit__argmax(3)", 61, 62)]
    ops = [ms("_flash_decode_paged_kernel", 10, 30), ms("fusion.4", 40, 60),
           ms("argmax", 61, 62)]
    p = planes(ops, modules, host)
    assert trace.reduce_planes(p)["prefill_chunk_ms"] == [pytest.approx(20.0)]
    assert spans.reduce(p)["prefill_chunk_ms"] == [pytest.approx(20.0)]


def test_fixture_keeps_the_first_milliseconds():
    fx = spans.fixture(two_decode_ticks(), 60.0, "hand-made")
    assert fx["host"][0] == ["bench.window", 0.0, 60 * MS]
    assert all(s + d <= 60 * MS for _, s, d in fx["host"])
    assert fx["reduced"]["decode_steps"] == 1
    assert fx["reduced"]["busy_s"] == pytest.approx(0.018)
    assert sum(fx["reduced"]["idle_by_span"].values()) == pytest.approx(0.042)


def _recorded(path):
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    return rec, {"devices": rec["devices"],
                 "host": [tuple(e) for e in rec["host"]]}


def test_old_keys_read_the_same_on_the_first_v5e_trace():
    _, p = _recorded(OLD)
    old = trace.reduce_planes(p)
    new = spans.reduce(p)
    assert {k: new[k] for k in old} == old
    assert set(new["idle_by_span"]) <= {"decode call", "schedule", "harness"}
    assert sum(new["idle_by_span"].values()) == pytest.approx(
        new["window_s"] - new["busy_s"])


def test_program_spans_share_the_device_clock():
    """Each decode program starts after its dispatch span opened and ends
    before the next wait span closed."""
    rec, p = _recorded(NEW)
    r = spans.reduce(p)
    assert r["busy_s"] == pytest.approx(rec["reduced"]["busy_s"])
    host = p["host"]
    dispatch = sorted(s for n, s, d in host if n == "engine.decode.dispatch")
    waits = sorted((s, s + d) for n, s, d in host
                   if n == "engine.decode.wait")
    mods = [(s, s + d) for n, s, d in p["devices"]["/device:TPU:0"][
        "XLA Modules"] if n.startswith("jit_decode_packed(")]
    assert len(mods) == rec["reduced"]["decode_steps"] >= 2
    for s, e in mods:
        opened = [d for d in dispatch if d <= s]
        assert opened, "a decode program before any dispatch span"
        nxt = [w for w in waits if w[0] >= opened[-1]]
        assert nxt and e <= nxt[0][1]
    # the program's spans hold nearly all the idle time; what is left lies
    # in the harness's wrappers, between the program's spans
    idle = r["window_s"] - r["busy_s"]
    program = sum(t for what, t in r["idle_by_span"].items()
                  if what.startswith(("engine.", "sched.")))
    assert program > 0.9 * idle
    assert r["decode_gaps_ms"] and r["tick_self_ms"]
