"""The reduction from a trace to busy time, step and kernel times and idle
gaps, on a hand-made trace and on a short trace recorded on a v5e."""
import gzip
import json

import pytest

from harness import trace
from conftest import DATA

MS = 1e6


def planes(ops, modules, host):
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": modules}},
            "host": host}


def test_hand_made_trace():
    host = [("bench.window", 0.0, 100 * MS),
            ("bench.tick", 0.0, 50 * MS), ("bench.decode", 20 * MS, 30 * MS),
            ("bench.tick", 50 * MS, 50 * MS),
            ("bench.prefill", 52 * MS, 8 * MS)]
    modules = [("jit_decode_packed(123)", 25 * MS, 10 * MS),
               ("jit_prefill_chunk(456)", 60 * MS, 20 * MS),
               ("jit_argmax", 36 * MS, 1 * MS)]
    ops = [("fusion.1", 25 * MS, 4 * MS),
           ("_flash_decode_paged_kernel", 29 * MS, 6 * MS),
           ("argmax", 36 * MS, 1 * MS),
           ("fusion.2", 60 * MS, 20 * MS),
           ("fusion.3", 61 * MS, 2 * MS)]          # nested in fusion.2
    r = trace.reduce_planes(planes(ops, modules, host))
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.031)       # 10 + 1 + 20 ms
    assert r["decode_step_ms"] == [pytest.approx(10.0)]
    assert r["prefill_chunk_ms"] == [pytest.approx(20.0)]
    assert r["kernel_s"] == pytest.approx(0.006)
    # gaps, each named by the span open at its midpoint: 0-25 ms (in the
    # tick, before the decode call opened), 35-36 and 37-60 ms (the decode
    # call, 20-50 ms), 80-100 ms (the second tick after its prefill call)
    gaps = {round(t * 1e3): what for what, t in r["idle_gaps"]}
    assert gaps == {25: "schedule", 1: "decode call", 23: "decode call",
                    20: "schedule"}
    # ranked by self time: fusion.2 less the fusion.3 inside it
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(0.018)]


def test_chunk_programs_by_name():
    # the engine's step names: decode by its kernel, chunks (sparse and
    # dense) by name; the tap's gather and a merge are neither
    host = [("bench.window", 0.0, 100 * MS)]
    modules = [("jit_decode_packed(9)", 0.0, 10 * MS),
               ("jit_logit_tap", 11 * MS, 1 * MS),
               ("jit_prefill_chunk(7)", 20 * MS, 13 * MS),
               ("jit_prefill_chunk_dense(8)", 40 * MS, 5 * MS),
               ("jit_merge_staging", 50 * MS, 2 * MS),
               ("jit_decode_padded(3)", 60 * MS, 4 * MS)]
    ops = [("_flash_decode_paged_kernel", 1 * MS, 8 * MS),
           ("fusion.1", 11 * MS, 1 * MS), ("while.46", 20 * MS, 13 * MS),
           ("fusion.2", 40 * MS, 5 * MS), ("copy.1", 50 * MS, 2 * MS),
           ("fusion.3", 60 * MS, 4 * MS)]
    r = trace.reduce_planes(planes(ops, modules, host))
    assert r["decode_step_ms"] == [pytest.approx(10.0)]
    assert r["prefill_chunk_ms"] == [pytest.approx(13.0), pytest.approx(5.0)]


def test_no_window_span_reads_nothing():
    assert trace.reduce_planes(planes([], [], [])) is None


def test_recorded_v5e_trace():
    path = DATA / "v5e-decode-8k-trace.json.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    got = trace.reduce_planes({"devices": rec["devices"],
                               "host": [tuple(e) for e in rec["host"]]})
    want = rec["reduced"]
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < got["busy_s"] < got["window_s"]
    assert len(got["decode_step_ms"]) == want["decode_steps"]
    assert got["kernel_s"] > 0
