"""Whole runs of the test cell on the CPU, the look for a chip skipped: a
sound run is correct, and a run whose served path is broken, or that runs
the lower-precision control, is not.

The test cell's limit (0.06 on the widest gap) was set from CPU readings
of these sizes: sound runs read 0.009-0.037 over six seeds, the fp8 KV
control 0.10-0.16 over three."""
import numpy as np
import pytest

import run
from harness import spec
from conftest import TEST_PEAKS


def run_cell(root, name, seed, **kw):
    cell = spec.load_cell(name, root=root)
    return run.run(cell, seed, 3.0, False, require_tpu=False,
                   peaks=TEST_PEAKS, **kw)


def test_device_gate_refuses_the_cpu(tiny_root):
    cell = spec.load_cell("tiny-closed", root=tiny_root)
    assert run.run(cell, 1, 1.0, False) is None


def test_sound_run_is_correct(tiny_root):
    res = run_cell(tiny_root, "tiny-closed", 2 ** 33 + 5)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert res["check"]["served_tokens_compared"]["value"] >= 10
    for k in ("itl_p95_ms", "output_tok_s", "setup_s"):
        assert res["metrics"][k]["value"] > 0


def test_altered_token_is_not_correct(tiny_root):
    def hook(drv):
        inner = drv._decode
        calls = [0]

        def decode(slots, toks, pos):
            out = np.array(inner(slots, toks, pos))
            calls[0] += 1
            if calls[0] % 7 == 0:     # a token altered where it is made
                out[0] = (out[0] + 1) % 512
            return out
        drv._decode = decode
    res = run_cell(tiny_root, "tiny-closed", 5, driver_hook=hook)
    assert not res["correct"]
    assert res["check"]["gap_max"]["value"] > res["check"]["gap_max"]["limit"]


def test_fp8_control_is_not_correct(tiny_root):
    res = run_cell(tiny_root, "tiny-closed", 7, control="fp8")
    assert not res["correct"], res["check"]


def test_traced_open_loop_run(tiny_root):
    cell = spec.load_cell("tiny-open", root=tiny_root)
    res = run.run(cell, 11, 3.0, True, require_tpu=False, peaks=TEST_PEAKS)
    assert res["correct"], res["check"]
    # counters are read on any device; the trace's device planes are a
    # TPU's, so on the CPU the trace metrics stay silent rather than 0
    assert 0 < res["metrics"]["sched.decode_rows"]["value"] <= 4
    assert "step.decode_ms" not in res["metrics"]
