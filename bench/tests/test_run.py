"""Whole runs of the test cell on the CPU, the look for a chip skipped: a
sound run is correct, and a run whose served path is broken, or that runs
the lower-precision control, is not.

The test cell's limits were set from CPU readings of these sizes (seeds
1-9 sound, 1-3 for each fault): sound runs read ``logit_err`` 0.0151-0.0206
and ``logit_err_mean`` 0.0088-0.0095; the fp8 KV control 0.0805-0.0982 and
0.0355-0.0409; logits scaled by 1.05 (no token changes) 0.054 and 0.050;
limits 0.04 and 0.018.  ``gap_max``: sound 0-0.039, the fp8 control
0.118-0.201, a token altered one in seven 6.28; limit 0.06."""
import jax.numpy as jnp
import pytest

import run
from harness import spec, tap
from conftest import TEST_PEAKS


def run_cell(root, name, seed, **kw):
    cell = spec.load_cell(name, root=root)
    return run.run(cell, seed, 3.0, False, require_tpu=False,
                   peaks=TEST_PEAKS, **kw)


def scale_decode_logits(factor):
    """A driver hook that scales every decode step's logits: the order of
    each row, and so every greedy token, stays as it was."""
    def hook(drv):
        eng = drv.eng
        inner = eng._decode_packed_fn

        def packed_fn(bucket):
            fn = inner(bucket)

            def step(*args):
                logits, cache = fn(*args)
                return logits * jnp.float32(factor), cache
            return step
        eng._decode_packed_fn = packed_fn
    return hook


def test_device_gate_refuses_the_cpu(tiny_root):
    cell = spec.load_cell("tiny-closed", root=tiny_root)
    assert run.run(cell, 1, 1.0, False) is None


def test_sound_run_is_correct(tiny_root):
    res = run_cell(tiny_root, "tiny-closed", 2 ** 33 + 5)
    assert res["correct"], res["check"]
    assert res["basis"] == "program_logits"
    assert list(res)[-1] == "check"
    assert res["check"]["served_tokens_compared"]["value"] >= 10
    for k in ("logit_err", "logit_err_mean", "gap_max"):
        assert res["check"][k]["value"] <= res["check"][k]["limit"]
    assert res["check"]["logit_rows_missing"]["value"] == 0
    for k in ("itl_p95_ms", "output_tok_s", "setup_s"):
        assert res["metrics"][k]["value"] > 0


def test_altered_token_is_not_correct(tiny_root):
    res = run_cell(tiny_root, "tiny-closed", 5, fault="token")
    assert not res["correct"]
    assert res["check"]["gap_max"]["value"] > res["check"]["gap_max"]["limit"]


def test_fp8_control_is_not_correct(tiny_root):
    res = run_cell(tiny_root, "tiny-closed", 7, control="fp8")
    assert not res["correct"], res["check"]
    assert res["basis"] == "program_logits"
    c = res["check"]
    assert c["logit_err_mean"]["value"] > c["logit_err_mean"]["limit"]


def test_scaled_logits_fail_the_logit_check_only(tiny_root):
    # 5% on every decode logit changes no token, so the served tokens and
    # their gaps are a sound run's; only the program's logits show it
    res = run_cell(tiny_root, "tiny-closed", 9,
                   driver_hook=scale_decode_logits(1.05))
    c = res["check"]
    assert not res["correct"]
    assert c["gap_max"]["value"] <= c["gap_max"]["limit"]
    assert c["logit_err"]["value"] > c["logit_err"]["limit"]
    assert c["logit_err_mean"]["value"] > c["logit_err_mean"]["limit"]


def without_logit_limits(root, name):
    cell = spec.load_cell(name, root=root)
    for k in ("logit_err_limit", "logit_err_mean_limit"):
        del cell.traffic["check"][k]
    return cell


def test_without_the_tap_decides_on_served_tokens(tiny_root, monkeypatch):
    # a cell that sets no logit limits reads no logits: the gap check
    # alone, on a program with or without the seam
    monkeypatch.setattr(tap, "SEAM", "_no_such_method")
    cell = without_logit_limits(tiny_root, "tiny-closed")
    res = run.run(cell, 2 ** 33 + 5, 3.0, False, require_tpu=False,
                  peaks=TEST_PEAKS)
    assert res["basis"] == "served_tokens"
    assert res["correct"], res["check"]
    assert list(res["check"]) == ["gap_max", "served_tokens_compared",
                                  "failed"]
    bad = run.run(cell, 5, 3.0, False, require_tpu=False, peaks=TEST_PEAKS,
                  fault="token")
    assert bad["basis"] == "served_tokens" and not bad["correct"]


@pytest.mark.parametrize("seam", ["_no_such_method", "shape"])
def test_lost_tap_is_not_correct(tiny_root, monkeypatch, seam):
    # a cell with logit limits on a program whose logits the tap cannot
    # read, because the seam is gone or hands over something else: the
    # run is not correct, and says why, rather than falling back
    if seam == "shape":
        take = tap.LogitTap._take
        monkeypatch.setattr(tap.LogitTap, "_take",
                            lambda self, lg, rs: take(self, lg[:, :-1], rs))
    else:
        monkeypatch.setattr(tap, "SEAM", seam)
    res = run_cell(tiny_root, "tiny-closed", 2 ** 33 + 5)
    c = res["check"]
    assert res["basis"] == "program_logits"
    assert not res["correct"]
    assert c["logit_err"]["value"] is None
    assert c["logit_rows_missing"]["value"] == \
        c["served_tokens_compared"]["value"] > 0
    assert c["gap_max"]["value"] <= c["gap_max"]["limit"]


def test_traced_open_loop_run(tiny_root):
    cell = spec.load_cell("tiny-open", root=tiny_root)
    res = run.run(cell, 11, 3.0, True, require_tpu=False, peaks=TEST_PEAKS)
    assert res["correct"], res["check"]
    # counters are read on any device; the trace's device planes are a
    # TPU's, so on the CPU the trace metrics stay silent rather than 0
    assert 0 < res["metrics"]["sched.decode_rows"]["value"] <= 4
    assert "step.decode_ms" not in res["metrics"]
    assert "step.prefill_chunk_ms" not in res["metrics"]
