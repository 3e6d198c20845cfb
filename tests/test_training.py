"""Training substrate: convergence, determinism, checkpoint fault tolerance,
microbatch equivalence, gradient compression."""
import functools
import os
import signal
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data.synthetic import lm_batch
from repro.models.transformer import TransformerConfig, init_params, loss_fn
from repro.training import (
    AdamWConfig,
    CheckpointManager,
    TrainConfig,
    compress_decompress,
    make_train_state,
    make_train_step,
)

REPO = Path(__file__).resolve().parents[1]

CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, d_ff=128, vocab_size=260)


def _mk(tcfg=None):
    tcfg = tcfg or TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=5))
    state = make_train_state(jax.random.PRNGKey(0),
                             lambda r: init_params(r, CFG), tcfg)
    step = jax.jit(make_train_step(functools.partial(loss_fn, cfg=CFG), tcfg))
    return state, step, tcfg


def _batches(n, batch=4, seq=64):
    return [jax.tree.map(jnp.asarray, lm_batch(i, batch=batch, seq_len=seq))
            for i in range(n)]


class TestConvergence:
    def test_loss_decreases(self):
        state, step, _ = _mk()
        losses = []
        for b in _batches(30):
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1

    def test_deterministic_replay(self):
        """Same seed + same batches -> bitwise identical training. The basis
        of restart-consistency."""
        s1, step, _ = _mk()
        s2, _, _ = _mk()
        for b in _batches(3):
            s1, _ = step(s1, b)
            s2, _ = step(s2, b)
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestMicrobatch:
    def test_accumulation_matches_full_batch(self):
        """mean-of-microbatch grads == full-batch grads (same update)."""
        tc1 = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=5),
                          microbatches=1)
        tc4 = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=5),
                          microbatches=4)
        s1, step1, _ = _mk(tc1)
        s4, step4, _ = _mk(tc4)
        b = _batches(1, batch=8)[0]
        s1, m1 = step1(s1, b)
        s4, m4 = step4(s4, b)
        assert float(m1["loss"]) == pytest.approx(float(m4["loss"]),
                                                  rel=1e-5)
        for a, c in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s4["params"])):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(c, np.float32),
                atol=3e-3)


class TestCompression:
    def test_error_feedback_unbiased_over_steps(self):
        """Error feedback: accumulated (deq + err) equals the true gradient
        sum to quantization precision — the EF-SGD guarantee."""
        rng = np.random.default_rng(0)
        g_true = [jnp.asarray(rng.standard_normal((32, 32)) * 1e-3)
                  for _ in range(5)]
        err = {"g": jnp.zeros((32, 32))}
        total_deq = jnp.zeros((32, 32))
        for g in g_true:
            deq, err_new = compress_decompress({"g": g}, err)
            total_deq = total_deq + deq["g"]
            err = err_new
        total_true = sum(g_true)
        resid = total_deq + err["g"] - total_true
        assert float(jnp.abs(resid).max()) < 1e-5

    def test_training_with_compression_converges(self):
        tc = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=5),
                         compress_grads=True)
        state, step, _ = _mk(tc)
        losses = []
        for b in _batches(25):
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1


class TestCheckpoint:
    def test_resume_bitwise_identical(self, tmp_path):
        """Train 6 steps straight vs train 3 + checkpoint + restore + 3."""
        batches = _batches(6)
        sa, step, _ = _mk()
        for b in batches:
            sa, _ = step(sa, b)

        sb, step2, _ = _mk()
        cm = CheckpointManager(str(tmp_path), keep=2)
        for b in batches[:3]:
            sb, _ = step2(sb, b)
        cm.save(3, sb)
        template = jax.eval_shape(lambda: sb)
        _, sb2 = cm.restore_latest(template)
        for b in batches[3:]:
            sb2, _ = step2(sb2, b)
        for a, b_ in zip(jax.tree.leaves(sa["params"]),
                         jax.tree.leaves(sb2["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def test_atomic_no_partial_files(self, tmp_path):
        state, _, _ = _mk()
        cm = CheckpointManager(str(tmp_path), keep=1)
        cm.save(1, state, blocking=False)
        cm.wait()
        files = os.listdir(tmp_path)
        assert not any(".tmp" in f for f in files)
        assert cm.latest_step() == 1

    def test_keep_n_gc(self, tmp_path):
        state, _, _ = _mk()
        cm = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            cm.save(s, {"x": jnp.zeros(3)})
        assert cm.steps() == [3, 4]

    def test_failure_injection_mid_save(self, tmp_path):
        """A crash DURING save must leave the previous checkpoint loadable:
        simulate by writing a corrupt .tmp alongside a good checkpoint."""
        state, _, _ = _mk()
        cm = CheckpointManager(str(tmp_path), keep=3)
        cm.save(1, state)
        # simulated crash: partial temp file from a dying writer
        with open(os.path.join(str(tmp_path),
                               "step_0000000002.npz.tmp.999"), "wb") as f:
            f.write(b"garbage")
        assert cm.latest_step() == 1
        template = jax.eval_shape(lambda: state)
        step, restored = cm.restore_latest(template)
        assert step == 1
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(restored["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFailureRecoveryEndToEnd:
    def test_killed_worker_resumes_identically(self, tmp_path):
        """Launch a real training subprocess, SIGKILL it mid-run, relaunch,
        and verify the final params equal an uninterrupted run's."""
        script = f"""
import sys, functools
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.data.synthetic import lm_batch
from repro.models.transformer import TransformerConfig, init_params, loss_fn
from repro.training import *
CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, d_ff=128, vocab_size=260)
tc = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=5))
state = make_train_state(jax.random.PRNGKey(0),
                         lambda r: init_params(r, CFG), tc)
step_fn = jax.jit(make_train_step(
    functools.partial(loss_fn, cfg=CFG), tc))
cm = CheckpointManager({str(tmp_path)!r}, keep=2)
start, restored = cm.restore_latest(jax.eval_shape(lambda: state))
if restored is not None:
    state = restored
else:
    start = 0
import os
for i in range(start, 8):
    b = jax.tree.map(jnp.asarray, lm_batch(i, batch=4, seq_len=64))
    state, _ = step_fn(state, b)
    cm.save(i + 1, state)
    print("STEP", i + 1, flush=True)
    if i + 1 == {'{}'.format(4)} and os.environ.get("CRASH") == "1":
        os.kill(os.getpid(), 9)
np.save({str(tmp_path)!r} + "/final.npy",
        np.asarray(jax.tree.leaves(state["params"])[0], np.float32))
"""
        env = dict(os.environ, CRASH="1", PYTHONPATH="src")
        p1 = subprocess.run([sys.executable, "-c", script], env=env,
                            cwd=REPO, capture_output=True, text=True,
                            timeout=300)
        assert p1.returncode != 0  # it crashed (SIGKILL)
        env2 = dict(os.environ, CRASH="0", PYTHONPATH="src")
        p2 = subprocess.run([sys.executable, "-c", script], env=env2,
                            cwd=REPO, capture_output=True, text=True,
                            timeout=300)
        assert p2.returncode == 0, p2.stderr[-2000:]
        resumed = np.load(str(tmp_path) + "/final.npy")

        # uninterrupted reference in-process
        state, step_fn, _ = _mk()
        for i in range(8):
            b = jax.tree.map(jnp.asarray,
                             lm_batch(i, batch=4, seq_len=64))
            state, _ = step_fn(state, b)
        ref = np.asarray(jax.tree.leaves(state["params"])[0], np.float32)
        np.testing.assert_array_equal(resumed, ref)
