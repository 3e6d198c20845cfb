"""``chip_smoke.py`` off the chip: the device gate refuses the CPU, and its
phases run end to end at the smoke config (jnp executors on CPU)."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.configs.smollm_135m import SMOKE  # noqa: E402
from repro.models.transformer import init_params  # noqa: E402


def test_device_gate_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_kernel_check_phase(kv_dtype):
    r = chip_smoke.kernel_check(kv_dtype, cfg=SMOKE, num_blocks=64, slots=4,
                                max_seq=1024)
    assert r["err"] <= r["bound"] and r["items"] > 0


@pytest.mark.parametrize("extra", [{}, {"attention": "dense"},
                                   {"kv_dtype": "int8"}],
                         ids=["sparse", "dense", "int8"])
def test_serve_phase(extra):
    params = init_params(jax.random.PRNGKey(0), SMOKE)
    prompts = chip_smoke.make_prompts(SMOKE, 3, 130, 600)
    kw = dict(chip_smoke.SERVE_KW, max_seq_len=1024, num_slots=2,
              budget_per_head=256, **extra)
    r = chip_smoke.serve_phase("smoke", SMOKE, params, prompts, max_tokens=4,
                               engine_kw=kw)
    assert r["completed"] == 3 and r["generated_tokens"] == 12


def test_hplb_island_phase_on_four_host_devices():
    script = """
import dataclasses
import chip_smoke
from repro.configs.yi_6b import FULL
cfg = dataclasses.replace(FULL, head_dim=32)
r = chip_smoke.hplb_island_check(cfg=cfg, model=4, slots=2, max_seq=1024)
assert len(r["devices"]) == 4 and r["err"] <= r["bound"], r
print("ISLAND_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    p = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "ISLAND_OK" in p.stdout
