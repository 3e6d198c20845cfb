"""GPipe pipeline-parallel wrapper: schedule correctness on 4 devices."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def test_pipeline_matches_sequential():
    script = """
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.sharding.pipeline import pipeline_apply
mesh = make_mesh((4,), ("pipe",))
S, d = 4, 16
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.standard_normal((S, d, d)).astype(np.float32) * 0.3)
stage_fn = lambda w, x: jnp.tanh(x @ w)
x = jnp.asarray(rng.standard_normal((8, d)).astype(np.float32))
for M in (4, 8):
    fn = pipeline_apply(stage_fn, mesh, microbatches=M)
    with jax.set_mesh(mesh):
        y = jax.jit(lambda ws, x: fn(ws, x))(ws, x)
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ ws[s])
    err = float(jnp.abs(y - ref).max())
    assert err < 1e-6, (M, err)
print("PIPE_OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "PIPE_OK" in p.stdout
