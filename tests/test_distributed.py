"""Multi-device tests: shard_map S-HPLB islands, GSPMD train step, elastic
checkpoint resharding.  Each runs in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the flag must precede
jax import and must not leak into other tests)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(script: str, timeout=420):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       cwd=REPO, capture_output=True, text=True,
                       timeout=timeout)
    assert p.returncode == 0, f"stderr:\n{p.stderr[-3000:]}"
    return p.stdout


def test_hplb_prefill_island_multidevice_matches_dense():
    """4 model shards × 2 data shards: S-HPLB work-list prefill with
    FULL-causal budgets == dense flash attention, heads genuinely sharded."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.attention.worklist_jnp import causal_items
from repro.attention import flash_attention_ref
from repro.core.worklist import worklist_from_budgets
from repro.attention.policies import streaming_policy
from repro.serving.sharded_attention import hplb_prefill_attention
mesh = make_mesh((2, 4), ("data", "model"))
B, H, Hkv, S, D = 2, 8, 4, 512, 32
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, H, S, D))
k = jax.random.normal(ks[1], (B, Hkv, S, D))
v = jax.random.normal(ks[2], (B, Hkv, S, D))
nq = S // 128
# full-causal worklists per device (4 shards x 2 heads)
full = lambda h, nb, nq, nkv: [np.arange(qb + 1) for qb in range(nq)]
wl = worklist_from_budgets(np.full(H, S), num_devices=4, seq_len=S,
                           block=128, policy_fn=full, group_size=2)
items = np.tile(wl.items[:, None], (1, 3, 1, 1))  # [4, L=3 layers, Lpad, 7]
attend = hplb_prefill_attention(mesh)
with jax.set_mesh(mesh):
    o = jax.jit(lambda q, k, v, it: attend(1, q, k, v, it))(
        q, k, v, jnp.asarray(items))
r = jax.vmap(lambda a, b, c: flash_attention_ref(a, b, c, causal=True))(q, k, v)
err = float(jnp.abs(o - r).max())
assert err < 2e-5, err
print("ISLAND_OK", err)
""")
    assert "ISLAND_OK" in out


def test_flash_decode_island_multidevice():
    """Sequence-sharded cache over 4 model shards: budgeted flash-decode
    (all blocks) == dense decode reference."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.serving.sharded_attention import flash_decode_attention
from repro.attention import dense_attention
mesh = make_mesh((2, 4), ("data", "model"))
B, H, Hkv, Smax, D = 2, 8, 4, 1024, 32
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, H, 1, D))
kc = jax.random.normal(ks[1], (B, Hkv, Smax, D))
vc = jax.random.normal(ks[2], (B, Hkv, Smax, D))
nblk = Smax // 128
n_sh = 4
ids = np.full((n_sh, Hkv, nblk // n_sh), -1, np.int32)
for s in range(n_sh):
    for h in range(Hkv):
        ids[s, h] = np.arange(s * (nblk // n_sh), (s + 1) * (nblk // n_sh))
pos = 900
attend = flash_decode_attention(mesh, seq_axes=("model",))
with jax.set_mesh(mesh):
    o = jax.jit(lambda *a: attend(*a, pos))(q, kc, vc, jnp.asarray(ids))
mask = (jnp.arange(Smax) <= pos)[None, None]
r = dense_attention(q, kc, vc, mask=mask[:, :, None])
err = float(jnp.abs(o - r).max())
assert err < 2e-5, err
print("DECODE_OK", err)
""")
    assert "DECODE_OK" in out


def test_flash_decode_paged_island_multidevice():
    """Block-sharded POOL over 4 model shards (DESIGN.md §2.7): each shard
    remaps the global block table to its local pool range; paged budgeted
    flash-decode (all blocks) == dense decode reference."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.serving.sharded_attention import flash_decode_attention_paged
from repro.attention import dense_attention
mesh = make_mesh((2, 4), ("data", "model"))
B, H, Hkv, Smax, D, BLK = 2, 8, 4, 1024, 32, 128
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, H, 1, D))
kc = jax.random.normal(ks[1], (B, Hkv, Smax, D))
vc = jax.random.normal(ks[2], (B, Hkv, Smax, D))
T = Smax // BLK
N = B * T              # 16 pool blocks, 4 per model shard
rng = np.random.default_rng(0)
perm = rng.permutation(N).reshape(B, T).astype(np.int32)
k_pool = np.zeros((N, Hkv, BLK, D), np.float32)
v_pool = np.zeros((N, Hkv, BLK, D), np.float32)
for b in range(B):
    for j in range(T):
        k_pool[perm[b, j]] = np.asarray(kc)[b, :, j*BLK:(j+1)*BLK]
        v_pool[perm[b, j]] = np.asarray(vc)[b, :, j*BLK:(j+1)*BLK]
ids = np.tile(np.arange(T, dtype=np.int32)[None, None], (B, Hkv, 1))
pos = np.array([900, 700], np.int32)   # PER-SLOT positions, batch-sharded
attend = flash_decode_attention_paged(mesh, seq_axes=("model",))
with jax.set_mesh(mesh):
    o = jax.jit(lambda *a: attend(*a))(
        q, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(ids),
        jnp.asarray(perm), jnp.asarray(pos))
mask = (jnp.arange(Smax)[None] <= pos[:, None])[:, None, None]
r = dense_attention(q, kc, vc, mask=mask)
err = float(jnp.abs(o - r).max())
assert err < 2e-5, err
print("PAGED_DECODE_OK", err)
""")
    assert "PAGED_DECODE_OK" in out


def test_swap_gather_scatter_islands_shard_local():
    """Preemption swap islands on a HEAD-SHARDED pool over 4 model shards
    (DESIGN.md §2.10): gather pulls a sequence's blocks off every shard's
    own kv-head slice with NO collective, scatter restores them into fresh
    block ids, and the round trip is bitwise exact."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.serving.sharded_attention import (
    hplb_swap_gather_kv_blocks, hplb_swap_scatter_kv_blocks)
mesh = make_mesh((2, 4), ("data", "model"))
L, N, Hkv, BLK, D = 2, 9, 4, 16, 8      # N = 8 usable + 1 trash block
rng = np.random.default_rng(0)
pool0 = rng.normal(size=(L, 2, N, Hkv, BLK, D)).astype(np.float32)
ids = np.array([5, 2, 7, 8], np.int32)   # trash-padded (8) swap bucket
gather = hplb_swap_gather_kv_blocks(mesh)
scatter = hplb_swap_scatter_kv_blocks(mesh)
with jax.set_mesh(mesh):
    pool, blocks = jax.jit(gather)(jnp.asarray(pool0), ids)
    blocks = np.asarray(jax.device_get(blocks))
    # gather == plain take on the unsharded pool, all kv heads present
    assert np.array_equal(blocks, pool0[:, :, ids]), "gather mismatch"
    # no collective in the lowered gather HLO: the island is shard-local
    hlo = jax.jit(gather).lower(jnp.asarray(pool0), ids).compile()
    txt = hlo.as_text()
    assert "all-gather" not in txt and "all-to-all" not in txt, \
        "swap gather must not communicate"
    # swap-in to DIFFERENT fresh blocks: scatter then re-gather round-trips
    new_ids = np.array([0, 3, 1, 8], np.int32)
    pool2 = jax.jit(scatter)(pool, jnp.asarray(blocks), new_ids)
    back = np.asarray(jax.device_get(pool2))[:, :, new_ids[:3]]
    assert np.array_equal(back, pool0[:, :, ids[:3]]), "scatter mismatch"
print("SWAP_ISLANDS_OK")
""")
    assert "SWAP_ISLANDS_OK" in out


def test_hplb_decode_packed_island_multidevice():
    """Head-parallel COST-PACKED decode island (DESIGN.md §2.8): each of 4
    model shards executes its own packed ragged worklist against its kv-head
    shard of the cache; full-budget selections == dense decode reference."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.worklist import pack_decode_items, pow2_bucket, extend_packed_items
from repro.serving.sharded_attention import hplb_decode_attention_packed
from repro.attention import dense_attention
mesh = make_mesh((2, 4), ("data", "model"))
B, H, Hkv, Smax, D, BLK = 2, 8, 4, 512, 32, 128
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, H, 1, D))
kc = jax.random.normal(ks[1], (B, Hkv, Smax, D))
vc = jax.random.normal(ks[2], (B, Hkv, Smax, D))
nblk = Smax // BLK
ids = np.tile(np.arange(nblk, dtype=np.int32)[None, None], (B, Hkv, 1))
# one kv head per model shard; packed lists pinned to the owner shard,
# kv-head ids remapped shard-LOCAL for the sharded cache slices
wl = pack_decode_items(ids, num_shards=4, block=BLK,
                       shard_of_kvhead=np.arange(Hkv),
                       kvhead_local=True,
                       bucket=pow2_bucket(B * nblk))
pos = np.array([500, 300], np.int32)
attend = hplb_decode_attention_packed(mesh)
with jax.set_mesh(mesh):
    o = jax.jit(lambda *a: attend(*a))(
        q, kc, vc, jnp.asarray(wl.items), jnp.asarray(pos))
mask = (jnp.arange(Smax)[None] <= pos[:, None])[:, None, None]
r = dense_attention(q, kc, vc, mask=mask)
err = float(jnp.abs(o - r).max())
assert err < 2e-5, err
print("PACKED_DECODE_OK", err, wl.lengths.tolist())
""")
    assert "PACKED_DECODE_OK" in out


def test_flash_decode_2d_island_multidevice():
    """2D head x sequence decode island (DESIGN.md §2.11): pool blocks
    striped over ``seq``, kv heads over ``model``, one flash-decoding
    psum merge along ``seq`` only.  Full-selection striped decode == dense
    reference at model=2 x seq in {2, 4}; a slot whose blocks all live on
    ONE stripe leaves every other stripe fully masked (l = 0) and must
    still merge to finite, exact outputs."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh_2d
from repro.serving.sharded_attention import flash_decode_attention_2d
from repro.attention import dense_attention
for n_seq in (2, 4):
    mesh = make_host_mesh_2d(model=2, seq=n_seq, num_kv_heads=4)
    B, H, Hkv, Smax, D, BLK = 2, 8, 4, 1024, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, 1, D))
    kc = jax.random.normal(ks[1], (B, Hkv, Smax, D))
    vc = jax.random.normal(ks[2], (B, Hkv, Smax, D))
    T = Smax // BLK
    N = B * T            # 16 pool blocks, N_loc = N // n_seq per stripe
    rng = np.random.default_rng(1)
    perm = rng.permutation(N).reshape(B, T).astype(np.int32)
    if n_seq == 2:
        # slot 1 maps ONLY stripe-0-owned physical ids [0, N//2): stripe 1
        # is fully masked for it — the l=0 dropout case (and vice versa)
        perm[1] = rng.permutation(N // 2)
        perm[0] = N // 2 + rng.permutation(N // 2)
    k_pool = np.zeros((N, Hkv, BLK, D), np.float32)
    v_pool = np.zeros((N, Hkv, BLK, D), np.float32)
    for b in range(B):
        for j in range(T):
            k_pool[perm[b, j]] = np.asarray(kc)[b, :, j*BLK:(j+1)*BLK]
            v_pool[perm[b, j]] = np.asarray(vc)[b, :, j*BLK:(j+1)*BLK]
    ids = np.tile(np.arange(T, dtype=np.int32)[None, None], (B, Hkv, 1))
    pos = np.array([900, 700], np.int32)
    attend = flash_decode_attention_2d(mesh)
    with jax.set_mesh(mesh):
        o = jax.jit(lambda *a: attend(*a))(
            q, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(ids),
            jnp.asarray(perm), jnp.asarray(pos))
    assert bool(jnp.isfinite(o).all()), "non-finite striped merge"
    mask = (jnp.arange(Smax)[None] <= pos[:, None])[:, None, None]
    r = dense_attention(q, kc, vc, mask=mask)
    err = float(jnp.abs(o - r).max())
    assert err < 2e-5, (n_seq, err)
    print("SEQPAR_2D_OK", n_seq, err)
""")
    assert out.count("SEQPAR_2D_OK") == 2


def test_gspmd_train_step_multidevice_matches_single():
    """jit train step under a (2 data, 4 model) mesh: loss identical to the
    single-device run (GSPMD is semantics-preserving)."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import functools
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.data.synthetic import lm_batch
from repro.models.transformer import TransformerConfig, init_params, loss_fn
from repro.training import AdamWConfig, TrainConfig, make_train_state, make_train_step
from repro.sharding import specs as sh
CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=8,
                        num_kv_heads=4, d_ff=128, vocab_size=256)
tc = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=2))
state = make_train_state(jax.random.PRNGKey(0),
                         lambda r: init_params(r, CFG), tc)
step = make_train_step(functools.partial(loss_fn, cfg=CFG), tc)
b = jax.tree.map(jnp.asarray, lm_batch(0, batch=4, seq_len=64))
# single device
s1, m1 = jax.jit(step)(state, b)
# sharded
mesh = make_mesh((2, 4), ("data", "model"))
pspec = sh.param_specs(jax.eval_shape(lambda: state["params"]), mesh)
with jax.set_mesh(mesh):
    sharded_state = {
        "params": jax.device_put(state["params"], jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspec,
            is_leaf=lambda x: isinstance(x, P))),
        "opt": state["opt"],
    }
    s2, m2 = jax.jit(step)(sharded_state, b)
d = abs(float(m1["loss"]) - float(m2["loss"]))
assert d < 5e-3, d  # bf16 cross-shard reduction-order tolerance
print("GSPMD_OK", d)
""")
    assert "GSPMD_OK" in out


def test_elastic_checkpoint_reshard():
    """Save under a 4-device mesh, restore under 8- and 2-device meshes;
    values identical everywhere (elastic scaling)."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import tempfile
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.training import CheckpointManager
tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((8,))}
d = tempfile.mkdtemp()
mesh4 = make_mesh((4,), ("model",))
t4 = jax.device_put(tree, NamedSharding(mesh4, P("model")))
cm = CheckpointManager(d, keep=1)
cm.save(1, t4)
for n in (8, 2, 1):
    mesh = make_mesh((n,), ("model",))
    sh = {"w": NamedSharding(mesh, P("model")),
          "b": NamedSharding(mesh, P("model"))}
    _, restored = cm.restore_latest(jax.eval_shape(lambda: tree), sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert len(restored["w"].sharding.device_set) == n
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


def test_moe_expert_parallel_multidevice():
    """MoE layer with experts sharded over 4 model shards: same outputs as
    unsharded."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.models.moe import MoEConfig, moe_ffn, moe_init
cfg = MoEConfig(num_experts=8, experts_per_token=2)
p = moe_init(jax.random.PRNGKey(0), 32, 64, cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
y1 = moe_ffn(x, p, cfg)
mesh = make_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh):
    y2 = jax.jit(lambda x, p: moe_ffn(x, p, cfg))(x, p)
err = float(jnp.abs(y1.astype(jnp.float32) - y2.astype(jnp.float32)).max())
assert err < 2e-2, err
print("MOE_OK", err)
""")
    assert "MOE_OK" in out


def test_hplb_repermute_kv_cache_island():
    """Plan-epoch swap on a HEAD-SHARDED cache: the all-gather + local
    take island must equal the single-host kv-head gather for a delta
    that MOVES kv heads across model shards."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.serving.sharded_attention import hplb_repermute_kv_cache
from repro.models.transformer import permute_cache_kv_heads
mesh = make_mesh((8,), ("model",))
L, B, Hkv, S, D = 2, 2, 8, 64, 16
cache = jax.random.normal(jax.random.PRNGKey(0), (L, 2, B, Hkv, S, D))
rng = np.random.default_rng(3)
# per-layer shuffles that move heads BETWEEN shards (1 head per shard)
kv_perm = np.stack([rng.permutation(Hkv) for _ in range(L)])
rep = hplb_repermute_kv_cache(mesh)
with jax.set_mesh(mesh):
    got = jax.jit(lambda c, p: rep(c, p))(cache, jnp.asarray(kv_perm))
want = permute_cache_kv_heads(cache, kv_perm)
err = float(jnp.abs(got - want).max())
assert err == 0.0, err
print("REPERM_OK")
""")
    assert "REPERM_OK" in out
