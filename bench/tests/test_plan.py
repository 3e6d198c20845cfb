"""The benchmark's own budgets and block selections agree with the
program's, at the benchmark's configurations."""
import json

import numpy as np
import pytest

from harness import plan
from conftest import BENCH


@pytest.mark.parametrize("name,max_seq", [("smollm2-135m", 8192),
                                          ("yi-6b-200k", 32768)])
def test_budgets_match_the_engine_plan(name, max_seq):
    from repro.core.planner import make_plan
    from repro.core.sparsity import synthetic_head_curves
    cfg = json.loads((BENCH / f"configs/{name}.json").read_text())
    L, H = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    mine = plan.maxmin_budgets(L, H, budget_per_head=512, seq_len=max_seq,
                               block=128, floor=128)
    theirs = make_plan(synthetic_head_curves(L, H, seed=0), num_devices=1,
                       num_kv_heads=cfg["num_key_value_heads"],
                       seq_len=max_seq, total_budget_per_head=512,
                       block=128, floor=128, allocator="maxmin",
                       partitioner="best")
    for l in range(L):
        np.testing.assert_array_equal(theirs.layers[l].perm, np.arange(H))
        np.testing.assert_array_equal(mine[l], theirs.layers[l].budgets)


def test_prefill_selection_matches_strided_policy():
    from repro.attention.policies import strided_policy
    for head in range(5):
        for nb in (1, 2, 3, 7, 20):
            got = strided_policy(head, nb, 40, 40)
            for qb in range(40):
                assert plan.prefill_blocks(head, nb, qb) == list(got[qb])


def test_decode_selection_matches_engine():
    from repro.core.sparsity import synthetic_head_curves
    from repro.models.transformer import TransformerConfig
    from repro.serving import Engine, EngineConfig
    cfg = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                            num_kv_heads=2, d_ff=64, vocab_size=64,
                            head_dim=16)
    ec = EngineConfig(budget_per_head=256, max_seq_len=2048, num_slots=1,
                      num_kv_blocks=16)
    import jax
    from repro.models.transformer import init_params
    eng = Engine(cfg, init_params(jax.random.PRNGKey(0), cfg), ec,
                 profile=synthetic_head_curves(2, 4))
    budgets = plan.maxmin_budgets(2, 4, budget_per_head=256, seq_len=2048,
                                  block=128, floor=128)
    sel = plan.Selection(budgets, group=2, block=128)
    for nblk in range(1, 17):
        ids = eng.decode_block_ids(nblk * 128)
        for l in range(2):
            for kh in range(2):
                want = [int(b) for b in ids[l, kh] if b >= 0]
                assert list(sel.decode_pairs(l, kh, nblk)) == want
