"""The served path of a small Yi-6B-shaped model against the benchmark's
plain float32 reference, on the CPU.

The model keeps Yi-6B-200K's ratios at a small width: 8 query heads per
KV head, head_dim 128 (the unpadded path), an untied head and RoPE theta
5e6, over 2 layers.  ``Engine`` serves it as a long-document deployment
would: paged pool, 256-token chunked prefill, S-HPLB sparse max-min
budgets, packed decode.  The prompts end in a 128-token and in a
256-token chunk bucket, and decoding rows shave the chunks of a prompt
admitted beside them to 128 tokens.

Every served token's logits, read where the engine hands them to its
numerical check (``bench/harness/tap.py``), are compared with the
reference (``bench/harness/families/llama_reference.py``, the selection
from ``bench/harness/plan.py``) by the benchmark's own statistic: the RMS
error over the reference's RMS spread, at seeded vocabulary ids and the
row's top 8.  The fp8 KV pool, the step below the bf16 the configuration
states, has to fail the same limits.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import families, model, plan  # noqa: E402
from harness.tap import LogitTap, logit_ids  # noqa: E402

CFG = {
    "name": "yi-shaped-tiny",
    "hidden_size": 256, "intermediate_size": 688,
    "num_hidden_layers": 2,
    "num_attention_heads": 16, "num_key_value_heads": 2, "head_dim": 128,
    "vocab_size": 1024, "tie_word_embeddings": False,
    "rope_theta": 5000000.0,
    # the engine's RMSNorm epsilon, so the comparison sees the path alone
    "rms_norm_eps": 1e-06,
    "engine_profile_seed": 0,
    "engine": {
        "attention": "sparse", "budget_per_head": 256, "allocator": "maxmin",
        "partitioner": "best", "block": 128, "floor": 128,
        "cache_layout": "paged", "prefill_mode": "chunked",
        "prefill_chunk_tokens": 256, "decode_worklist": "packed",
        "kv_dtype": "bf16", "prefix_cache": False,
        "num_slots": 4, "max_seq_len": 1024, "num_kv_blocks": 32,
    },
}
SEED = 2654435769
# 640 = 256 + 256 + 128 and 768 = 3 x 256 tokens; 384 is admitted while
# the others decode
PROMPT_LENS = (640, 768, 384)
MAX_TOKENS = 24
# the served path reads 0.0123 at worst and 0.0094 on average here, from
# bf16 weights, activations and pool; twice that leaves room for another
# XLA's rounding, and the fp8 pool reads 0.0648 and 0.0440
LOGIT_ERR_LIMIT = 0.025
LOGIT_ERR_MEAN_LIMIT = 0.018


def _logit_err_rows():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.logit_err_rows


def serve_and_compare(kv_dtype: str) -> np.ndarray:
    """Serve the prompts greedily; the per-token logit errors of every
    request against the reference."""
    from repro.serving import Engine, SamplingParams
    from repro.serving.scheduler import Request
    fam = families.load(CFG)
    m = fam.dims(CFG)
    ecfg = model.engine_config(CFG, {"engine": {}}, {"kv_dtype": kv_dtype})
    eng = Engine(fam.program_config(CFG), fam.make_weights(CFG, SEED), ecfg,
                 profile=model.head_profile(m["L"], m["H"],
                                            CFG["engine_profile_seed"]))
    rng = np.random.default_rng(SEED)
    sp = SamplingParams(max_tokens=MAX_TOKENS)
    reqs = {i: Request(rid=i, sampling=sp, prompt=rng.integers(
                0, m["V"], size=n).astype(np.int32))
            for i, n in enumerate(PROMPT_LENS)}
    batcher = eng.make_batcher()
    ids = logit_ids(SEED, m["V"], 256)
    tap = LogitTap(eng, batcher, reqs, ids)
    for r in reqs.values():
        batcher.submit(r)
    batcher.run(*eng.step_fns(sp))
    assert tap.ok, tap.reason
    sel = plan.Selection(plan.maxmin_budgets(
        m["L"], m["H"], budget_per_head=ecfg.budget_per_head,
        seq_len=ecfg.max_seq_len, block=ecfg.block, floor=ecfg.floor,
        profile_seed=CFG["engine_profile_seed"]),
        group=m["H"] // m["Hkv"], block=ecfg.block)
    ref = fam.Reference(m, fam.make_weights(CFG, SEED), sel)
    err_rows = _logit_err_rows()
    errs = []
    for rid, r in reqs.items():
        n = len(r.generated)
        assert n == MAX_TOKENS
        assert tap.missing(rid, n) == 0
        vals, top_i, top_v = tap.request(rid, n)
        cmp_ids = np.concatenate([np.broadcast_to(ids, (n, len(ids))),
                                  top_i], axis=1)
        _, z_ref = ref.compare(r.prompt, list(r.generated), cmp_ids)
        errs.append(err_rows(np.concatenate([vals, top_v], 1), z_ref))
    return np.concatenate(errs)


@pytest.fixture(scope="module")
def sound():
    return serve_and_compare("bf16")


def test_served_logits_match_the_reference(sound):
    assert np.isfinite(sound).all()
    assert sound.max() < LOGIT_ERR_LIMIT, sound.max()
    assert sound.mean() < LOGIT_ERR_MEAN_LIMIT, sound.mean()


def test_fp8_pool_fails_the_same_limits():
    fp8 = serve_and_compare("fp8")
    assert fp8.max() > LOGIT_ERR_LIMIT, fp8.max()
    assert fp8.mean() > LOGIT_ERR_MEAN_LIMIT, fp8.mean()
