"""The operation and byte counters against hand counts at SmolLM2-135M
widths (30 layers, d 576, 9 heads / 3 KV heads of 64, d_ff 1536, vocab
49152, tied)."""
import json

import numpy as np

from harness import costs, families, plan
from conftest import BENCH

CFG = json.loads((BENCH / "configs/smollm2-135m.json").read_text())


def two_block_selection():
    # every head budgeted 256 tokens: two 128-token blocks
    return plan.Selection(np.full((30, 9), 256), group=3, block=128)


def test_matmul_params():
    per_layer = 576 * 64 * (9 + 2 * 3) + 9 * 64 * 576 + 3 * 576 * 1536
    assert families.load(CFG).matmul_params(CFG) == (30 * per_layer, 576 * 49152)
    assert per_layer == 3538944


def test_decode_step_flops_one_row():
    # position 300: three blocks held; a two-block budget reads the sink
    # block whole (128 keys) and the newest up to position 300 (45 keys)
    keys = 30 * 3 * (128 + 45)
    want = 2 * (106168320 + 28311552) + 4 * 3 * 64 * keys
    got = costs.decode_step_flops(CFG, two_block_selection(), [300])
    assert got == want == 280917504


def test_chunk_flops_first_256_tokens():
    # query block 0 reads itself causally (8256 keys over its 128 queries);
    # query block 1 reads block 0 whole and itself causally
    keys = 30 * 9 * (8256 + 128 * 128 + 8256)
    want = 2 * 256 * 106168320 + 2 * 28311552 + 4 * 64 * keys
    got = costs.chunk_flops(CFG, two_block_selection(), 0, 256, True)
    assert got == want == 56688574464


def test_decode_attention_bytes_at_unpadded_head_dim():
    assert costs.decode_attention_bytes(CFG, 1000, 128) == 1000 * 128 * 64 * 4
