"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests must see the single
real CPU device (the 512-device flag is dry-run-only).  Multi-device tests
spawn subprocesses that set the flag before importing jax."""
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def recycle_pool():
    """``recycle(engine)``: serve a warm-up batch on the engine whose
    sequences grow into new blocks at different ticks.  Their tables
    interleave, so the pool's free list comes back out of order and its
    blocks still hold their K/V: the sequences a test serves next map
    recycled, scattered block ids."""
    from repro.serving import SamplingParams

    def recycle(eng):
        blk, n = eng.ecfg.block, eng.kv.num_blocks
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, eng.cfg.vocab_size, size=(blk - 2 * i,))
                   for i in range(1, min(3, n // 2) + 1)]
        eng.serve(prompts, SamplingParams(max_tokens=8))
        ids = eng.kv.alloc.free_ids()
        assert len(ids) == n, "warm-up left blocks mapped"
        assert ids not in (sorted(ids), sorted(ids, reverse=True)), \
            "warm-up left the free list in order"
        return eng
    return recycle
