"""Which KV blocks each attention head reads, as the configuration states it.

The configurations run S-HPLB sparse attention: every (layer, query head)
gets a token budget from the max-min allocation over an offline recovery
profile, and the budget picks the key blocks a query reads.  These are part
of what the model computes, so the reference and the FLOP counters need
them, and neither may take them from the program.  This module computes
them on its own, from the configuration file:

- ``synthetic_head_curves``: the assumed offline profile (random weights
  have no real one), ``rec(f) = f ** (1 / (1 + alpha))`` with per-head
  ``alpha`` log-uniform over [0.15, 40] from a fixed generator.
- ``maxmin_budgets``: the paper's max-min budget shifting (S-HPLB section
  3.2): start from the uniform split of ``H * budget_per_head`` tokens and
  move one block from the highest-recovery head to the lowest-recovery
  head while the minimum recovery rises.
- ``prefill_blocks``: the static strided selection a prompt query block
  reads (sink block, the two diagonal blocks, strided middle blocks with a
  head-dependent phase, then the most recent middle blocks).
- ``decode_blocks``: the selection a decoding token reads per KV head
  (the largest budget of the KV head's query heads): the sink block plus
  the most recent blocks, always including the newest.

The arithmetic follows ``repro.core.sparsity.synthetic_head_curves``,
``repro.core.budget.maxmin_allocation`` and
``repro.attention.policies.strided_policy`` step for step, so that floating
point ties break the same way; it imports nothing from them.
"""
from __future__ import annotations

import functools

import numpy as np

GRID = np.unique(np.concatenate([np.array([0.0]), np.logspace(-4, 0, 49)]))


def synthetic_head_curves(num_layers: int, num_heads: int, seed: int = 0,
                          alpha_range=(0.15, 40.0)) -> np.ndarray:
    """``[L, H, len(GRID)]`` recovery curves over the top-fraction grid."""
    rng = np.random.default_rng(12345)
    jitter = np.random.default_rng(seed)
    lo, hi = alpha_range
    alphas = np.exp(rng.uniform(np.log(lo), np.log(hi),
                                size=(num_layers, num_heads)))
    curves = np.empty((num_layers, num_heads, len(GRID)))
    for l in range(num_layers):
        for h in range(num_heads):
            a = max(alphas[l, h] * (1.0 + 0.03 * jitter.standard_normal()),
                    1e-3)
            curves[l, h] = np.clip(np.maximum(GRID, 0.0) ** (1.0 / (1.0 + a)),
                                   0.0, 1.0)
    curves[..., 0] = 0.0
    curves[..., -1] = 1.0
    return curves


def _recovery(curves, seq_len, budgets):
    fr = np.clip(budgets / float(seq_len), 0.0, 1.0)
    return np.array([np.interp(fr[h], GRID, curves[h])
                     for h in range(curves.shape[0])])


def _maxmin_layer(curves, *, total, seq_len, block, floor):
    H = curves.shape[0]
    base = max(floor, int(round(total / H)))
    b = np.clip(np.ceil(np.full(H, base, np.float64) / block) * block,
                floor, seq_len).astype(np.int64)
    # centre the quantized uniform split on the total, round robin
    guard, max_steps = 0, max(1000, 8 * H)
    while b.sum() + block <= total and guard < max_steps:
        guard += 1
        for h in range(H):
            if b[h] + block <= seq_len:
                b[h] += block
                break
        else:
            break
    while b.sum() - block >= total and guard < max_steps:
        guard += 1
        for h in range(H):
            if b[h] - block >= floor:
                b[h] -= block
                break
        else:
            break
    rec = _recovery(curves, seq_len, b)
    for _ in range(100_000):
        recv = int(np.argmin(rec))
        can = b - block >= floor
        can[recv] = False
        if not can.any():
            break
        cand = np.where(can)[0]
        donor = int(cand[np.argmax(rec[cand])])
        if b[recv] + block > seq_len:
            break
        new_d = np.interp((b[donor] - block) / seq_len, GRID, curves[donor])
        new_r = np.interp((b[recv] + block) / seq_len, GRID, curves[recv])
        others = np.delete(rec, [donor, recv])
        others_min = float(others.min()) if others.size else np.inf
        if min(float(new_d), float(new_r), others_min) <= rec[recv] + 1e-12:
            break
        b[donor] -= block
        b[recv] += block
        rec[donor], rec[recv] = new_d, new_r
    return b


def maxmin_budgets(num_layers: int, num_heads: int, *, budget_per_head: int,
                   seq_len: int, block: int, floor: int,
                   profile_seed: int = 0) -> np.ndarray:
    """``[L, H]`` token budgets by query head."""
    curves = synthetic_head_curves(num_layers, num_heads, profile_seed)
    return np.stack([
        _maxmin_layer(curves[l], total=budget_per_head * num_heads,
                      seq_len=seq_len, block=block, floor=floor)
        for l in range(num_layers)])


def budget_blocks(budgets: np.ndarray, block: int) -> np.ndarray:
    return np.maximum(-(-np.asarray(budgets, np.int64) // block), 1)


def prefill_blocks(head: int, nb: int, qb: int, sink: int = 1,
                   local: int = 2) -> list[int]:
    """KV blocks query block ``qb`` of query head ``head`` reads in a
    prompt, under a budget of ``nb`` blocks."""
    n = min(nb, qb + 1)
    sel = set(range(min(sink, n)))
    for i in range(local):
        if len(sel) >= n:
            break
        if qb - i >= 0:
            sel.add(qb - i)
    middle = list(range(sink, qb - local + 1))
    if middle and len(sel) < n:
        stride = max(1, len(middle) // (n - len(sel)))
        for b in middle[head % stride::stride]:
            if len(sel) >= n:
                break
            sel.add(b)
        for b in reversed(middle):
            if len(sel) >= n:
                break
            sel.add(b)
    return sorted(sel)


def decode_blocks(nb: int, nblk: int) -> list[int]:
    """KV blocks a decoding token reads when its sequence holds ``nblk``
    blocks, under a KV-head budget of ``nb`` blocks."""
    n = min(nb, nblk)
    recent = range(max(0, nblk - max(1, n - 1)), nblk)
    return sorted(set(([0] if n > 1 else []) + list(recent)))[:n]


class Selection:
    """Block masks of one configuration: ``[L, H]`` budgets in blocks for
    the prompt, ``[L, Hkv]`` for decoding."""

    def __init__(self, budgets: np.ndarray, *, group: int, block: int):
        self.block = block
        self.group = group
        self.nb = budget_blocks(budgets, block)                   # [L, H]
        L, H = self.nb.shape
        self.nb_kv = budget_blocks(
            budgets.reshape(L, H // group, group).max(axis=2), block)

    @property
    def num_layers(self) -> int:
        return self.nb.shape[0]

    @property
    def num_heads(self) -> int:
        return self.nb.shape[1]

    def masks(self, layer: int, nqb: int):
        """Block-level masks ``(prompt [H, nqb, nqb], decode [H, nqb, nqb])``
        of one layer: entry ``[h, qb, kb]`` says whether a query in block
        ``qb`` reads key block ``kb``, for a prompt query and for a decoding
        token respectively (causality inside a block is applied by the
        caller)."""
        H = self.num_heads
        pm = np.zeros((H, nqb, nqb), bool)
        dm = np.zeros((H, nqb, nqb), bool)
        for h in range(H):
            nb, nbk = int(self.nb[layer, h]), int(self.nb_kv[layer,
                                                             h // self.group])
            for qb in range(nqb):
                pm[h, qb, prefill_blocks(h, nb, qb)] = True
            for qb in range(nqb):
                dm[h, qb, decode_blocks(nbk, qb + 1)] = True
        return pm, dm

    def prefill_pairs(self, layer: int, head: int, qb: int) -> list[int]:
        return _prefill_cached(head, int(self.nb[layer, head]), qb)

    def decode_pairs(self, layer: int, kv_head: int, nblk: int) -> list[int]:
        return _decode_cached(int(self.nb_kv[layer, kv_head]), nblk)


_prefill_cached = functools.lru_cache(maxsize=1 << 16)(
    lambda head, nb, qb: tuple(prefill_blocks(head, nb, qb)))
_decode_cached = functools.lru_cache(maxsize=1 << 16)(
    lambda nb, nblk: tuple(decode_blocks(nb, nblk)))
