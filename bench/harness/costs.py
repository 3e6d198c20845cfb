"""Operations and bytes the configuration requires, from shapes.

Counted as the algorithm needs them, not as the program happens to run
them: only active decode rows, only real chunk tokens, only the keys a
query reads under its selection and causality.  A multiply-add is two
operations.  Bytes are those of the selected KV tiles at the unpadded
head size.  The weights a token passes through are the family's count
(``harness.families``).
"""
from __future__ import annotations

import numpy as np

from harness import families


def decode_attention_keys(sel, positions) -> np.ndarray:
    """``[L]`` keys read per query head group, summed over the rows of one
    decode step: for every row, layer and KV head, the tokens of its
    selected blocks up to the row's position."""
    block = sel.block
    L, Hkv = sel.nb_kv.shape
    out = np.zeros(L, np.int64)
    for p in positions:
        p = int(p)
        nblk = p // block + 1
        for l in range(L):
            for kh in range(Hkv):
                blocks = sel.decode_pairs(l, kh, nblk)
                full = sum(1 for b in blocks if b < nblk - 1)
                out[l] += full * block + (p % block + 1 if nblk - 1 in blocks
                                          else 0)
    return out


def decode_step_flops(cfg: dict, sel, positions) -> float:
    """One decode step over ``positions`` (one per active row)."""
    fam = families.load(cfg)
    m, (w, head) = fam.dims(cfg), fam.matmul_params(cfg)
    G = m["H"] // m["Hkv"]
    keys = decode_attention_keys(sel, positions).sum()
    return 2.0 * len(positions) * (w + head) + 4.0 * G * m["Dh"] * keys


def chunk_attention_keys(sel, q_offset: int, length: int) -> int:
    """Keys read by the ``length`` prompt queries from ``q_offset``, summed
    over layers and query heads."""
    block = sel.block
    L, H = sel.nb.shape
    total = 0
    for qb in range(q_offset // block, -(-(q_offset + length) // block)):
        lo = max(q_offset, qb * block)
        hi = min(q_offset + length, (qb + 1) * block)   # queries [lo, hi)
        nq = hi - lo
        # causal keys inside the diagonal block: sum over q of (q % blk + 1)
        diag = sum(q - qb * block + 1 for q in range(lo, hi))
        for l in range(L):
            for h in range(H):
                blocks = sel.prefill_pairs(l, h, qb)
                off = sum(1 for b in blocks if b < qb)
                total += off * block * nq + (diag if qb in blocks else 0)
    return total


def chunk_flops(cfg: dict, sel, q_offset: int, length: int,
                final: bool) -> float:
    """One prefill chunk of ``length`` real tokens at ``q_offset``; a final
    chunk also computes one row of logits."""
    fam = families.load(cfg)
    m, (w, head) = fam.dims(cfg), fam.matmul_params(cfg)
    keys = chunk_attention_keys(sel, q_offset, length)
    return (2.0 * length * w + (2.0 * head if final else 0.0)
            + 4.0 * m["Dh"] * keys)


def decode_attention_bytes(cfg: dict, real_items: int, block: int,
                           kv_bytes: int = 2) -> float:
    """Bytes of K and V the packed decode kernel has to read for
    ``real_items`` (row, KV head, block) tiles."""
    Dh = families.load(cfg).dims(cfg)["Dh"]
    return float(real_items) * block * Dh * 2 * kv_bytes


def decode_attention_flops(cfg: dict, real_items: int, block: int) -> float:
    """Upper bound of the decode kernel's operations: every tile whole."""
    m = families.load(cfg).dims(cfg)
    return 4.0 * real_items * block * m["Dh"] * (m["H"] // m["Hkv"])
