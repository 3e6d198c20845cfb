"""Share of its roofline a prefill chunk reaches: the least time the chip
needs for the traced chunks, each the larger of its bytes over the HBM
peak and its operations over the bf16 peak, over their device time.

The bytes are those a chunk cannot avoid moving: every layer's weights
once, the output head for a final chunk, the resident K and V blocks its
queries select (for each KV head, the union over its query heads and the
chunk's query blocks) and the chunk's own K and V, written once.  The
operations are ``costs.chunk_flops``.  A chunk program that copied the
whole KV pool would read a few percent here at Yi-6B's width."""
from harness import costs, families

LAYER = "model step"
UNIT = "%"
MOVES = "itl_p95_ms"
WEIGHT_BYTES = 2                     # the weights are served in bfloat16


def chunk_bytes(cfg: dict, sel, q_offset: int, length: int, final: bool,
                kv_bytes: int = 2) -> float:
    """Bytes one chunk of ``length`` real tokens at ``q_offset`` has to
    move."""
    fam = families.load(cfg)
    m, (w, head) = fam.dims(cfg), fam.matmul_params(cfg)
    block, group = sel.block, sel.group
    first = q_offset // block
    qbs = range(first, -(-(q_offset + length) // block))
    resident = 0
    for l in range(m["L"]):
        for kh in range(m["Hkv"]):
            resident += len({b for h in range(kh * group, (kh + 1) * group)
                             for qb in qbs for b in sel.prefill_pairs(l, h, qb)
                             if b < first})
    token_kv = m["Hkv"] * m["Dh"] * 2 * kv_bytes     # K and V, one layer
    return (WEIGHT_BYTES * (w + (head if final else 0))
            + token_kv * (resident * block + m["L"] * length))


def read(v):
    t = v.trace and v.trace["prefill_chunk_ms"]
    if not t or not v.chunk_calls:
        return None
    need = sum(max(chunk_bytes(v.cfg, v.sel, off, n, final, v.kv_bytes)
                   / v.peaks["hbm_bytes_per_s"],
                   costs.chunk_flops(v.cfg, v.sel, off, n, final)
                   / v.peaks["bf16_flops"])
               for n, off, final in v.chunk_calls)
    return 100.0 * need / (sum(t) / 1e3)
