"""Share of the chip's bf16 peak a prefill chunk reaches: the operations the
traced chunks require (weights over the real tokens, attention over the
selected keys, the logits row of a final chunk) over their device time."""
from harness import costs

LAYER = "whole step"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(v):
    t = v.trace and v.trace["prefill_chunk_ms"]
    if not t or not v.chunk_calls:
        return None
    flops = sum(costs.chunk_flops(v.cfg, v.sel, off, n, final)
                for n, off, final in v.chunk_calls)
    return 100.0 * flops / (sum(t) / 1e3 * v.peaks["bf16_flops"])
