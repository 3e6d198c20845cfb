"""Share of the chip's bf16 peak a decode step reaches: the operations the
traced decode steps require (weights over the active rows, attention over
the selected keys) over their device time in the trace."""
from harness import costs

LAYER = "whole step"
UNIT = "%"
MOVES = "output_tok_s"


def read(v):
    t = v.trace and v.trace["decode_step_ms"]
    if not t or not v.decode_calls:
        return None
    flops = sum(costs.decode_step_flops(v.cfg, v.sel, p)
                for p in v.decode_calls)
    return 100.0 * flops / (sum(t) / 1e3 * v.peaks["bf16_flops"])
