"""Share of its roofline the Pallas paged decode kernel reaches: the least
time the chip needs for the kernel's work (the selected K and V tiles at
the unpadded head size over the HBM peak; their operations over the bf16
peak are far smaller) over the kernel's device time in the trace."""
from harness import costs

LAYER = "decode kernel"
UNIT = "%"
MOVES = "output_tok_s"


def read(v):
    t = v.trace and v.trace["kernel_s"]
    items = v.counters["real_items"]
    if not t or not items:
        return None
    block = v.sel.block
    need = max(costs.decode_attention_bytes(v.cfg, items, block, v.kv_bytes)
               / v.peaks["hbm_bytes_per_s"],
               costs.decode_attention_flops(v.cfg, items, block)
               / v.peaks["bf16_flops"])
    return 100.0 * need / t
