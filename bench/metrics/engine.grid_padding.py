"""Share of the packed decode grid that is padding: 1 - ``real_items`` /
``grid_items`` of ``Engine.decode_stats`` over the traced window."""
LAYER = "engine and packer"
UNIT = "%"
MOVES = "output_tok_s"


def read(v):
    g = v.counters["grid_items"]
    return 100.0 * (1.0 - v.counters["real_items"] / g) if g else None
