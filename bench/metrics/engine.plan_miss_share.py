"""Share of decode ticks whose packed plan was built on the critical path
(``plan_misses`` over ``plan_hits + plan_misses`` of
``Engine.decode_stats``)."""
LAYER = "engine and packer"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(v):
    h, m = v.counters["plan_hits"], v.counters["plan_misses"]
    return 100.0 * m / (h + m) if h + m else None
