"""Share of scheduler ticks that carry a prefill chunk (SchedulerStats
``prefill_chunks`` over the harness's tick count)."""
LAYER = "scheduler"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(v):
    n = v.counters["ticks"]
    return 100.0 * v.counters["prefill_chunks"] / n if n else None
