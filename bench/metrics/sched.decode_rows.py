"""Mean active decode rows per decode tick, counted by the harness around
its calls into the scheduler's decode step."""
LAYER = "scheduler"
UNIT = "rows"
MOVES = "output_tok_s"


def read(v):
    n = v.counters["decode_calls"]
    return v.counters["decode_rows"] / n if n else None
