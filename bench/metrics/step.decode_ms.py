"""Device time of one decode step: the mean length of the device programs
that run the Pallas paged decode kernel, from the trace."""
LAYER = "model step"
UNIT = "ms"
MOVES = "output_tok_s"


def read(v):
    t = v.trace and v.trace["decode_step_ms"]
    return sum(t) / len(t) if t else None
