"""Device time of one prefill chunk (128 or 256 tokens): the mean length of
the other step programs of the decode step's name, from the trace."""
LAYER = "model step"
UNIT = "ms"
MOVES = "ttft_p50_ms"


def read(v):
    t = v.trace and v.trace["prefill_chunk_ms"]
    return sum(t) / len(t) if t else None
