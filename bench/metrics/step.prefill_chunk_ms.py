"""Device time of one prefill chunk (128 or 256 tokens): the mean length of
the programs named ``jit_prefill_chunk*`` in the trace.  Most ticks of the
served cells hold a chunk, and a tick with one sets the tail of the gaps
between tokens."""
LAYER = "model step"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(v):
    t = v.trace and v.trace["prefill_chunk_ms"]
    return sum(t) / len(t) if t else None
