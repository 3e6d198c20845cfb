"""End-to-end numbers of one measured window, from host-clock stamps.

A request's clock starts when it was due: when its client's previous
request completed (closed loop) or at its scheduled arrival (open loop),
not when the harness got round to submitting it.  Every number is taken
over all requests and all the time of the window:

- TTFT: every request due in the window, from due to its first token.  A
  request with no first token when the window closes counts with its age
  then, so a stall cannot hide.
- Gaps between output tokens: every gap that ends in the window, across
  all requests, plus, for each request still generating at the close, the
  open gap since its last token.
- Output tokens per second: tokens stamped in the window over its length.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Record:
    """What the window saw of one request."""
    rid: int
    due: float
    submitted: float
    prompt_len: int
    max_tokens: int
    token_times: list
    tokens: list
    finished: bool = False
    refused: bool = False
    t_done: float | None = None
    prompt: object = None


def percentile(values, q: float) -> float | None:
    """``q``-th percentile, linear between closest ranks (numpy's
    default); None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_samples(records, t0: float, t1: float) -> list[float]:
    out = []
    for r in records:
        if not (t0 <= r.due < t1):
            continue
        first = r.token_times[0] if r.token_times else None
        out.append((first if first is not None and first <= t1 else t1)
                   - r.due)
    return out


def itl_samples(records, t0: float, t1: float) -> list[float]:
    out = []
    for r in records:
        ts = [t for t in r.token_times if t <= t1]
        for a, b in zip(ts, ts[1:]):
            if b > t0:
                out.append(b - a)
        if ts and not r.finished and not r.refused and ts[-1] >= t0:
            out.append(t1 - ts[-1])
    return out


def output_tokens(records, t0: float, t1: float) -> int:
    return sum(1 for r in records for t in r.token_times if t0 < t <= t1)


def lateness(records, t0: float, t1: float) -> list[float]:
    """How late the generator sent each request due in the window."""
    return [r.submitted - r.due for r in records if t0 <= r.due < t1]


def window_metrics(records, t0: float, t1: float) -> dict:
    """The cell's end-to-end numbers (ms, tokens/s) and their sample
    counts."""
    ttft = ttft_samples(records, t0, t1)
    itl = itl_samples(records, t0, t1)
    late = lateness(records, t0, t1)
    span = t1 - t0
    ms = lambda v: None if v is None else 1e3 * v
    return {
        "ttft_p50_ms": ms(percentile(ttft, 50)),
        "ttft_p95_ms": ms(percentile(ttft, 95)),
        "itl_p95_ms": ms(percentile(itl, 95)),
        "itl_p50_ms": ms(percentile(itl, 50)),
        "output_tok_s": output_tokens(records, t0, t1) / span,
        "ttft_n": len(ttft),
        "itl_n": len(itl),
        "window_s": span,
        "late_p50_ms": ms(percentile(late, 50)),
        "late_max_ms": ms(max(late)) if late else None,
    }
