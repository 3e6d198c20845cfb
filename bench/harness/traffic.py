"""One general traffic generator, driven by a mix file ``bench/traffic/<name>.json``.

Every seed gets the same work: the prompt and output lengths are a fixed
set, the quantiles of a clipped lognormal at ``(i + 0.5) / n``, and in an
open loop the gaps between arrivals are the quantiles of an exponential of
the mix's mean rate.  The set is cut into ``STRATA`` strata of neighbouring
values, and the k-th request of every run takes a value of the same
stratum (a fixed pattern); the seed only chooses which member of the
stratum, and draws the token ids.  So runs with different seeds differ in
which request meets which, not in how much there is to do: a window that
admits a handful of new requests sees the same mix of sizes under every
seed.

Mix keys:

- ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the previous one completes) or ``"open"`` (Poisson
  arrivals at ``rate_per_s``, sent whether or not the server keeps up).
- ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of the
  lognormal lengths, in tokens.
- ``requests``: how many requests the fixed set holds; the generator
  cycles through them.
- ``fill``: closed loop only.  ``"residual"`` starts each client in mid
  answer, as a closed loop would be found at a random instant: its first
  request carries a share of its output as prompt and asks for the rest,
  so requests complete all through the window.  The shares are
  ``(i + 0.5) / clients``.
- ``warmup_s``: open loop only.  Seconds of arrivals served before the
  window opens, so the window starts at steady load.
- ``engine``: ``num_slots``, ``max_seq_len``, ``num_kv_blocks``.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

SEED_MOD = 2 ** 63 - 1
STRATA = 8


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one purpose, from any whole ``seed`` (also above
    2**32): streams for lengths, tokens and arrivals do not overlap."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) % SEED_MOD, tag])


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse of the standard normal distribution function (Acklam's
    rational approximation, relative error below 1.2e-9)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1))
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                 * r + b[4]) * r + 1))
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                 + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                            + 1))
    return out


def lognormal_sizes(n: int, spec: dict) -> np.ndarray:
    """The fixed set of ``n`` lengths: lognormal quantiles, clipped."""
    p = (np.arange(n) + 0.5) / n
    x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(p))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, mean_s: float) -> np.ndarray:
    """The fixed set of ``n`` gaps between arrivals: exponential quantiles."""
    p = (np.arange(n) + 0.5) / n
    return -mean_s * np.log1p(-p)


def stratified_order(values: np.ndarray, seed: int, stream: str) -> np.ndarray:
    """``values`` reordered so that position ``k`` always draws from the same
    stratum (a fixed pattern of the ``STRATA`` strata of sorted values,
    each group of ``STRATA`` positions visiting every stratum once) and the
    seed picks the member."""
    v = np.sort(np.asarray(values))
    n = len(v)
    k = min(STRATA, n)
    strata = np.array_split(np.arange(n), k)
    fixed = np.random.default_rng(1729)
    members = [rng_for(seed, f"{stream}/{i}").permutation(st)
               for i, st in enumerate(strata)]
    used = [0] * k
    out = []
    while len(out) < n:
        for s_ in fixed.permutation(k):
            if used[s_] < len(members[s_]):
                out.append(v[members[s_][used[s_]]])
                used[s_] += 1
    return np.asarray(out[:n])


@dataclasses.dataclass
class Item:
    """One request to send: its prompt tokens and how many to generate."""
    index: int
    prompt: np.ndarray
    max_tokens: int


class Mix:
    """A traffic mix under one seed."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.seed = int(seed)
        self.vocab = int(vocab)
        n = int(spec["requests"])
        self.prompt_lens = stratified_order(
            lognormal_sizes(n, spec["prompt"]), seed, "prompt")
        self.output_lens = stratified_order(
            lognormal_sizes(n, spec["output"]), seed, "output")
        self._tokens = rng_for(seed, "tokens")
        self._next = 0
        if spec["loop"] == "open":
            self.gaps = stratified_order(
                exponential_gaps(n, 1.0 / float(spec["rate_per_s"])), seed,
                "gaps")
        # the share of its answer each client is found in: a fixed set,
        # evenly spread, in a fixed order
        c = int(spec.get("clients", 0))
        self.shares = (np.random.default_rng(1730).permutation(c) + 0.5) / c

    @property
    def closed(self) -> bool:
        return self.spec["loop"] == "closed"

    def next_item(self, carried: float = 0.0) -> Item:
        """The next request; ``carried`` moves that share
        of its output into its prompt (a client found in mid answer)."""
        i = self._next
        self._next += 1
        k = i % len(self.prompt_lens)
        p, o = int(self.prompt_lens[k]), int(self.output_lens[k])
        done = int(math.floor(carried * (o - 1)))
        toks = self._tokens.integers(0, self.vocab, size=p + done,
                                     dtype=np.int32)
        return Item(i, toks, o - done)

    def arrival_offsets(self, horizon_s: float) -> np.ndarray:
        """Open loop: offsets (s) of every arrival before ``horizon_s``,
        cycling through the fixed set of gaps."""
        reps = int(horizon_s / float(self.gaps.sum())) + 1
        t = np.cumsum(np.tile(self.gaps, reps))
        return t[t < horizon_s]
