"""The program's own logits, read where the engine hands them to its
numerical check.

The engine calls ``Engine._sentinel_check(logits, row_slots)`` after every
step that samples: with the ``[1, V]`` logits of a final prefill chunk and
``[(0, slot)]``, and with the ``[num_slots, V]`` logits of a decode step and
one ``(slot, slot)`` pair per active row.  The tap wraps that method on the
one engine it serves and, for each call, dispatches one small jitted
program (``logit_tap``): every row's logits at a fixed seeded set of
vocabulary ids and the row's top ``TOP``.  Nothing waits for it inside the
tick; the next call moves the previous call's values to the host, long
finished by then.  Each row is filed under the request bound to the slot
and the index of the token it is about to produce.

A program without that method, or one that passes something else than
2-D logits there, leaves the tap off (``ok`` false, ``reason`` says why):
every sampled token then counts as missing, and a cell that sets logit
limits is not correct.  A change to the program that drops the method has
to hand the logits on by a hook of its own first, for the tap to read.
"""
from __future__ import annotations

import functools
import sys

import numpy as np

SEAM = "_sentinel_check"
TOP = 8
IDS = 1024


def logit_ids(seed: int, vocab: int, n: int = IDS) -> np.ndarray:
    """The run's compared vocabulary ids: ``n`` distinct ids from the
    seed, sorted."""
    from harness.traffic import rng_for
    return np.sort(rng_for(seed, "logit-ids").choice(vocab, min(n, vocab),
                                                     replace=False)
                   ).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _gather_fn():
    import jax
    import jax.numpy as jnp

    def logit_tap(logits, ids):
        z = logits.astype(jnp.float32)
        top_v, top_i = jax.lax.top_k(z, TOP)
        return jnp.take(z, ids, axis=1), top_i, top_v

    return jax.jit(logit_tap)


def gather(logits, ids):
    """``(logits at ids [R, n], top ids [R, TOP], top values [R, TOP])``
    of every row, on the device."""
    return _gather_fn()(logits, ids)


class LogitTap:
    """Files every sampled row's gathered logits by (request, token index)."""

    def __init__(self, eng, batcher, reqs: dict, ids: np.ndarray):
        import jax.numpy as jnp
        self.ids = jnp.asarray(ids)
        self.vocab = int(eng.cfg.vocab_size)
        self.batcher = batcher
        self.reqs = reqs
        self.rows: dict[tuple[int, int], tuple] = {}
        self._pending = None
        self.reason = None
        inner = getattr(eng, SEAM, None)
        self.ok = callable(inner)
        if not self.ok:
            self.reason = f"the engine has no {SEAM}"
            log(f"[tap] {self.reason}: no program logits to compare")
            return

        def seam(logits, row_slots):
            self._take(logits, row_slots)
            return inner(logits, row_slots)
        setattr(eng, SEAM, seam)

    def _take(self, logits, row_slots) -> None:
        if not self.ok:
            return
        if getattr(logits, "ndim", 0) != 2 or logits.shape[1] != self.vocab:
            self.ok = False
            self.reason = (f"{SEAM} got {getattr(logits, 'shape', None)}, "
                           f"not [rows, {self.vocab}] logits")
            log(f"[tap] {self.reason}: no program logits to compare")
            return
        self._settle()
        keys = []
        for row, slot in row_slots:
            rid = self.batcher.rid_of_slot(int(slot))
            if rid in self.reqs:
                keys.append((int(row), rid, len(self.reqs[rid].generated)))
        got = gather(logits, self.ids)
        for a in got:
            a.copy_to_host_async()
        self._pending = (got, keys)

    def _settle(self) -> None:
        if self._pending is None:
            return
        got, keys = self._pending
        self._pending = None
        vals, top_i, top_v = (np.asarray(a) for a in got)
        for row, rid, k in keys:
            self.rows[(rid, k)] = (vals[row], top_i[row], top_v[row])

    def missing(self, rid: int, n: int) -> int:
        """How many of tokens ``0..n-1`` of request ``rid`` have no
        program logits."""
        self._settle()
        if not self.ok:
            return n
        return sum((rid, k) not in self.rows for k in range(n))

    def request(self, rid: int, n: int):
        """``(logits at the ids [n, IDS], top ids [n, TOP], top values
        [n, TOP])`` of tokens ``0..n-1`` of request ``rid``, or None when
        the tap is off or missed one of them."""
        self._settle()
        if not self.ok:
            return None
        got = [self.rows.get((rid, k)) for k in range(n)]
        if any(g is None for g in got):
            return None
        return tuple(np.stack(parts) for parts in zip(*got))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
