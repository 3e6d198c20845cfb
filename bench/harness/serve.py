"""Drives the system's served path: ``Engine.make_batcher()`` and
``Engine.step_fns()``, ticked here through ``ContinuousBatcher.submit`` and
``tick``, with the mix's requests sent on their due times.

The two step closures are wrapped so the harness can annotate its calls
(``bench.prefill``, ``bench.decode``, and ``bench.tick`` around a tick) and
count what passes through them: decode rows and positions, chunk offsets
and lengths.
"""
from __future__ import annotations

import time

import numpy as np

from harness.stats import Record


class Driver:
    def __init__(self, eng, mix, clock=time.monotonic):
        import jax
        from repro.serving.sampler import SamplingParams
        self._ann = jax.profiler.TraceAnnotation
        self._sp = SamplingParams
        self.eng = eng
        self.mix = mix
        self.clock = clock
        self.batcher = eng.make_batcher()
        self._prefill, self._decode = eng.step_fns(SamplingParams())
        self.reqs = {}
        self.records: dict[int, Record] = {}
        self.ticks = 0
        self.decode_calls: list[np.ndarray] = []
        self.chunk_calls: list[tuple[int, int, bool]] = []
        self.arrivals = np.zeros(0)
        self._next_arrival = 0

    # -- the wrapped step closures ------------------------------------------
    def prefill(self, toks, slot, q_offset, is_final, prompt_len):
        with self._ann("bench.prefill"):
            self.chunk_calls.append((int(toks.shape[-1]), int(q_offset),
                                     bool(is_final)))
            return self._prefill(toks, slot, q_offset, is_final, prompt_len)

    def decode(self, slots, toks, pos):
        with self._ann("bench.decode"):
            self.decode_calls.append(np.array(pos, np.int64))
            return self._decode(slots, toks, pos)

    # -- requests -------------------------------------------------------------
    def submit(self, item, due: float) -> None:
        from repro.serving.scheduler import Request
        req = Request(rid=item.index, prompt=item.prompt,
                      sampling=self._sp(max_tokens=item.max_tokens))
        self.batcher.submit(req)
        self.reqs[req.rid] = req
        self.records[req.rid] = Record(
            rid=req.rid, due=due, submitted=self.clock(),
            prompt_len=len(item.prompt), max_tokens=item.max_tokens,
            token_times=req.token_times, tokens=req.generated,
            prompt=item.prompt)

    def tick(self) -> None:
        with self._ann("bench.tick"):
            finished = self.batcher.tick(self.prefill, self.decode)
            self.eng.on_tick(self.batcher)
        self.ticks += 1
        now = self.clock()
        for req in finished:
            rec = self.records[req.rid]
            rec.finished = bool(req.done and not req.failed)
            rec.refused = bool(req.rejected or req.failed)
            rec.t_done = req.t_done if req.t_done is not None else now
            if self.mix.closed:
                self.submit(self.mix.next_item(), rec.t_done)

    def _send_due(self, now: float) -> None:
        while (self._next_arrival < len(self.arrivals)
               and self.arrivals[self._next_arrival] <= now):
            self.submit(self.mix.next_item(),
                        float(self.arrivals[self._next_arrival]))
            self._next_arrival += 1

    # -- phases ---------------------------------------------------------------
    def start_closed(self) -> None:
        """Every client sends its first request, found in mid answer when
        the mix asks for it; ticks until each has its first token."""
        fill = self.mix.spec.get("fill") == "residual"
        now = self.clock()
        for c in range(int(self.mix.spec["clients"])):
            share = float(self.mix.shares[c]) if fill else 0.0
            self.submit(self.mix.next_item(carried=share), now)
        while self.batcher.pending or self.batcher.prefilling is not None:
            self.tick()

    def start_open(self, t_origin: float, horizon_s: float) -> None:
        """Schedules the Poisson arrivals from ``t_origin``."""
        self.arrivals = t_origin + self.mix.arrival_offsets(horizon_s)
        self._next_arrival = 0

    def run_until(self, t_end: float) -> None:
        while True:
            now = self.clock()
            if now >= t_end:
                return
            self._send_due(now)
            if not self.batcher.busy:
                nxt = (self.arrivals[self._next_arrival]
                       if self._next_arrival < len(self.arrivals) else t_end)
                time.sleep(max(0.0, min(nxt, t_end) - now))
                continue
            self.tick()

    def counters(self) -> dict:
        """Cumulative counts of the scheduler, engine and harness."""
        st = self.batcher.stats
        ds = self.eng.decode_stats
        return {
            "ticks": self.ticks,
            "prefill_chunks": st.prefill_chunks,
            "decode_steps": st.decode_steps,
            "decode_calls": len(self.decode_calls),
            "chunk_calls": len(self.chunk_calls),
            "decode_rows": int(sum(len(p) for p in self.decode_calls)),
            "real_items": ds["real_items"],
            "grid_items": ds["grid_items"],
            "plan_hits": ds["plan_hits"],
            "plan_misses": ds["plan_misses"],
        }


def warm_steps(eng, on_logits=None) -> list[int]:
    """Compiles and runs once every packed decode program the engine can
    pick (each item bucket up to its cap) and both prefill chunk buckets,
    on inactive rows whose writes land in the pool's trash block, and
    ``on_logits`` on each program's logits (the harness's own programs
    over them compile here too).  Returns the decode buckets."""
    import jax.numpy as jnp
    from repro.core.worklist import pow2_bucket
    from repro.kernels.sparse_decode import DEC_FIELDS
    ec = eng.ecfg
    ns, L = ec.num_slots, eng.cfg.num_layers
    cap = eng._packed_item_cap()
    buckets = sorted({pow2_bucket(n, lo=8, hi=cap)
                      for n in [8 << i for i in range(32) if 8 << i < cap]
                      + [cap]})
    zeros = jnp.zeros((ns,), jnp.int32)
    table = jnp.full((ns, eng.kv.table_width), -1, jnp.int32)
    idle = jnp.zeros((ns,), bool)
    for b in buckets:
        fn = eng._decode_packed_fn(b)
        items = jnp.zeros((L, b, DEC_FIELDS), jnp.int32)
        logits, cache = fn(eng.params, eng.cache, zeros, zeros, table, items,
                           idle)
        eng._set_cache(cache)
        _settle(logits, on_logits)
    chunk = ec.prefill_chunk_tokens
    for b in sorted({ec.block, chunk}):
        fn = eng._prefill_chunk_fn(b)
        items = jnp.asarray(eng._chunk_worklists(b, 0, b))
        row = jnp.full((eng.kv.table_width,), -1, jnp.int32)
        logits, cache = fn(eng.params, eng.cache, jnp.zeros((1, b), jnp.int32),
                           row, 0, b, b - 1, items)
        eng._set_cache(cache)
        _settle(logits, on_logits)
    return buckets


def _settle(logits, on_logits) -> None:
    import jax
    jax.block_until_ready(on_logits(logits) if on_logits else logits)
