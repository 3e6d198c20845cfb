"""Profiler spans and stable program names of the served path.

A tiny engine serves under ``jax.profiler.trace``: the host plane holds
each tick-path span, nested as the scheduler and engine open them; each
jitted step lowers to a module named after its function, under which
``CompileMeter`` counts its compile; the scheduler's tick and row counters
match counts taken around the step closures; and greedy tokens do not
change when the profiler is on.
"""
import glob
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsity import synthetic_head_curves
from repro.core.worklist import DEC_FIELDS
from repro.models.transformer import TransformerConfig, init_params
from repro.serving import Engine, EngineConfig, SamplingParams

CFG = TransformerConfig(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, d_ff=128, vocab_size=256,
                        layer_loop="unroll")
KW = dict(budget_per_head=256, max_seq_len=512, num_slots=4,
          prefill_chunk_tokens=128)
PROMPT_LENS = (300, 90, 200)
MAX_TOKENS = 6

# span -> the span it opens inside (None: opened outside any program span)
PARENT = {
    "sched.tick": None,
    "sched.admit": {"sched.tick"},
    "sched.prefill_step": {"sched.tick"},
    "engine.prefill.inputs": {"sched.prefill_step"},
    "engine.prefill.dispatch": {"sched.prefill_step"},
    "engine.prefill.wait": {"sched.prefill_step"},
    "sched.decode": {"sched.tick"},
    "engine.decode.inputs": {"sched.decode"},
    "engine.decode.plan": {"sched.decode"},
    "engine.decode.dispatch": {"sched.decode"},
    "engine.decode.prefetch": {"sched.decode"},
    "engine.decode.probe": {"sched.decode"},
    "engine.decode.sample": {"sched.decode"},
    "engine.decode.wait": {"sched.decode"},
    "engine.sentinel": {"sched.prefill_step", "sched.decode"},
    "engine.on_tick": None,
}


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def make_engine(params, **kw):
    ecfg = EngineConfig(**{**KW, **kw})
    profile = (synthetic_head_curves(CFG.num_layers, CFG.num_heads)
               if ecfg.attention == "sparse" else None)
    return Engine(CFG, params, ecfg, profile=profile)


def prompts():
    return [np.random.default_rng(i).integers(0, CFG.vocab_size, size=(n,))
            for i, n in enumerate(PROMPT_LENS)]


def program_spans(trace_dir):
    """``(name, start_ns, end_ns)`` of every ``sched.*`` / ``engine.*``
    span on the host plane."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("sched.", "engine.")):
                    s = float(ev.start_ns)
                    out.append((ev.name, s, s + float(ev.duration_ns)))
    return out


def parents(spans):
    """``{name: {innermost enclosing span name or None}}``."""
    got = {}
    for i, (name, s, e) in enumerate(spans):
        best, width = None, float("inf")
        for j, (pn, ps, pe) in enumerate(spans):
            if j != i and ps <= s and e <= pe and pe - ps < width:
                best, width = pn, pe - ps
        got.setdefault(name, set()).add(best)
    return got


@pytest.fixture(scope="module")
def served(params, tmp_path_factory):
    """Greedy tokens served with the profiler off, then by the same
    (warmed) engine with it on, and the trace's program spans."""
    eng = make_engine(params, telemetry_every=2)
    sp = SamplingParams(max_tokens=MAX_TOKENS)
    off = [r.generated for r in eng.serve(prompts(), sp)]
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        on = [r.generated for r in eng.serve(prompts(), sp)]
    return off, on, parents(program_spans(trace_dir))


@pytest.mark.parametrize("name", sorted(PARENT))
def test_span_nesting(served, name):
    _, _, got = served
    assert name in got, f"no {name} span in the trace"
    want = PARENT[name] or {None}
    assert got[name] <= want, (name, got[name])


def test_greedy_tokens_same_with_profiler_on(served):
    off, on, _ = served
    assert off == on
    assert all(len(t) == MAX_TOKENS for t in on)


# -- stable program names -----------------------------------------------------

def _rows(eng):
    ns = eng.ecfg.num_slots
    zeros = jnp.zeros((ns,), jnp.int32)
    return zeros, jnp.zeros((ns,), bool)


def _table(eng):
    return jnp.full((eng.ecfg.num_slots, eng.kv.table_width), -1, jnp.int32)


def _bids(eng):
    ids = eng._decode_ids_for_nblocks(1)
    return jnp.asarray(np.stack([ids] * eng.ecfg.num_slots, axis=1))


def _chunk_args(eng, b=None):
    b = b or eng.ecfg.block
    row = jnp.full((eng.kv.table_width,), -1, jnp.int32)
    return (eng.params, eng.cache, jnp.zeros((1, b), jnp.int32), row, 0, b,
            b - 1)


def decode_packed(eng):
    zeros, act = _rows(eng)
    items = jnp.zeros((CFG.num_layers, 8, DEC_FIELDS), jnp.int32)
    return eng._decode_packed_fn(8), (eng.params, eng.cache, zeros, zeros,
                                      _table(eng), items, act)


def decode_padded(eng):
    zeros, act = _rows(eng)
    return eng._decode_fn(), (eng.params, eng.cache, zeros, zeros,
                              _table(eng), _bids(eng), act)


def decode_dense(eng):
    zeros, act = _rows(eng)
    return eng._decode_fn(), (eng.params, eng.cache, zeros, zeros,
                              _table(eng), act)


def prefill_paged(eng):
    b = eng.ecfg.block
    row = jnp.full((eng.kv.table_width,), -1, jnp.int32)
    return eng._prefill_paged_fn(b), (eng.params, eng.cache,
                                      jnp.zeros((1, b), jnp.int32), row,
                                      b - 1)


def prefill_chunk(eng):
    b = eng.ecfg.block
    items = jnp.asarray(eng._chunk_worklists(b, 0, b))
    return eng._prefill_chunk_fn(b), _chunk_args(eng) + (items,)


def prefill_chunk_b256(eng):
    items = jnp.asarray(eng._chunk_worklists(256, 0, 256))
    return eng._prefill_chunk_fn(256), _chunk_args(eng, 256) + (items,)


def prefill_chunk_dense(eng):
    return eng._prefill_chunk_fn(eng.ecfg.block), _chunk_args(eng)


def kv_permute(eng):
    tbl = jnp.zeros((CFG.num_layers, CFG.num_kv_heads), jnp.int32)
    return eng._kv_permute_fn(), (eng.cache, tbl)


def swap_gather(eng):
    return eng._swap_gather_fn(1), (eng.cache, jnp.zeros((1,), jnp.int32))


def swap_scatter(eng):
    L, two, _, hkv, blk, dh = eng.cache.shape
    blocks = jnp.zeros((L, two, 1, hkv, blk, dh), eng.cache.dtype)
    return eng._swap_scatter_fn(1), (eng.cache, blocks,
                                     jnp.zeros((1,), jnp.int32))


def telemetry_probe(eng):
    zeros, _ = _rows(eng)
    bids = _bids(eng)
    return eng._telemetry_fn(bids.shape[-1]), (eng.params, eng.cache, zeros,
                                               zeros, _table(eng), bids,
                                               zeros)


# step -> the engine configuration that jits it
STEPS = {
    decode_packed: {},
    decode_padded: {},
    decode_dense: {"attention": "dense"},
    prefill_paged: {},
    prefill_chunk: {},
    prefill_chunk_b256: {},
    prefill_chunk_dense: {"attention": "dense"},
    kv_permute: {},
    swap_gather: {},
    swap_scatter: {},
    telemetry_probe: {},
}


@pytest.fixture(scope="module")
def engines(params):
    made = {}

    def get(kw):
        key = tuple(sorted(kw.items()))
        if key not in made:
            made[key] = make_engine(params, **kw)
        return made[key]
    return get


# each chunk bucket (128 or 256 tokens here) is a program of its own name
PROGRAM = {
    prefill_chunk: "jit_prefill_chunk_b128",
    prefill_chunk_b256: "jit_prefill_chunk_b256",
    prefill_chunk_dense: "jit_prefill_chunk_dense_b128",
}


@pytest.mark.parametrize("step", list(STEPS), ids=lambda f: f.__name__)
def test_step_lowers_under_its_name(engines, step):
    fn, args = step(engines(STEPS[step]))
    text = fn.lower(*args).as_text()
    assert f"module @{PROGRAM.get(step, 'jit_' + step.__name__)} " in text


def _bench_trace():
    path = Path(__file__).resolve().parents[1] / "bench/harness/trace.py"
    spec = importlib.util.spec_from_file_location("bench_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("step", list(PROGRAM), ids=lambda f: f.__name__)
def test_chunk_program_counted_as_a_chunk(step):
    """The benchmark's trace reader counts a program as a prefill chunk by
    the prefix of its name; every bucket's name keeps it."""
    assert PROGRAM[step].startswith(_bench_trace().CHUNK_TAG)


# -- counters -----------------------------------------------------------------

@pytest.mark.parametrize("counter", ["ticks", "decode_rows"])
def test_scheduler_counter_matches_wrapped_closures(params, counter):
    eng = make_engine(params)
    batcher = eng.make_batcher()
    from repro.serving.scheduler import Request
    sp = SamplingParams(max_tokens=MAX_TOKENS)
    for i, p in enumerate(prompts()):
        batcher.submit(Request(rid=i, prompt=np.asarray(p, np.int32),
                               sampling=sp))
    prefill_fn, decode_fn = eng.step_fns(sp)
    seen = {"ticks": 0, "decode_rows": 0}

    def decode(slots, toks, pos):
        seen["decode_rows"] += len(slots)
        return decode_fn(slots, toks, pos)

    def on_tick():
        seen["ticks"] += 1
    batcher.run(prefill_fn, decode, on_tick=on_tick)
    assert seen[counter] > 0
    assert getattr(batcher.stats, counter) == seen[counter]


def test_compile_meter_names_the_step_that_compiled(engines):
    from repro.utils.compile_cache import CompileMeter
    meter = CompileMeter()
    snap = meter.snapshot()
    fn, args = decode_packed(engines({}))
    fn.lower(*args).compile()
    got = meter.programs_since(snap)
    assert got["jit(decode_packed)"][0] == 1
    assert "jit(decode_packed) 1 x" in meter.since(snap)
