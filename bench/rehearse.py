#!/usr/bin/env python3
"""Describe-and-compile rehearsal: each cell's packed decode step and
prefill chunk step, compiled for a described TPU v5e at the cell's sizes,
with no chip attached.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--workload <name> ...]

The steps are the ones the engine jits (``decode_step_paged`` with packed
items, ``prefill_chunk_paged`` with sparse items, the pool donated), at the
largest decode item bucket and the 256-token chunk.  Off the chip the
kernel dispatch sees the CPU, so it is pointed at the Pallas kernel here.
Prints each program's ``memory_analysis()`` with the resident weights and
pool; nothing runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def rehearse(cell) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from harness import families, model
    from repro.kernels import ops
    from repro.kernels.sparse_decode import DEC_FIELDS
    from repro.models import transformer as tfm

    ops._on_tpu = lambda: True            # compile the Pallas kernel
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg, traffic = cell.config, cell.traffic
    fam = families.load(cfg)
    m = fam.dims(cfg)
    tcfg = fam.program_config(cfg)
    ecfg = model.engine_config(cfg, traffic)
    import dataclasses
    tcfg = dataclasses.replace(tcfg, block_q=ecfg.block, block_kv=ecfg.block)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: fam.weights_fn(cfg)(jax.random.PRNGKey(0))))
    N = ecfg.num_kv_blocks + 1
    pool = jax.ShapeDtypeStruct((m["L"], 2, N, m["Hkv"], ecfg.block, m["Dh"]),
                                jnp.bfloat16, sharding=one)
    B, T = ecfg.num_slots, ecfg.max_seq_len // ecfg.block
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    # the packed item cap: every slot at its widest selection
    from harness import plan
    sel = plan.Selection(plan.maxmin_budgets(
        m["L"], m["H"], budget_per_head=ecfg.budget_per_head,
        seq_len=ecfg.max_seq_len, block=ecfg.block, floor=ecfg.floor),
        group=m["H"] // m["Hkv"], block=ecfg.block)
    cap = -(-(B * m["Hkv"] * int(sel.nb_kv.max())) // 8) * 8

    def decode(params, pool, token, pos, table, items, act):
        return tfm.decode_step_paged(params, pool, token, pos, table, tcfg,
                                     packed_items=items, cache_len=pos + 1,
                                     active=act)

    def chunk(params, pool, tokens, table, off, kv_len, last, items):
        return tfm.prefill_chunk_paged(params, pool, tokens, table, off, tcfg,
                                       kv_len=kv_len, sparse_items=items,
                                       last_index=last)

    out = {"weights_bytes": sum(int(x.size) * x.dtype.itemsize
                                for x in jax.tree.leaves(params)),
           "pool_bytes": int(pool.size) * 2, "decode_item_cap": cap}
    dec = jax.jit(decode, donate_argnums=(1,)).lower(
        params, pool, i32(B), i32(B), i32(B, T), i32(m["L"], cap, DEC_FIELDS),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)).compile()
    C = ecfg.prefill_chunk_tokens
    n_items = 8 * (-(-sum(len(sel.prefill_pairs(0, h, q))
                          for h in range(m["H"]) for q in range(C // 128)) // 8))
    chk = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, pool, i32(1, C), i32(T), i32(), i32(), i32(),
        i32(m["L"], 4 * n_items, 7)).compile()
    for name, c in (("decode", dec), ("chunk", chk)):
        ma = c.memory_analysis()
        out[name] = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}
        out[name]["pallas_kernels"] = c.as_text().count("tpu_custom_call")
    return out


def main() -> int:
    from harness import spec
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args()
    names = args.workload or [w["name"] for w in json.load(
        open(BENCH.parent / "BENCHMARK.json"))["workloads"]]
    for n in names:
        print(json.dumps({"workload": n, **rehearse(spec.load_cell(n))}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
