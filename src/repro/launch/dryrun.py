import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input-shape) cell and mesh:
    jax.jit(step).lower(**input_specs).compile()
must SUCCEED on the single-pod (16, 16) = 256-chip mesh and the multi-pod
(2, 16, 16) = 512-chip mesh.  The compiled artifact yields:

- ``memory_analysis()``  — bytes per device (proves the sharding fits),
- ``cost_analysis()``    — HLO FLOPs / bytes accessed (roofline numerator),
- collective bytes       — parsed from the post-optimization HLO text
  (all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute operand/result sizes),

all written to ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` for
EXPERIMENTS.md §Dry-run and the §Roofline analysis.

NOTE the XLA_FLAGS line above MUST precede any jax import — jax locks the
device count on first init.  Never set this flag globally.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k [--multi-pod] [--out artifacts/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import re
import time
import traceback

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.configs import ARCHS, SHAPES, cells  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_step  # noqa: E402

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_ARRAY_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _array_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _ARRAY_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result sizes of collective ops in post-optimization HLO.

    Returns {op_name: {count, bytes}} + total.  Result size is the
    per-device payload (for all-gather: the gathered output; for
    all-reduce/reduce-scatter/all-to-all/permute: the transferred tensor).
    ``-start`` variants counted; ``-done`` skipped (same transfer).
    """
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.+?) ([\w\-]+)\(", line)
        if not m:
            continue
        result_type, op = m.groups()
        base = op.replace("-start", "")
        if base.endswith("-done") or base not in _COLLECTIVES:
            continue
        if op.endswith("-done"):
            continue
        out[base]["count"] += 1
        out[base]["bytes"] += _array_bytes(result_type)
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             out_dir: str, variant: str = "default") -> dict:
    spec = ARCHS[arch_id]
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch_id}__{shape_name}__{mesh_name}"
    if variant != "default":
        cell_id += f"__{variant}"
    rec = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "variant": variant, "status": "ok",
    }
    t0 = time.time()
    try:
        if shape.name == "long_500k" and spec.long_mode == "skip":
            rec["status"] = f"skip:{spec.skip_reason}"
            return _dump(rec, out_dir, cell_id)

        mesh = make_production_mesh(multi_pod=multi_pod)
        # §Perf variants: each maps to step-builder kwargs
        VARIANTS = {
            "default": {},                         # paper-faithful S-HPLB
            "dense": {"sparse": False},            # full-attention baseline
            "uniform": {"allocator": "uniform"},   # top-k baseline budgets
            "naive_lb": {"partitioner": "naive"},  # S-HPLB minus balancer
            "lpt": {"partitioner": "lpt"},         # paper's greedy only
            "compress": {"compress_grads": True},  # int8 grad all-reduce
            "remat_none": {"remat": "none"},
            "microbatch4": {"microbatches": 4},
            # quantized KV pool (§2.12): the REAL engine-path kv_dtype —
            # int8/fp8 codes + per-(block, kv-head) scales threaded through
            # decode_step and the shard_map flash-decode island
            "f8cache": {"kv_dtype": "fp8"},
            "int8cache": {"kv_dtype": "int8"},
            "rows": {"force_rows": True},          # (head, q_blk) row balance
            "moe_cf1": {"moe_cf": 1.0},            # MoE capacity 1.0
            "moe_int8": {"moe_int8_dispatch": True},  # int8 MoE all-to-all
        }
        built = build_step(spec, shape, mesh, **VARIANTS[variant])
        rec["meta"] = {k: v for k, v in built.meta.items()
                       if isinstance(v, (int, float, str, bool, list))}

        # attach shardings to the abstract inputs
        def attach(a, s):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)

        abstract = jax.tree.map(attach, built.abstract, built.in_shardings)
        with jax.set_mesh(mesh):
            lowered = jax.jit(built.fn).lower(**abstract)
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # older jax: [dict]
            cost = cost[0] if cost else {}
        if cost:
            rec["cost"] = {k: float(v) for k, v in cost.items()
                           if isinstance(v, (int, float))
                           and k in ("flops", "bytes accessed",
                                     "transcendentals", "optimal_seconds")}
        rec["collectives"] = collective_bytes(compiled.as_text())
        rec["total_s"] = round(time.time() - t0, 1)
    except Exception as e:  # noqa: BLE001 — record failures, don't crash the suite
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        rec["total_s"] = round(time.time() - t0, 1)
    return _dump(rec, out_dir, cell_id)


def _dump(rec: dict, out_dir: str, cell_id: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_id + ".json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec["status"]
    extra = ""
    if status == "ok":
        gf = rec.get("cost", {}).get("flops", 0) / 1e9
        cb = rec.get("collectives", {}).get("total_bytes", 0) / 1e9
        extra = (f" flops={gf:.1f}G coll={cb:.3f}GB "
                 f"lower={rec.get('lower_s')}s compile={rec.get('compile_s')}s")
    print(f"[dryrun] {cell_id}: {status}{extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="default",
                    help="default (paper S-HPLB) | dense (full-attention)")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    if args.all:
        ok = err = skip = 0
        for spec, shape, status in cells():
            rec = run_cell(spec.arch_id, shape.name, args.multi_pod,
                           args.out, args.variant)
            s = rec["status"]
            ok += s == "ok"
            err += s == "error"
            skip += s.startswith("skip")
        print(f"[dryrun] done: {ok} ok, {skip} skip, {err} error")
        raise SystemExit(1 if err else 0)

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.out,
                   args.variant)
    raise SystemExit(0 if rec["status"] != "error" else 1)


if __name__ == "__main__":
    main()
