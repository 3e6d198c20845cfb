"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --shape train_4k [--steps N] [--mesh host|prod|prod-multipod] \
        [--smoke] [--remat full] [--microbatches 1] [--compress-grads]

``--mesh host`` runs on whatever devices exist (the CPU path used by the
examples/CI); ``prod`` targets the 16x16 pod (real TPU deployment; on this
container use the dry-run instead).  Fault tolerance: checkpoints every
``--ckpt-every`` steps (atomic, async), auto-resume from latest, and the
data stream is a pure function of the step index, so a restarted worker
replays exactly the batches it owes.

XLA collective/latency flags for real TPU runs are set here (overlap of
gradient all-reduce with the backward pass — the standard latency-hiding
scheduler knobs).
"""
import os

# compute/comm overlap knobs for real TPU deployments (harmless on CPU)
os.environ.setdefault(
    "LIBTPU_INIT_ARGS",
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_enable_async_all_gather=true")

import argparse  # noqa: E402
import functools  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES  # noqa: E402
from repro.data.synthetic import lm_batch  # noqa: E402
from repro.launch.mesh import make_host_mesh, make_production_mesh  # noqa: E402
from repro.launch.steps import _init_fn_for, _loss_fn_for  # noqa: E402
from repro.sharding import specs as sh  # noqa: E402
from repro.training import (  # noqa: E402
    AdamWConfig,
    CheckpointManager,
    TrainConfig,
    make_train_state,
    make_train_step,
)
from repro.utils.logging import get_logger  # noqa: E402

log = get_logger("train")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "prod", "prod-multipod"])
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config + small batch/seq")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--remat", default="full", choices=["none", "full"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    spec = ARCHS[args.arch]
    shape = SHAPES[args.shape]
    cfg = spec.smoke if args.smoke else spec.full
    batch = args.batch or (4 if args.smoke else shape.global_batch)
    seq = args.seq or (128 if args.smoke else shape.seq_len)
    run_spec = type(spec)(**{**spec.__dict__, "full": cfg})

    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "prod-multipod")

    tcfg = TrainConfig(
        optimizer=AdamWConfig(total_steps=args.steps),
        microbatches=args.microbatches, remat=args.remat,
        compress_grads=args.compress_grads)
    init = _init_fn_for(run_spec)
    loss_fn = _loss_fn_for(run_spec)
    step = make_train_step(loss_fn, tcfg)

    with jax.set_mesh(mesh):
        state = make_train_state(jax.random.PRNGKey(0), init, tcfg)
        pspec = sh.param_specs(jax.eval_shape(lambda: state["params"]),
                               mesh)
        state = dict(state, params=jax.device_put(
            state["params"],
            jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                         is_leaf=lambda x: isinstance(x, P))))
        step_fn = jax.jit(step, donate_argnums=0)

        ckpt_dir = args.ckpt_dir or f"artifacts/train_{args.arch}"
        cm = CheckpointManager(ckpt_dir, keep=3)
        start, restored = cm.restore_latest(
            jax.eval_shape(lambda: state))
        if restored is not None:
            state = restored
            log.info("resumed from step %d", start)
        else:
            start = 0

        t0 = time.time()
        for i in range(start, args.steps):
            b = jax.tree.map(jnp.asarray,
                             lm_batch(i, batch=batch, seq_len=seq,
                                      vocab=min(cfg.vocab_size, 260)))
            state, metrics = step_fn(state, b)
            if (i + 1) % args.ckpt_every == 0 or i + 1 == args.steps:
                cm.save(i + 1, state, blocking=False)
            if i % 10 == 0:
                log.info("step %d loss %.4f (%.2f s/step)", i,
                         float(metrics["loss"]),
                         (time.time() - t0) / max(i - start + 1, 1))
        cm.wait()
        log.info("done at step %d", args.steps)


if __name__ == "__main__":
    main()
