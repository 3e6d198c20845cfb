"""Mesh factory for the production topologies.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (smoke tests and benches must keep seeing the
single real CPU device; only the dry-run sets the 512-device XLA flag).

§2.11 adds the 2D head x sequence topology: a ``seq`` axis orthogonal to
``model`` stripes one sequence's paged KV pool across devices, so a 100k+
context is no longer bound by a single device's HBM.  Factorizations are
VALIDATED here with actionable errors — a bad ``model * seq`` split used
to surface as an opaque shard_map shape error three layers down.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    JAX 0.9 defaults new meshes to ``Explicit`` axes, under which the
    GSPMD-style ``PartitionSpec`` / ``with_sharding_constraint`` code here
    (and ``jnp.take`` on a sharded embedding) raises ``ShardingTypeError``;
    ``Auto`` axes keep the compiler's sharding propagation."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def _check_factorization(n: int, axes: dict[str, int]) -> None:
    """Reject axis sizes that do not factor the device count, with the
    fix spelled out (which flag to change, what the product is)."""
    prod = 1
    for v in axes.values():
        if v < 1:
            raise ValueError(
                f"mesh axis sizes must be >= 1, got {axes}")
        prod *= v
    if prod != n:
        parts = " * ".join(f"{k}={v}" for k, v in axes.items())
        raise ValueError(
            f"mesh factorization {parts} = {prod} does not match the "
            f"{n} visible device(s); pick axis sizes whose product is "
            f"{n} (e.g. lower --seq-shards, or force more host devices "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count={prod})")


def validate_heads_divide(num_kv_heads: int, model: int) -> None:
    """KV heads must split evenly over the model axis — a non-divisible
    count silently truncates head shards inside shard_map otherwise."""
    if model > 0 and num_kv_heads % model:
        raise ValueError(
            f"num_kv_heads={num_kv_heads} is not divisible by the model "
            f"axis size {model}; shrink the model axis to a divisor of "
            f"{num_kv_heads} (row-mode partitioning handles non-divisible "
            f"Q heads, but KV heads must tile the head-sharded cache)")


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod (data=16, model=16) = 256 chips; multi-pod adds pod=2."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int | None = None, data: int | None = None):
    """Mesh over whatever devices exist (tests / examples / CPU smoke)."""
    n = len(jax.devices())
    if model is None and data is None:
        model = 1
        data = n
    elif model is None:
        if data < 1 or n % data:
            raise ValueError(
                f"data={data} does not divide the {n} visible device(s); "
                f"pick a divisor of {n}")
        model = n // data
    elif data is None:
        if model < 1 or n % model:
            raise ValueError(
                f"model={model} does not divide the {n} visible "
                f"device(s); pick a divisor of {n}")
        data = n // model
    _check_factorization(n, {"data": data, "model": model})
    return make_mesh((data, model), ("data", "model"))


def make_host_mesh_2d(model: int = 1, seq: int = 1,
                      data: int | None = None,
                      num_kv_heads: int | None = None):
    """2D head x sequence mesh over the host's devices (DESIGN.md §2.11).

    Axes ``(data, model, seq)``: ``model`` shards kv heads (the HPLB
    axis), ``seq`` stripes the paged KV pool's block axis — one sequence's
    blocks spread over the seq shards and decode merges per-stripe
    ``(out, m, l)`` partials with one collective along ``seq`` only.
    ``data`` defaults to whatever is left over.  ``num_kv_heads`` (when
    given) validates head divisibility up front.
    """
    n = len(jax.devices())
    if model < 1 or seq < 1:
        raise ValueError(
            f"model and seq axis sizes must be >= 1, got model={model} "
            f"seq={seq}")
    if data is None:
        if n % (model * seq):
            _check_factorization(n, {"model": model, "seq": seq})
        data = n // (model * seq)
    _check_factorization(n, {"data": data, "model": model, "seq": seq})
    if num_kv_heads is not None:
        validate_heads_divide(num_kv_heads, model)
    return make_mesh((data, model, seq), ("data", "model", "seq"))
