"""Logical-axis sharding constraints for model code.

Models annotate activations with LOGICAL axis names; this module maps them
to whatever mesh is active (single-pod ``(data, model)``, multi-pod
``(pod, data, model)``, or none — in which case constraints are no-ops, so
the same model code runs in tests, smoke runs, and production).

Logical axes:
    "batch"  -> sharded over ('pod', 'data')   (whichever exist)
    "model"  -> sharded over ('model',)
    "seq"    -> sharded over ('data',)          (sequence/context parallel)
    None     -> replicated
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

LOGICAL = {
    "batch": ("pod", "data"),
    "model": ("model",),
    "seq": ("data",),
    "expert": ("model",),
}


def _mesh_axes() -> tuple[str, ...]:
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return ()
    return tuple(m.axis_names)


def logical_spec(*logical_axes: str | None) -> P:
    """Resolve logical axis names to a PartitionSpec for the active mesh."""
    present = _mesh_axes()
    out = []
    for ax in logical_axes:
        if ax is None:
            out.append(None)
            continue
        phys = tuple(a for a in LOGICAL.get(ax, ()) if a in present)
        if len(phys) == 0:
            out.append(None)
        elif len(phys) == 1:
            out.append(phys[0])
        else:
            out.append(phys)
    return P(*out)


def constrain(x, *logical_axes: str | None):
    """with_sharding_constraint against the active mesh (no-op without one)."""
    if not _mesh_axes():
        return x
    return jax.lax.with_sharding_constraint(x, logical_spec(*logical_axes))
