"""What every model family shares: the weights' key from the run's seed,
the engine configuration of a cell and the assumed offline profile.  The
architecture's own pieces are in ``harness.families``."""
from __future__ import annotations

from harness.traffic import rng_for


def weight_key(seed: int) -> int:
    return int(rng_for(seed, "weights").integers(0, 2 ** 31 - 1))


def engine_config(cfg: dict, traffic: dict, overrides: dict | None = None):
    from repro.serving import EngineConfig
    kw = {**cfg["engine"], **traffic["engine"], **(overrides or {})}
    return EngineConfig(**kw)


def head_profile(num_layers: int, num_heads: int, seed: int):
    """The assumed offline profile the engine plans from."""
    from repro.core.sparsity import synthetic_head_curves
    return synthetic_head_curves(num_layers, num_heads, seed=seed)
