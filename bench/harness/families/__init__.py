"""A configuration's model family: the module that knows its architecture.

``"family"`` in a configuration file names a module of this package
(``llama`` when the key is absent).  A family module gives:

- ``dims(cfg)``: the sizes the harness uses (``L``, ``d``, ``H``, ``Hkv``,
  ``Dh``, ``ff``, ``V``, ...);
- ``weights_fn(cfg)``: a jitted maker of the weights from a PRNG key, and
  ``make_weights(cfg, seed, layers_on_host=False)``;
- ``program_config(cfg)``: the system's model configuration;
- ``Reference(dims, weights, selection)``: the plain float32 reference, with
  ``compare(prompt, served, ids)``;
- ``matmul_params(cfg)``: per-token weights of the layers and of the head.

A new architecture adds its own module here and names it in its file.
"""
from __future__ import annotations

import importlib


def load(cfg: dict):
    """The family module of configuration ``cfg``."""
    return importlib.import_module(
        f"harness.families.{cfg.get('family', 'llama')}")
