"""The Llama-layout decoder (RMSNorm, rotary embeddings, grouped-query
attention, SwiGLU): its sizes, weights, program configuration, operation
counts and plain reference (``llama_reference``).

Weights are made on the device, in the type they are served in, by one
jitted call from the run's seed (a loop over layers inside it, so no
full-model float32 temporary exists).  The layout is the one the engine
takes: layers stacked on a leading axis, projections ``[in, out]``, norm
scales in float32, a separate ``lm_head`` only for untied embeddings.
"""
from __future__ import annotations

import functools

import numpy as np

from harness.families.llama_reference import Reference  # noqa: F401
from harness.model import weight_key


def dims(cfg: dict) -> dict:
    """The sizes the harness uses, from the configuration's published keys."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "d": d, "H": H,
        "Hkv": cfg["num_key_value_heads"],
        "Dh": cfg.get("head_dim") or d // H,
        "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "tied": bool(cfg["tie_word_embeddings"]),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
    }


@functools.lru_cache(maxsize=None)
def _weights_fn(L, d, H, Hkv, Dh, ff, V, tied):
    import jax
    import jax.numpy as jnp

    def dense(k, i, o):
        return (jax.random.normal(k, (i, o), jnp.float32)
                * (1.0 / np.sqrt(i))).astype(jnp.bfloat16)

    def layer(k):
        ks = jax.random.split(k, 7)
        return {
            "attn": {"wq": dense(ks[0], d, H * Dh),
                     "wk": dense(ks[1], d, Hkv * Dh),
                     "wv": dense(ks[2], d, Hkv * Dh),
                     "wo": dense(ks[3], H * Dh, d)},
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "mlp": {"gate": dense(ks[4], d, ff), "up": dense(ks[5], d, ff),
                    "down": dense(ks[6], ff, d)},
        }

    def make(key):
        k_emb, k_layers, k_head = jax.random.split(key, 3)
        out = {
            "embed": (jax.random.normal(k_emb, (V, d), jnp.float32)
                      * (1.0 / np.sqrt(d))).astype(jnp.bfloat16),
            "layers": jax.lax.map(layer, jax.random.split(k_layers, L)),
            "ln_f": jnp.ones((d,), jnp.float32),
        }
        if not tied:
            out["lm_head"] = dense(k_head, d, V)
        return out

    return jax.jit(make)


def weights_fn(cfg: dict):
    """The jitted maker of the configuration's weights from a PRNG key."""
    m = dims(cfg)
    return _weights_fn(m["L"], m["d"], m["H"], m["Hkv"], m["Dh"], m["ff"],
                       m["V"], m["tied"])


def make_weights(cfg: dict, seed: int, layers_on_host: bool = False):
    """The configuration's weights for ``seed``, on the default device.

    ``layers_on_host`` hands the stacked layers over as host arrays: the
    engine copies every layer to the host anyway to permute its heads, and
    a device copy held by the caller during that would double the model
    on the chip (Yi-6B at 16 layers then does not fit with its KV pool)."""
    import jax
    w = jax.block_until_ready(
        weights_fn(cfg)(jax.random.PRNGKey(weight_key(seed))))
    if layers_on_host:
        w["layers"] = jax.device_get(w["layers"])
    return w


def program_config(cfg: dict):
    """The system's model configuration for this file."""
    from repro.models.transformer import TransformerConfig
    m = dims(cfg)
    return TransformerConfig(
        name=cfg["name"], num_layers=m["L"], d_model=m["d"],
        num_heads=m["H"], num_kv_heads=m["Hkv"], d_ff=m["ff"],
        vocab_size=m["V"], head_dim=m["Dh"], rope_theta=m["theta"],
        attn_pattern="G", tie_embeddings=m["tied"])


def matmul_params(cfg: dict) -> tuple[int, int]:
    """(per-token weights of all layers, weights of the output head)."""
    m = dims(cfg)
    d, Dh = m["d"], m["Dh"]
    attn = d * Dh * (m["H"] + 2 * m["Hkv"]) + m["H"] * Dh * d
    return m["L"] * (attn + 3 * d * m["ff"]), d * m["V"]
