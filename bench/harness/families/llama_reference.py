"""Plain reference of a served request: the configuration's forward pass
over its prompt and served tokens, in float32 at full matmul precision.

It follows the published decoder (RMSNorm, rotary embeddings in the
split-halves form, grouped-query attention, SwiGLU) and the configuration's
S-HPLB sparsity: a prompt query reads the key blocks of its head's strided
selection, a decoding token the sink and most recent blocks of its KV
head's budget (``harness.plan``), and both are causal inside a block.  It
uses the run's weights as made from the seed, converted to float32; it
takes nothing the program made.

One jitted program per layer works through the sequence in blocks of 256
query rows, so the attention scores of one block are the largest
temporary.  The sequence is padded to a multiple of 4096 tokens so a run
compiles a handful of shapes.
"""
from __future__ import annotations

import functools

import numpy as np

ROWS = 256
PAD = 4096


@functools.lru_cache(maxsize=None)
def _layer_fn(S, d, H, Hkv, Dh, ff, theta, eps, block):
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST
    G = H // Hkv
    f32 = jnp.float32

    def mm(a, w):
        return jnp.matmul(a, w.astype(f32), precision=hp)

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):                       # x [S, h, Dh]
        half = Dh // 2
        inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=f32) / Dh))
        fr = pos.astype(f32)[:, None] * inv[None, :]
        c, s = jnp.cos(fr)[:, None], jnp.sin(fr)[:, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def run(x, lw, pmask, dmask, prompt_len):
        pos = jnp.arange(S)
        h = norm(x, lw["ln1"])
        k = rope(mm(h, lw["attn"]["wk"]).reshape(S, Hkv, Dh), pos)
        v = mm(h, lw["attn"]["wv"]).reshape(S, Hkv, Dh)

        def rows(i):
            r0 = i * ROWS
            xr = jax.lax.dynamic_slice_in_dim(x, r0, ROWS)
            hr = jax.lax.dynamic_slice_in_dim(h, r0, ROWS)
            qpos = r0 + jnp.arange(ROWS)
            q = rope(mm(hr, lw["attn"]["wq"]).reshape(ROWS, H, Dh), qpos)
            q = q.reshape(ROWS, Hkv, G, Dh)
            s = jnp.einsum("rkgd,tkd->kgrt", q, k, precision=hp) / np.sqrt(Dh)
            qb = qpos // block
            allow = jnp.where((qpos < prompt_len)[None, :, None],
                              pmask[:, qb, :], dmask[:, qb, :])  # [H, R, nb]
            allow = jnp.repeat(allow, block, axis=2)            # [H, R, S]
            allow = allow & (pos[None, None, :] <= qpos[None, :, None])
            s = jnp.where(allow.reshape(Hkv, G, ROWS, S), s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("kgrt,tkd->rkgd", p, v, precision=hp)
            xr = xr + mm(o.reshape(ROWS, H * Dh), lw["attn"]["wo"])
            h2 = norm(xr, lw["ln2"])
            g = jax.nn.silu(mm(h2, lw["mlp"]["gate"]))
            return xr + mm(g * mm(h2, lw["mlp"]["up"]), lw["mlp"]["down"])

        return jax.lax.map(rows, jnp.arange(S // ROWS)).reshape(S, d)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _head_fn(n, k, eps, tied):
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST

    def run(x, ln_f, head, start, served, ids):
        xs = jax.lax.dynamic_slice_in_dim(x, start, n)
        xs = xs * jax.lax.rsqrt(jnp.mean(xs * xs, -1, keepdims=True) + eps)
        xs = xs * ln_f
        w = head.astype(jnp.float32)
        logits = (jnp.matmul(xs, w.T, precision=hp) if tied
                  else jnp.matmul(xs, w, precision=hp))
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, served[:, None], 1)[:, 0]
        return best - got, jnp.take_along_axis(logits, ids, 1)

    return jax.jit(run)


class Reference:
    """The reference for one configuration's sizes (``dims``), its weights
    and selection."""

    def __init__(self, m: dict, weights, selection):
        self.m = m
        self.w = weights
        self.sel = selection
        self._masks: dict[tuple[int, int], tuple] = {}

    def _layer_masks(self, layer: int, nqb: int):
        key = (layer, nqb)
        if key not in self._masks:
            self._masks[key] = self.sel.masks(layer, nqb)
        return self._masks[key]

    def compare(self, prompt: np.ndarray, served: list[int],
                ids: np.ndarray | None = None):
        """The reference at each served token's position: how far the
        served token's logit lies below the best (``[n]``), and the
        float32 logits at ``ids`` (``[n, k]`` vocabulary ids, one row per
        served token; None gives ``[n, 0]``)."""
        import jax
        import jax.numpy as jnp
        m = self.m
        n = len(served)
        P = len(prompt)
        toks = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(served[:-1], np.int32)])
        nrow = max(128, 1 << (n - 1).bit_length())
        S = -(-(P - 1 + nrow) // PAD) * PAD
        padded = np.zeros(S, np.int32)
        padded[:len(toks)] = toks
        x = jnp.take(self.w["embed"], jnp.asarray(padded), axis=0)
        x = x.astype(jnp.float32)
        fn = _layer_fn(S, m["d"], m["H"], m["Hkv"], m["Dh"], m["ff"],
                       m["theta"], m["eps"], self.sel.block)
        nqb = S // self.sel.block
        for l in range(m["L"]):
            lw = jax.tree.map(lambda a: a[l], self.w["layers"])
            pm, dm = self._layer_masks(l, nqb)
            x = fn(x, lw, jnp.asarray(pm), jnp.asarray(dm), P)
        served_pad = np.zeros(nrow, np.int32)
        served_pad[:n] = served
        ids = np.zeros((n, 0), np.int32) if ids is None else ids
        ids_pad = np.zeros((nrow, ids.shape[1]), np.int32)
        ids_pad[:n] = ids
        head = self.w["embed"] if m["tied"] else self.w["lm_head"]
        g, z = _head_fn(nrow, ids.shape[1], m["eps"], m["tied"])(
            x, self.w["ln_f"], head, P - 1, jnp.asarray(served_pad),
            jnp.asarray(ids_pad))
        return np.asarray(g)[:n], np.asarray(z)[:n]
