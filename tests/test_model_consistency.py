"""Cross-path consistency: forward == prefill+decode for every decode-
capable family, sparse paths degrade gracefully, sharding spec sanity."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P


class TestTransformerConsistency:
    def setup_method(self):
        from repro.models.transformer import TransformerConfig, init_params
        self.cfg = TransformerConfig(
            num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            d_ff=128, vocab_size=256)
        self.params = init_params(jax.random.PRNGKey(0), self.cfg)
        self.toks = jax.random.randint(
            jax.random.PRNGKey(1), (2, 96), 0, 256)

    def test_decode_chain_matches_forward(self):
        from repro.models.transformer import decode_step, forward, prefill
        lg_f = forward(self.params, self.toks, self.cfg)
        lg_p, cache = prefill(self.params, self.toks[:, :64], self.cfg,
                              cache_len=128)
        np.testing.assert_allclose(np.asarray(lg_p),
                                   np.asarray(lg_f[:, 63]), atol=1e-4)
        # feed the TRUE next tokens; logits must match teacher-forced fwd
        for t in range(64, 70):
            lg_d, cache = decode_step(self.params, cache, self.toks[:, t],
                                      t, self.cfg)
            np.testing.assert_allclose(np.asarray(lg_d),
                                       np.asarray(lg_f[:, t]), atol=1e-4)

    def test_local_global_pattern_decode(self):
        from repro.models.transformer import (TransformerConfig, decode_step,
                                              forward, init_params, prefill)
        cfg = TransformerConfig(
            num_layers=3, d_model=64, num_heads=4, num_kv_heads=1,
            d_ff=96, vocab_size=128, attn_pattern="LLG", local_window=48,
            layer_loop="unroll")
        params = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 80), 0, 128)
        lg_f = forward(params, toks, cfg)
        lg_p, cache = prefill(params, toks[:, :64], cfg, cache_len=96)
        np.testing.assert_allclose(np.asarray(lg_p),
                                   np.asarray(lg_f[:, 63]), atol=1e-2)
        lg_d, _ = decode_step(params, cache, toks[:, 64], 64, cfg)
        np.testing.assert_allclose(np.asarray(lg_d),
                                   np.asarray(lg_f[:, 64]), atol=1e-2)


class TestMambaConsistency:
    def test_recurrent_decode_matches_forward(self):
        from repro.models.mamba2 import (Mamba2Config, decode_step, forward,
                                         init_params, init_state)
        cfg = Mamba2Config(num_layers=2, d_model=64, d_state=16,
                           head_dim=16, chunk=32, vocab_size=128)
        p = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
        lg = forward(p, toks, cfg)
        st = init_state(cfg, 2)
        for t in range(10):
            lgt, st = decode_step(p, st, toks[:, t], cfg)
            np.testing.assert_allclose(np.asarray(lgt),
                                       np.asarray(lg[:, t]), atol=2e-3)


class TestGriffinConsistency:
    def test_hybrid_decode_matches_forward(self):
        from repro.models.rglru import (GriffinConfig, decode_step, forward,
                                        init_params, init_state)
        cfg = GriffinConfig(num_layers=3, d_model=64, num_heads=4,
                            num_kv_heads=1, d_ff=96, vocab_size=128,
                            local_window=48, pattern="RRA")
        p = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
        lg = forward(p, toks, cfg)
        st = init_state(cfg, 2, window_cache=48)
        for t in range(10):
            lgt, st = decode_step(p, st, toks[:, t], t, cfg)
            np.testing.assert_allclose(np.asarray(lgt),
                                       np.asarray(lg[:, t]), atol=2e-3)


class TestWhisperConsistency:
    def test_decoder_cache_matches_forward(self):
        from repro.models.whisper import (WhisperConfig, decode_step, encode,
                                          forward, init_cache, init_params)
        cfg = WhisperConfig(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                            vocab_size=200, max_frames=32, max_target=24)
        p = init_params(jax.random.PRNGKey(0), cfg)
        frames = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 200)
        lg = forward(p, {"frames": frames, "tokens": toks}, cfg)
        mem = encode(p, frames, cfg)
        cache = init_cache(cfg, 2, 24)
        for t in range(6):
            lgt, cache = decode_step(p, cache, mem, toks[:, t], t, cfg)
            np.testing.assert_allclose(np.asarray(lgt),
                                       np.asarray(lg[:, t]), atol=2e-2)


class TestShardingSpecs:
    def test_divisibility_sanitation(self):
        """Dims not divisible by the mesh axis fall back to replication."""
        from repro.sharding import specs as sh
        mesh = AbstractMesh((16, 16), ("data", "model"))
        tree = {
            "embed": jax.ShapeDtypeStruct((51865, 512), jnp.bfloat16),
            "layers": {"attn": {
                "wq": jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)}},
        }
        spec = sh.param_specs(tree, mesh)
        assert spec["embed"] == P(None, None)         # 51865 % 16 != 0
        assert spec["layers"]["attn"]["wq"] == P(None, "model")

    def test_cache_seq_fallback(self):
        from repro.sharding import specs as sh
        mesh = AbstractMesh((16, 16), ("data", "model"))
        cache = jax.ShapeDtypeStruct((4, 2, 128, 8, 32768, 128),
                                     jnp.bfloat16)
        spec = sh.cache_specs(cache, mesh)
        # 8 kv heads % 16 fails -> model moves to the seq dim
        assert spec[3] is None and spec[4] == "model"
        assert spec[2] == "data"

    def test_cache_long_context_b1(self):
        from repro.sharding import specs as sh
        mesh = AbstractMesh((16, 16), ("data", "model"))
        cache = jax.ShapeDtypeStruct((4, 2, 1, 8, 524288, 128),
                                     jnp.bfloat16)
        spec = sh.cache_specs(cache, mesh)
        assert spec[2] is None                 # B=1 unshardable
        assert spec[4] == ("data", "model")    # full context parallelism

    def test_opt_state_mirrors_params(self):
        from repro.sharding import specs as sh
        pspec = {"w": P(None, "model")}
        ospec = sh.opt_specs({"m": 0, "v": 0, "step": 0}, pspec)
        assert ospec["m"] == pspec and ospec["v"] == pspec
