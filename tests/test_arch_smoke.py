"""Per-architecture smoke tests: reduced config, forward + train step + decode.

Each assigned architecture instantiates a REDUCED config of the same family
(same pattern / attention type / MoE routing / recurrence) and runs on CPU:
  * one forward pass — asserts logits shape and finiteness,
  * one train step (CE loss grad) — asserts finite grads,
  * one decode step (where the family has one) — asserts cache consistency.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, list_archs
from repro.model import model as M

jax.config.update("jax_platform_name", "cpu")

B, S = 2, 64


def _inputs(cfg, batch=B, seq=S):
    rng = np.random.default_rng(0)
    kw = {}
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    if cfg.frontend == "vision":
        s_f = seq // 4
        kw["frontend_embeds"] = jnp.asarray(
            rng.standard_normal((batch, s_f, cfg.d_model)).astype(np.float32)
        )
        kw["positions"] = jnp.broadcast_to(
            jnp.arange(seq, dtype=jnp.int32)[None, None], (3, batch, seq)
        )
    if cfg.is_enc_dec:
        kw["enc_tokens_embeds"] = jnp.asarray(
            rng.standard_normal((batch, seq, cfg.d_model)).astype(np.float32)
        )
    return tokens, kw


@pytest.fixture(scope="module", params=list_archs())
def arch(request):
    cfg = get_config(request.param).reduced()
    params = M.init_params(cfg, jax.random.key(0))
    return cfg, params


class TestForward:
    def test_forward_shape_and_finite(self, arch):
        cfg, params = arch
        tokens, kw = _inputs(cfg)
        logits = jax.jit(
            lambda p, t: M.forward(p, cfg, t, **kw)
        )(params, tokens)
        assert logits.shape == (B, S, cfg.padded_vocab)
        assert bool(jnp.isfinite(logits).all()), f"{cfg.name}: non-finite logits"

    def test_train_step_grads_finite(self, arch):
        cfg, params = arch
        tokens, kw = _inputs(cfg)
        labels = jnp.roll(tokens, -1, axis=1)

        def loss_fn(p):
            logits = M.forward(p, cfg, tokens, **kw)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
            return nll.mean()

        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        assert bool(jnp.isfinite(loss)), f"{cfg.name}: loss {loss}"
        flat = jax.tree.leaves(grads)
        assert all(bool(jnp.isfinite(g).all()) for g in flat), f"{cfg.name}: nan grads"
        assert any(float(jnp.abs(g).max()) > 0 for g in flat)


class TestDecode:
    def test_decode_step(self, arch):
        cfg, params = arch
        if cfg.is_enc_dec:
            pytest.skip("enc-dec decode covered separately")
        state = M.init_decode_state(cfg, batch=B, max_len=128)
        tokens = jnp.ones((B, 1), jnp.int32)
        step = jax.jit(
            lambda p, s, t, l: M.decode_step(p, cfg, s, t, l)
        )
        logits, state = step(params, state, tokens, jnp.int32(0))
        assert logits.shape == (B, 1, cfg.padded_vocab)
        assert bool(jnp.isfinite(logits).all())
        logits2, state = step(params, state, tokens, jnp.int32(1))
        assert bool(jnp.isfinite(logits2).all())

    def test_decode_matches_prefill_logits(self, arch):
        """Greedy consistency: step-by-step decode == teacher-forced forward."""
        cfg, params = arch
        if cfg.is_enc_dec or cfg.frontend == "vision":
            pytest.skip("needs extra inputs; covered by forward test")
        t = 8
        rng = np.random.default_rng(1)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, t)), jnp.int32)
        full = M.forward(params, cfg, tokens)

        state = M.init_decode_state(cfg, batch=1, max_len=64)
        outs = []
        for i in range(t):
            logits, state = M.decode_step(
                params, cfg, state, tokens[:, i : i + 1], jnp.int32(i)
            )
            outs.append(logits[:, 0])
        stepped = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(stepped, np.float32),
            np.asarray(full, np.float32),
            rtol=2e-2, atol=2e-2,
        )


class TestConfigs:
    def test_exact_assignment_numbers(self):
        expect = {
            "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
            "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
            "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
            "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
            "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
            "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
            "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
            "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
            "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
            "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
            "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
        }
        for arch_name in list_archs():
            cfg = get_config(arch_name)
            got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                   cfg.d_ff, cfg.vocab_size)
            assert got == expect[cfg.name], cfg.name

    def test_moe_expert_counts(self):
        assert get_config("dbrx-132b").num_experts == 16
        assert get_config("dbrx-132b").num_experts_per_tok == 4
        assert get_config("qwen3-moe-235b-a22b").num_experts == 128
        assert get_config("qwen3-moe-235b-a22b").num_experts_per_tok == 8

    def test_param_counts_in_band(self):
        # Sanity-check total params against the advertised scale (±40%).
        bands = {
            "qwen2-vl-7b": (5e9, 11e9),
            "dbrx-132b": (90e9, 180e9),
            "qwen3-moe-235b-a22b": (160e9, 320e9),
            "minitron-8b": (6e9, 12e9),
            "nemotron-4-15b": (11e9, 22e9),
            "qwen2-0.5b": (0.3e9, 0.8e9),
            "rwkv6-1.6b": (1.0e9, 2.4e9),
            "gemma3-1b": (0.6e9, 1.6e9),
            "recurrentgemma-2b": (1.6e9, 3.8e9),
            "granite-4.0-h-micro": (2.5e9, 4.0e9),
        }
        for name, (lo, hi) in bands.items():
            n = get_config(name).param_count()
            assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B not in [{lo/1e9},{hi/1e9}]"

    def test_moe_active_params(self):
        cfg = get_config("qwen3-moe-235b-a22b")
        active = cfg.active_param_count()
        assert 14e9 <= active <= 30e9, active / 1e9
