"""Llama model correctness on the TINY config (CPU, fast)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.engine.kv_cache import PagedKVCache
from k8s_llm_scheduler_tpu.models import TINY
from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.models.llama import (
    apply_rope,
    forward_decode,
    forward_prefill,
    init_params,
    param_count,
    rms_norm,
    rope_inv_freq,
)


CFG = LlamaConfig(
    name="test", vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq_len=256, rope_theta=10000.0, dtype=jnp.float32,
    tie_embeddings=True,
)

# jit once per shape — eager lax.scan on CPU is painfully slow.
forward_prefill = jax.jit(forward_prefill, static_argnums=(1,))
forward_decode = jax.jit(forward_decode, static_argnums=(1,))


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


class TestComponents:
    def test_rms_norm_unit_scale(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
        out = rms_norm(x, jnp.ones(32), 1e-5)
        rms = jnp.sqrt(jnp.mean(out**2, axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)

    def test_rope_preserves_norm(self):
        inv = rope_inv_freq(CFG)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 4, 8))
        pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
        rotated = apply_rope(x, pos, inv)
        np.testing.assert_allclose(
            jnp.linalg.norm(rotated, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5
        )

    def test_rope_relative_position_property(self):
        """<rope(q,m), rope(k,n)> depends only on m-n."""
        inv = rope_inv_freq(CFG)
        q = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, 8))
        k = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, 8))

        def dot_at(m, n):
            qm = apply_rope(q, jnp.full((1, 1), m), inv)
            kn = apply_rope(k, jnp.full((1, 1), n), inv)
            return float(jnp.sum(qm * kn))

        assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4
        assert abs(dot_at(7, 7) - dot_at(0, 0)) < 1e-4

    def test_llama3_rope_scaling_changes_low_freqs(self):
        scaled_cfg = LlamaConfig(
            name="t", vocab_size=64, d_model=32, n_layers=1, n_heads=2,
            n_kv_heads=2, d_ff=64, rope_theta=500000.0,
            rope_scaling=__import__(
                "k8s_llm_scheduler_tpu.models.configs", fromlist=["RopeScaling"]
            ).RopeScaling(factor=8.0),
        )
        base = rope_inv_freq(
            LlamaConfig(
                name="t", vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                n_kv_heads=2, d_ff=64, rope_theta=500000.0,
            )
        )
        scaled = rope_inv_freq(scaled_cfg)
        # High-frequency (early) entries unchanged, lowest-frequency scaled down.
        np.testing.assert_allclose(scaled[0], base[0], rtol=1e-6)
        assert scaled[-1] < base[-1]

    def test_param_count_tiny(self):
        params = init_params(jax.random.PRNGKey(0), TINY)
        n = param_count(params)
        assert 1e6 < n < 20e6  # sanity: a few-million-param model


class TestPrefill:
    def test_shapes(self, params):
        tokens = jnp.zeros((2, 16), dtype=jnp.int32)
        lens = jnp.array([16, 10])
        logits, k_all, v_all = forward_prefill(params, CFG, tokens, lens)
        assert logits.shape == (2, 16, CFG.vocab_size)
        assert k_all.shape == (CFG.n_layers, 2, 16, CFG.n_kv_heads, CFG.head_dim)
        assert logits.dtype == jnp.float32

    def test_causality(self, params):
        """Changing a future token must not change past logits."""
        rng = jax.random.PRNGKey(5)
        tokens = jax.random.randint(rng, (1, 12), 0, CFG.vocab_size)
        lens = jnp.array([12])
        logits1, _, _ = forward_prefill(params, CFG, tokens, lens)
        tokens2 = tokens.at[0, 8].set((tokens[0, 8] + 1) % CFG.vocab_size)
        logits2, _, _ = forward_prefill(params, CFG, tokens2, lens)
        np.testing.assert_allclose(logits1[0, :8], logits2[0, :8], atol=1e-4)
        assert not np.allclose(logits1[0, 8:], logits2[0, 8:], atol=1e-4)

    def test_padding_does_not_affect_valid_positions(self, params):
        rng = jax.random.PRNGKey(6)
        tokens = jax.random.randint(rng, (1, 8), 0, CFG.vocab_size)
        logits_short, _, _ = forward_prefill(params, CFG, tokens, jnp.array([8]))
        padded = jnp.pad(tokens, ((0, 0), (0, 8)), constant_values=7)
        logits_padded, _, _ = forward_prefill(params, CFG, padded, jnp.array([8]))
        np.testing.assert_allclose(
            logits_short[0, :8], logits_padded[0, :8], atol=1e-4
        )

    def test_batch_independence(self, params):
        rng = jax.random.PRNGKey(7)
        a = jax.random.randint(rng, (1, 8), 0, CFG.vocab_size)
        b = jax.random.randint(jax.random.PRNGKey(8), (1, 8), 0, CFG.vocab_size)
        la, _, _ = forward_prefill(params, CFG, a, jnp.array([8]))
        lab, _, _ = forward_prefill(
            params, CFG, jnp.concatenate([a, b]), jnp.array([8, 8])
        )
        np.testing.assert_allclose(la[0], lab[0], atol=1e-4)


class TestDecodeConsistency:
    def test_decode_matches_prefill(self, params):
        """Autoregressive decode through the paged cache must reproduce the
        prefill logits for the same token sequence — the core correctness
        invariant of the cache + decode path."""
        S = 12
        rng = jax.random.PRNGKey(9)
        tokens = jax.random.randint(rng, (1, S), 0, CFG.vocab_size)
        full_logits, _, _ = forward_prefill(params, CFG, tokens, jnp.array([S]))

        cache = PagedKVCache(CFG, num_pages=16, page_size=4, max_slots=2,
                             max_pages_per_seq=8, dtype=jnp.float32)
        slot = cache.allocate_slot(1, reserve_decode=S)

        B = cache.max_slots
        step_logits = []
        for t in range(S):
            cache.ensure_decode_capacity(slot)
            tok = jnp.zeros(B, dtype=jnp.int32).at[slot].set(tokens[0, t])
            pos = jnp.zeros(B, dtype=jnp.int32).at[slot].set(t)
            active = jnp.zeros(B, dtype=bool).at[slot].set(True)
            logits, cache.k, cache.v = forward_decode(
                params, CFG, tok, pos, cache.k, cache.v,
                cache.page_tables(), active,
            )
            cache.note_token_appended(slot)
            step_logits.append(logits[slot])

        decoded = jnp.stack(step_logits)  # [S, V]
        np.testing.assert_allclose(decoded, full_logits[0], atol=2e-3, rtol=1e-3)

    def test_prefill_into_cache_then_decode(self, params):
        """Prefill writes the cache; a single decode step continues exactly
        where the prefill's last logits left off."""
        S = 8  # multiple of page_size 4
        rng = jax.random.PRNGKey(10)
        tokens = jax.random.randint(rng, (1, S + 1), 0, CFG.vocab_size)
        full_logits, _, _ = forward_prefill(params, CFG, tokens, jnp.array([S + 1]))

        prompt = tokens[:, :S]
        logits_p, k_all, v_all = forward_prefill(params, CFG, prompt, jnp.array([S]))

        cache = PagedKVCache(CFG, num_pages=16, page_size=4, max_slots=2,
                             max_pages_per_seq=8, dtype=jnp.float32)
        slot = cache.allocate_slot(S, reserve_decode=4)
        cache.write_prefill(slot, k_all[:, 0], v_all[:, 0], S)

        B = cache.max_slots
        tok = jnp.zeros(B, dtype=jnp.int32).at[slot].set(tokens[0, S])
        pos = jnp.zeros(B, dtype=jnp.int32).at[slot].set(S)
        active = jnp.zeros(B, dtype=bool).at[slot].set(True)
        logits_d, _, _ = forward_decode(
            params, CFG, tok, pos, cache.k, cache.v, cache.page_tables(), active
        )
        np.testing.assert_allclose(logits_d[slot], full_logits[0, S], atol=2e-3, rtol=1e-3)

    def test_two_concurrent_slots_do_not_interfere(self, params):
        """Continuous batching invariant: decoding two sequences in the same
        step equals decoding each alone."""
        S = 6
        ra = jax.random.randint(jax.random.PRNGKey(11), (S,), 0, CFG.vocab_size)
        rb = jax.random.randint(jax.random.PRNGKey(12), (S,), 0, CFG.vocab_size)

        def decode_seq(seqs):
            """seqs: dict slot->tokens; decode all actives together."""
            cache = PagedKVCache(CFG, num_pages=32, page_size=4, max_slots=4,
                                 max_pages_per_seq=8, dtype=jnp.float32)
            slots = {name: cache.allocate_slot(1, reserve_decode=S) for name in seqs}
            B = cache.max_slots
            out = {name: [] for name in seqs}
            for t in range(S):
                tok = jnp.zeros(B, dtype=jnp.int32)
                pos = jnp.zeros(B, dtype=jnp.int32)
                act = jnp.zeros(B, dtype=bool)
                for name, seq in seqs.items():
                    s = slots[name]
                    cache.ensure_decode_capacity(s)
                    tok = tok.at[s].set(seq[t])
                    pos = pos.at[s].set(t)
                    act = act.at[s].set(True)
                logits, cache.k, cache.v = forward_decode(
                    params, CFG, tok, pos, cache.k, cache.v, cache.page_tables(), act
                )
                for name in seqs:
                    cache.note_token_appended(slots[name])
                    out[name].append(logits[slots[name]])
            return {k: jnp.stack(v) for k, v in out.items()}

        together = decode_seq({"a": ra, "b": rb})
        alone_a = decode_seq({"a": ra})["a"]
        alone_b = decode_seq({"b": rb})["b"]
        np.testing.assert_allclose(together["a"], alone_a, atol=2e-3, rtol=1e-3)
        np.testing.assert_allclose(together["b"], alone_b, atol=2e-3, rtol=1e-3)
