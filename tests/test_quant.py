"""Weight-only int8 quantization: accuracy, memory, end-to-end decisions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.models.llama import forward_prefill, init_params
from k8s_llm_scheduler_tpu.models.quant import (
    QUANT_KEYS,
    is_quantized,
    param_bytes,
    quantize_params,
    quantize_weight,
)


CFG = LlamaConfig(
    name="quant-test", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, d_ff=128, max_seq_len=512, rope_theta=10000.0,
    dtype=jnp.float32, tie_embeddings=True,
)


class TestQuantizeWeight:
    def test_roundtrip_error_within_half_step(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(3, 32, 48)).astype(np.float32))
        qw = quantize_weight(w)
        assert qw["q"].dtype == jnp.int8
        dequant = qw["q"].astype(jnp.float32) * qw["scale"]
        err = jnp.abs(dequant - w)
        assert float(jnp.max(err - qw["scale"] / 2)) <= 1e-6

    def test_per_channel_scales(self):
        # one huge output channel must not degrade the others
        w = np.ones((1, 16, 4), np.float32) * 0.01
        w[0, :, 2] = 100.0
        qw = quantize_weight(jnp.asarray(w))
        dequant = np.asarray(qw["q"].astype(jnp.float32) * qw["scale"])
        np.testing.assert_allclose(dequant[0, :, 0], w[0, :, 0], rtol=0.01)
        np.testing.assert_allclose(dequant[0, :, 2], w[0, :, 2], rtol=0.01)


class TestQuantizedModel:
    def test_logits_close_and_memory_halved(self):
        params = init_params(jax.random.PRNGKey(0), CFG)
        qparams = quantize_params(params)
        for key in QUANT_KEYS:
            assert is_quantized(qparams["layers"][key])
        # dense weights dominate; total must shrink substantially
        assert param_bytes(qparams) < 0.55 * param_bytes(params) + (
            param_bytes({"e": params["embed"]}) * 2
        )

        tokens = jnp.asarray(
            np.random.default_rng(1).integers(1, 256, size=(2, 64)), jnp.int32
        )
        lens = jnp.asarray([64, 40], jnp.int32)
        fp = jax.jit(forward_prefill, static_argnums=(1,))
        logits_f, _, _ = fp(params, CFG, tokens, lens)
        logits_q, _, _ = fp(qparams, CFG, tokens, lens)
        a = np.asarray(logits_f).ravel()
        b = np.asarray(logits_q).ravel()
        corr = np.corrcoef(a, b)[0, 1]
        assert corr > 0.995, corr

    def test_engine_decisions_with_quantized_weights(self):
        import json

        from k8s_llm_scheduler_tpu.engine.constrained import build_decision_dfa
        from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
        from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        cfg = LlamaConfig(
            name="quant-engine", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=2048,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        params = quantize_params(init_params(jax.random.PRNGKey(0), cfg))
        eng = InferenceEngine(
            params, cfg, tok, num_pages=64, page_size=64, max_slots=2,
            max_pages_per_seq=8, prefill_buckets=(128, 256), chunk_steps=4,
            temperature=0.0,
        )
        names = ["node-0", "node-1"]
        eng.set_grammar(build_decision_dfa(tok, names, max_reason_tokens=5))
        fins = eng.decide_wave(
            [tok.chat_prompt("sys", "quantized decision")], max_new_tokens=120
        )
        obj = json.loads(fins[0].text)
        assert obj["selected_node"] in names

    def test_backend_builder_quantize_flag(self):
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend

        cfg512 = LlamaConfig(
            name="quant-512", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=512,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        backend = build_local_backend(
            cfg=cfg512, quantize="int8", max_slots=2, num_pages=32, page_size=64,
            prefill_buckets=(128,), chunk_steps=4, max_new_tokens=100,
        )
        try:
            assert is_quantized(backend.engine.params["layers"]["wq"])
        finally:
            backend.close()

    def test_unknown_quantization_rejected(self):
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend

        cfg512 = LlamaConfig(
            name="quant-512b", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=512,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        with pytest.raises(ValueError, match="unknown quantization"):
            build_local_backend(cfg=cfg512, quantize="fp4")
