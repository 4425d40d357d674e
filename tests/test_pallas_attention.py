"""Pallas paged decode attention == XLA reference path.

Runs the kernel in interpreter mode on CPU (the same code path the chip
runs compiled), asserting numerical equivalence with
ops/attention.paged_decode_attention across GQA ratios, ragged sequence
lengths, and page-boundary crossings.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.ops.attention import paged_decode_attention
from k8s_llm_scheduler_tpu.ops.pallas_paged_attention import (
    paged_decode_attention_pallas,
)


def _random_case(
    rng,
    B=3,
    n_heads=8,
    n_kv=4,
    hd=64,
    num_pages=16,
    page_size=32,
    max_pages=4,
    seq_lens=None,
):
    q = jnp.asarray(rng.normal(size=(B, n_heads, hd)).astype(np.float32))
    k_cache = jnp.asarray(
        rng.normal(size=(num_pages, page_size, n_kv, hd)).astype(np.float32)
    )
    v_cache = jnp.asarray(
        rng.normal(size=(num_pages, page_size, n_kv, hd)).astype(np.float32)
    )
    # distinct pages per sequence (page 0 is the conventional scratch page)
    ids = rng.choice(np.arange(1, num_pages), size=(B, max_pages), replace=False)
    page_table = jnp.asarray(ids.astype(np.int32))
    if seq_lens is None:
        seq_lens = rng.integers(1, max_pages * page_size + 1, size=(B,))
    seq_lens = jnp.asarray(np.asarray(seq_lens, dtype=np.int32))
    return q, k_cache, v_cache, page_table, seq_lens


class TestPallasPagedDecode:
    def test_matches_xla_reference(self):
        rng = np.random.default_rng(0)
        args = _random_case(rng)
        ref = paged_decode_attention(*args)
        out = paged_decode_attention_pallas(*args)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_gqa_ratios(self):
        rng = np.random.default_rng(1)
        for n_heads, n_kv in ((8, 8), (8, 2), (4, 1)):
            args = _random_case(rng, n_heads=n_heads, n_kv=n_kv)
            ref = paged_decode_attention(*args)
            out = paged_decode_attention_pallas(*args)
            np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_page_boundary_lengths(self):
        """seq_len exactly at / one beyond each page boundary."""
        rng = np.random.default_rng(2)
        page_size, max_pages = 32, 4
        for L in (1, 31, 32, 33, 64, 127, 128):
            args = _random_case(
                rng, B=2, page_size=page_size, max_pages=max_pages,
                seq_lens=[L, max(1, L - 1)],
            )
            ref = paged_decode_attention(*args)
            out = paged_decode_attention_pallas(*args)
            np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_bfloat16_inputs(self):
        rng = np.random.default_rng(3)
        q, k, v, pt, sl = _random_case(rng)
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        ref = paged_decode_attention(q, k, v, pt, sl)
        out = paged_decode_attention_pallas(q, k, v, pt, sl)
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), rtol=2e-2, atol=2e-2
        )

    def test_single_token_sequence(self):
        rng = np.random.default_rng(4)
        args = _random_case(rng, B=1, seq_lens=[1])
        ref = paged_decode_attention(*args)
        out = paged_decode_attention_pallas(*args)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


class TestModelIntegration:
    def test_forward_decode_with_pallas_attention(self):
        """forward_decode produces the same logits with either kernel."""
        from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
        from k8s_llm_scheduler_tpu.models.llama import forward_decode, init_params

        cfg = LlamaConfig(
            name="pallas-int", vocab_size=128, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=256,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        num_pages, page_size, max_pages = 8, 32, 2
        B = 2
        k_cache = jnp.zeros((cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim))
        v_cache = jnp.zeros_like(k_cache)
        page_table = jnp.asarray([[1, 2], [3, 4]], dtype=jnp.int32)
        tokens = jnp.asarray([5, 9], dtype=jnp.int32)
        positions = jnp.asarray([3, 17], dtype=jnp.int32)
        active = jnp.asarray([True, True])

        logits_xla, k1, v1 = jax.jit(forward_decode, static_argnums=(1,))(
            params, cfg, tokens, positions, k_cache, v_cache, page_table, active
        )
        logits_pl, k2, v2 = jax.jit(
            forward_decode, static_argnums=(1, 8)
        )(
            params, cfg, tokens, positions, k_cache, v_cache, page_table,
            active, "pallas",
        )
        np.testing.assert_allclose(logits_pl, logits_xla, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(k2, k1, rtol=1e-6, atol=1e-6)


class TestPartials:
    def test_partials_merge_equals_full(self):
        """Kernel partials merged via merge_attention_parts == normalized."""
        from k8s_llm_scheduler_tpu.ops.attention import merge_attention_parts
        from k8s_llm_scheduler_tpu.ops.pallas_paged_attention import (
            paged_decode_attention_parts,
        )

        rng = np.random.default_rng(7)
        q, k, v, pt, sl = _random_case(rng)
        full = paged_decode_attention_pallas(q, k, v, pt, sl)
        o, m, l = paged_decode_attention_parts(q, k, v, pt, sl)
        merged = merge_attention_parts([(o, m, l)])
        B, n_heads, hd = q.shape
        merged = merged.reshape(B, n_heads, hd)
        np.testing.assert_allclose(merged, full, rtol=2e-5, atol=2e-5)

    def test_empty_region_contributes_zero_weight(self):
        from k8s_llm_scheduler_tpu.ops.attention import (
            attend_part,
            merge_attention_parts,
        )
        from k8s_llm_scheduler_tpu.ops.pallas_paged_attention import (
            paged_decode_attention_parts,
        )
        import jax.numpy as jnp

        rng = np.random.default_rng(8)
        q, k, v, pt, sl = _random_case(rng, B=2)
        zero_lens = jnp.zeros_like(sl)
        o, m, l = paged_decode_attention_parts(q, k, v, pt, zero_lens)
        # merge with a dense part over some other tokens: result must equal
        # the dense part alone
        B, n_heads, hd = q.shape
        n_kv = k.shape[2]
        g = n_heads // n_kv
        other_k = jnp.asarray(rng.normal(size=(B, 5, n_kv, hd)).astype(np.float32))
        other_v = jnp.asarray(rng.normal(size=(B, 5, n_kv, hd)).astype(np.float32))
        qg = (q.astype(jnp.float32) * hd**-0.5).reshape(B, n_kv, g, hd)
        mask = jnp.ones((B, 1, 1, 5), bool)
        dense = attend_part(qg, other_k, other_v, mask, "bkgh,blkh->bkgl")
        alone = merge_attention_parts([dense]).reshape(B, n_heads, hd)
        both = merge_attention_parts([dense, (o, m, l)]).reshape(B, n_heads, hd)
        np.testing.assert_allclose(both, alone, rtol=1e-6, atol=1e-6)


class TestEngineChunkedPallas:
    def test_chunked_decode_pallas_matches_gather(self):
        """Engine greedy generation identical with gather vs pallas own-token
        attention (CPU interpret mode)."""
        import jax
        from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
        from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
        from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
        from k8s_llm_scheduler_tpu.models.llama import init_params

        tok = ByteTokenizer()
        cfg = LlamaConfig(
            name="pallas-chunk", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=2048,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        kw = dict(
            num_pages=64, page_size=64, max_slots=2, max_pages_per_seq=8,
            prefill_buckets=(128, 256), chunk_steps=6, temperature=0.0,
        )
        eng_g = InferenceEngine(params, cfg, tok, paged_attn="gather", **kw)
        eng_p = InferenceEngine(params, cfg, tok, paged_attn="pallas", **kw)
        prompt = tok.chat_prompt("sys", "compare own-token attention impls")
        a = eng_g.generate(prompt, max_new_tokens=20)
        b = eng_p.generate(prompt, max_new_tokens=20)
        assert a.token_ids == b.token_ids


class TestFlashPrefixAttention:
    """Parity of the flash shared-prefix kernel (interpret mode on CPU)
    against the XLA attend_part cascade partials."""

    def _reference(self, q, pk, pv, plen):
        from k8s_llm_scheduler_tpu.ops.attention import attend_part

        B, S, n_heads, hd = q.shape
        n_kv = pk.shape[1]
        g = n_heads // n_kv
        qg = (q.astype(jnp.float32) * hd**-0.5).reshape(B, S, n_kv, g, hd)
        Sp = pk.shape[0]
        mask = (jnp.arange(Sp) < plen)[None, None, None, None, :]
        return attend_part(qg, pk, pv, mask, "bqkgh,skh->bkgqs")

    @pytest.mark.parametrize("plen", [0, 1, 130, 256])
    def test_partials_match_xla(self, plen):
        import jax
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            flash_prefix_attention_parts,
        )

        B, S, n_heads, n_kv, hd, Sp = 2, 16, 4, 2, 64, 256
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, n_heads, hd), dtype=jnp.float32)
        pk = jax.random.normal(ks[1], (Sp, n_kv, hd), dtype=jnp.float32)
        pv = jax.random.normal(ks[2], (Sp, n_kv, hd), dtype=jnp.float32)
        plen_arr = jnp.int32(plen)

        o, m, l = flash_prefix_attention_parts(q, pk, pv, plen_arr, interpret=True)
        o_r, m_r, l_r = self._reference(q, pk, pv, plen_arr)
        if plen == 0:
            # Both paths report zero weight (l*exp(m-M) == 0 in the merge);
            # the XLA path leaves p==1 garbage in o/l, so only m must agree.
            np.testing.assert_allclose(np.asarray(m), np.asarray(m_r))
            assert float(jnp.max(l)) == 0.0
            return
        # bf16 matmul operands inside the kernel (vs f32 in the reference):
        # tolerances sized to bf16 rounding; masking/indexing bugs show as
        # O(1) errors and still fail.
        np.testing.assert_allclose(np.asarray(m), np.asarray(m_r), rtol=2e-2, atol=1e-2)
        np.testing.assert_allclose(np.asarray(l), np.asarray(l_r), rtol=2e-2, atol=1e-2)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_r), rtol=5e-2, atol=5e-2)

    def test_cascade_merge_matches_full_xla(self):
        """chunk_attention_with_prefix with the pallas prefix part equals the
        pure-XLA cascade end to end."""
        import jax
        from k8s_llm_scheduler_tpu.ops import attention as A

        B, S, n_heads, n_kv, hd, Sp = 2, 32, 4, 2, 64, 256
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        q = jax.random.normal(ks[0], (B, S, n_heads, hd), dtype=jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, n_kv, hd), dtype=jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, n_kv, hd), dtype=jnp.float32)
        pk = jax.random.normal(ks[3], (Sp, n_kv, hd), dtype=jnp.float32)
        pv = jax.random.normal(ks[4], (Sp, n_kv, hd), dtype=jnp.float32)
        lens = jnp.array([S, S - 5], dtype=jnp.int32)
        plen = jnp.int32(200)

        ref = A.chunk_attention_with_prefix(q, kc, vc, lens, pk, pv, plen)
        A.set_prefix_attn_impl("pallas")
        try:
            got = A.chunk_attention_with_prefix(q, kc, vc, lens, pk, pv, plen)
        finally:
            A.set_prefix_attn_impl("auto")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-2, atol=2e-2)


class TestFlashCausalAttention:
    """Parity of the flash causal in-chunk kernel (interpret mode) against
    the XLA attend_part with the causal+valid mask."""

    def _reference(self, q, k, v, lens):
        from k8s_llm_scheduler_tpu.ops.attention import attend_part

        B, S, n_heads, hd = q.shape
        n_kv = k.shape[2]
        g = n_heads // n_kv
        qg = (q.astype(jnp.float32) * hd**-0.5).reshape(B, S, n_kv, g, hd)
        pos = jnp.arange(S)
        causal = pos[:, None] >= pos[None, :]
        valid = pos[None, :] < lens[:, None]
        mask = causal[None, None, None, :, :] & valid[:, None, None, None, :]
        return attend_part(qg, k, v, mask, "bqkgh,bskh->bkgqs")

    @pytest.mark.parametrize("lens", [(128, 128), (128, 65), (40, 1)])
    def test_partials_match_xla(self, lens):
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            flash_causal_attention_parts,
        )

        B, S, n_heads, n_kv, hd = 2, 128, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (B, S, n_heads, hd), dtype=jnp.float32)
        k = jax.random.normal(ks[1], (B, S, n_kv, hd), dtype=jnp.float32)
        v = jax.random.normal(ks[2], (B, S, n_kv, hd), dtype=jnp.float32)
        lens_arr = jnp.asarray(lens, dtype=jnp.int32)

        o, m, l = flash_causal_attention_parts(q, k, v, lens_arr, interpret=True)
        o_r, m_r, l_r = self._reference(q, k, v, lens_arr)
        # compare only rows whose queries are meaningful (pos < len): rows
        # past a sequence's end hold garbage on BOTH paths (merge ignores
        # them downstream), but their garbage need not be bit-equal.
        out = np.asarray(o / jnp.maximum(l[..., None], 1e-30))
        ref = np.asarray(o_r / jnp.maximum(l_r[..., None], 1e-30))
        for b in range(B):
            n = lens[b]
            np.testing.assert_allclose(
                out[b, :, :, :n], ref[b, :, :, :n], rtol=5e-2, atol=5e-2
            )
            np.testing.assert_allclose(
                np.asarray(m)[b, :, :, :n], np.asarray(m_r)[b, :, :, :n],
                rtol=2e-2, atol=1e-2,
            )

    def test_cascade_with_both_kernels_matches_xla(self):
        """chunk_attention_with_prefix with BOTH pallas parts (prefix +
        causal chunk) equals the pure-XLA cascade."""
        from k8s_llm_scheduler_tpu.ops import attention as A

        B, S, n_heads, n_kv, hd, Sp = 2, 128, 4, 2, 64, 256
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        q = jax.random.normal(ks[0], (B, S, n_heads, hd), dtype=jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, n_kv, hd), dtype=jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, n_kv, hd), dtype=jnp.float32)
        pk = jax.random.normal(ks[3], (Sp, n_kv, hd), dtype=jnp.float32)
        pv = jax.random.normal(ks[4], (Sp, n_kv, hd), dtype=jnp.float32)
        lens = jnp.array([S, S - 41], dtype=jnp.int32)
        plen = jnp.int32(130)

        ref = A.chunk_attention_with_prefix(q, kc, vc, lens, pk, pv, plen)
        got = A.chunk_attention_with_prefix(
            q, kc, vc, lens, pk, pv, plen, prefix_impl="pallas"
        )
        # rows past a sequence's length are garbage on both paths
        for b, n in enumerate([S, S - 41]):
            np.testing.assert_allclose(
                np.asarray(got)[b, :n], np.asarray(ref)[b, :n],
                rtol=2e-2, atol=2e-2,
            )


class TestShardedKernels:
    """shard_map-wrapped kernels over a tp-sharded kv-head axis == the
    unsharded kernels bit-for-bit (same per-shard program, interpret mode
    on the virtual CPU mesh). This is the layer that keeps flash attention
    on the 70B tp=8 serving path — GSPMD cannot partition a pallas_call."""

    def _mesh(self, tp):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:tp]), ("tp",))

    @pytest.mark.parametrize("tp", [2, 4])
    def test_prefix_shmap_matches_unsharded(self, tp):
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            flash_prefix_attention_parts,
            flash_prefix_attention_parts_shmap,
        )

        B, S, n_heads, n_kv, hd, Sp = 2, 16, 8, 4, 64, 256
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, n_heads, hd), dtype=jnp.float32)
        pk = jax.random.normal(ks[1], (Sp, n_kv, hd), dtype=jnp.float32)
        pv = jax.random.normal(ks[2], (Sp, n_kv, hd), dtype=jnp.float32)
        plen = jnp.int32(130)
        ref = flash_prefix_attention_parts(q, pk, pv, plen, interpret=True)
        out = flash_prefix_attention_parts_shmap(
            q, pk, pv, plen, self._mesh(tp), "tp", interpret=True
        )
        for r, o in zip(ref, out):
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(r), rtol=1e-5, atol=1e-5
            )

    def test_causal_shmap_matches_unsharded(self):
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            flash_causal_attention_parts,
            flash_causal_attention_parts_shmap,
        )

        B, S, n_heads, n_kv, hd = 2, 128, 8, 4, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, S, n_heads, hd), dtype=jnp.float32)
        k = jax.random.normal(ks[1], (B, S, n_kv, hd), dtype=jnp.float32)
        v = jax.random.normal(ks[2], (B, S, n_kv, hd), dtype=jnp.float32)
        lens = jnp.array([100, 128], dtype=jnp.int32)
        ref = flash_causal_attention_parts(q, k, v, lens, interpret=True)
        out = flash_causal_attention_parts_shmap(
            q, k, v, lens, self._mesh(2), "tp", interpret=True
        )
        for r, o in zip(ref, out):
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(r), rtol=1e-5, atol=1e-5
            )

    def test_paged_shmap_matches_unsharded(self):
        from k8s_llm_scheduler_tpu.ops.pallas_paged_attention import (
            paged_decode_attention_parts,
            paged_decode_attention_parts_shmap,
        )

        rng = np.random.default_rng(0)
        args = _random_case(rng)
        ref = paged_decode_attention_parts(*args, interpret=True)
        out = paged_decode_attention_parts_shmap(
            *args, self._mesh(4), "tp", interpret=True
        )
        for r, o in zip(ref, out):
            np.testing.assert_allclose(
                np.asarray(o), np.asarray(r), rtol=1e-5, atol=1e-5
            )

    @pytest.mark.parametrize("shards,ok", [(1, True), (2, True), (3, False)])
    def test_supported_checks_per_shard(self, shards, ok):
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            causal_attention_supported,
            prefix_attention_supported,
        )

        q_shape = (2, 128, 8, 64)  # n_heads=8; n_kv=4 below
        assert prefix_attention_supported(q_shape, 4, 256, shards=shards) is ok
        assert causal_attention_supported(q_shape, 4, shards=shards) is ok
