"""Ragged-M decode matmul (ops/ragged_matmul.py): kernel parity + the
ragged forward_block_decode path vs the dense XLA path.

SCALING.md's wave roofline: 62% of block-decode compute at the 250-token
point is F-width padding, decided on device by the DFA walk — this kernel
is the named fix. Interpret mode on CPU exercises the same code path the
chip runs (pattern: tests/test_pallas_attention.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.ops.ragged_matmul import ragged_matmul

pytestmark = pytest.mark.slow  # jit/pallas compiles: full-suite tier


class TestRaggedMatmulKernel:
    def _xw(self, m=96, k=256, n=384, dtype=jnp.float32, seed=0):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(m, k)), dtype)
        w = jnp.asarray(rng.normal(size=(k, n)), dtype)
        return x, w

    @pytest.mark.parametrize("total", [1, 7, 16, 64, 96])
    def test_matches_dense_on_valid_rows(self, total):
        x, w = self._xw()
        out = ragged_matmul(x, w, jnp.int32(total), bm=16, bn=128, bk=128)
        ref = x @ w
        np.testing.assert_allclose(
            np.asarray(out[:total]), np.asarray(ref[:total]),
            rtol=1e-4, atol=1e-4,
        )
        # rows beyond the last computed M-tile are zero by construction
        tile_end = -(-total // 16) * 16
        assert np.allclose(np.asarray(out[min(tile_end, 96):]), 0.0)

    def test_unaligned_k_and_n_are_padded(self):
        x, w = self._xw(m=40, k=200, n=130)
        out = ragged_matmul(x, w, jnp.int32(40), bm=8, bn=128, bk=128)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(x @ w), rtol=1e-4, atol=1e-4
        )

    def test_int8_weight_dict_matches_dense_dispatch(self):
        from k8s_llm_scheduler_tpu.models.llama import _dense

        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(64, 256)), jnp.bfloat16)
        w = {
            "q": jnp.asarray(rng.integers(-127, 128, size=(256, 384)), jnp.int8),
            "scale": jnp.asarray(rng.uniform(0.01, 0.1, size=(1, 384)), jnp.float32),
        }
        out = ragged_matmul(x, w, jnp.int32(64), bm=16)
        ref = _dense(x, w, "mk,kn->mn")
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=0.05, atol=0.05,
        )


class TestRaggedBlockDecode:
    """forward_block_decode(ragged=True) must match the dense path on the
    valid positions: logits at every live row, and every exposed gen-KV
    entry."""

    def _case(self, seed=0):
        from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
        from k8s_llm_scheduler_tpu.models.llama import init_params

        cfg = LlamaConfig(
            name="ragged-test", vocab_size=512, d_model=128, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=256, max_seq_len=2048,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        params = init_params(jax.random.PRNGKey(seed), cfg)
        rng = np.random.default_rng(seed)
        R, F, Ss, cap, Sp = 4, 8, 16, 24, 32
        L, kv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        blk_len = jnp.asarray([5, 1, 8, 0], jnp.int32)  # ragged incl. 0
        j = jnp.arange(F)
        blk_valid = j[None, :] < blk_len[:, None]
        blk_tok = jnp.asarray(
            rng.integers(1, 256, size=(R, F)), jnp.int32
        ) * blk_valid
        suffix_lens = jnp.asarray([10, 16, 3, 7], jnp.int32)
        tail = jnp.asarray([2, 0, 5, 9], jnp.int32)
        positions = (
            Sp + suffix_lens[:, None] + tail[:, None] + j[None, :]
        ).astype(jnp.int32)
        def t(*shape):
            return jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
        return cfg, params, dict(
            blk_tok=blk_tok, blk_valid=blk_valid, blk_len=blk_len,
            positions=positions,
            k_sfx=t(L, R, Ss, kv, hd), v_sfx=t(L, R, Ss, kv, hd),
            suffix_lens=suffix_lens,
            gen_k=t(L, R, cap + F, kv, hd), gen_v=t(L, R, cap + F, kv, hd),
            tail=tail,
            prefix_k_all=t(L, Sp, kv, hd), prefix_v_all=t(L, Sp, kv, hd),
            prefix_len=jnp.int32(Sp),
        )

    def test_engine_decisions_identical_dense_vs_ragged(self):
        """The full serving path (prompt -> wave -> parse) at temperature 0
        must produce THE SAME decisions with decode_matmul='ragged'."""
        from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend
        from k8s_llm_scheduler_tpu.testing import pod_burst, synthetic_cluster

        cluster = synthetic_cluster(4)
        nodes = cluster.get_node_metrics()
        cluster.close()
        pods = [raw_pod_to_spec(p) for p in pod_burst(3, distinct_shapes=3)]
        picks = {}
        for impl in ("dense", "ragged"):
            backend = build_local_backend(
                model="tiny", temperature=0.0, max_slots=4, num_pages=64,
                prefill_buckets=(512, 1024, 2048), decode_matmul=impl,
                compile_cache_dir=None,
            )
            try:
                picks[impl] = [
                    backend.get_scheduling_decision(p, nodes).selected_node
                    for p in pods
                ]
            finally:
                backend.close()
        assert picks["dense"] == picks["ragged"], picks

    def test_ragged_matches_dense(self):
        from k8s_llm_scheduler_tpu.models.llama import forward_block_decode

        cfg, params, kw = self._case()
        logits_d, gk_d, gv_d, counts_d = forward_block_decode(
            params, cfg, **kw, ragged=False
        )
        logits_r, gk_r, gv_r, counts_r = forward_block_decode(
            params, cfg, **kw, ragged=True
        )
        # both bodies narrow the prefix part to the same rung
        np.testing.assert_array_equal(np.asarray(counts_r), np.asarray(counts_d))
        live = np.asarray(kw["blk_len"]) > 0
        np.testing.assert_allclose(
            np.asarray(logits_r)[live], np.asarray(logits_d)[live],
            rtol=2e-3, atol=2e-3,
        )
        # exposed gen-KV entries (slot < tail + len) must be identical; what
        # the padded block positions left past them is never read
        tail = np.asarray(kw["tail"])
        blk_len = np.asarray(kw["blk_len"])
        F = np.asarray(kw["blk_tok"]).shape[1]
        cap = np.asarray(kw["gen_k"]).shape[2] - F
        for r in range(len(tail)):
            hi = tail[r] + blk_len[r]
            np.testing.assert_allclose(
                np.asarray(gk_r)[:, r, :hi], np.asarray(gk_d)[:, r, :hi],
                rtol=2e-3, atol=2e-3,
            )
            np.testing.assert_allclose(
                np.asarray(gv_r)[:, r, :hi], np.asarray(gv_d)[:, r, :hi],
                rtol=2e-3, atol=2e-3,
            )
            assert hi <= cap
