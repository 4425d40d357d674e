"""Shared-prefix (cascade) prefill + decode: equivalence and bookkeeping.

The engine prefills the burst-shared prompt prefix once per cluster snapshot
(engine/engine.py set_prefix) and each request then prefills only its suffix
against the dense prefix KV (models/llama.forward_prefill_suffix). These
tests prove the prefix path is token-identical to the full-prompt path
(greedy), that the device-side prefix cache hits, and that budgets hold
under chained decode chunks.
"""

import jax
import jax.numpy as jnp
import pytest

from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.models.llama import init_params


TOK = ByteTokenizer()

CFG = LlamaConfig(
    name="prefix-test", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, d_ff=128, max_seq_len=2048, rope_theta=10000.0,
    dtype=jnp.float32, tie_embeddings=True,
)


def make_engine(**kw):
    params = init_params(jax.random.PRNGKey(0), CFG)
    defaults = dict(
        num_pages=128, page_size=64, max_slots=4, max_pages_per_seq=32,
        prefill_buckets=(128, 256, 512, 1024),
        chunk_steps=8, temperature=0.0,
    )
    defaults.update(kw)
    return InferenceEngine(params, CFG, TOK, **defaults)


PREFIX = TOK.encode("CLUSTER STATE: node-a is mostly free, node-b is busy. " * 4)
SUFFIXES = [
    TOK.encode("POD: web-1 wants 0.5 cores."),
    TOK.encode("POD: batch-7 wants 2 cores and 4 GB."),
    TOK.encode("POD: tiny."),
]


class TestChatPromptParts:
    def test_byte_tokenizer_split_is_exact(self):
        pfx, sfx = TOK.chat_prompt_parts("sys prompt", "cluster text", "pod text")
        assert pfx + sfx == TOK.chat_prompt("sys prompt", "cluster text" + "pod text")


class TestPrefixEquivalence:
    def test_prefix_path_matches_full_prompt_greedy(self):
        """Same tokens whether the prefix is cached+shared or prefilled
        inline as part of the full prompt (temperature 0)."""
        full_engine = make_engine()
        fins_full = [
            full_engine.generate(PREFIX + sfx, max_new_tokens=12) for sfx in SUFFIXES
        ]

        pfx_engine = make_engine()
        pfx_engine.set_prefix(PREFIX)
        fins_pfx = [
            pfx_engine.generate(sfx, max_new_tokens=12) for sfx in SUFFIXES
        ]
        for a, b in zip(fins_full, fins_pfx):
            assert a.token_ids == b.token_ids

    def test_batched_admission_matches_serial(self):
        """One add_requests dispatch produces the same tokens as serial
        single-request admissions (greedy)."""
        serial = make_engine()
        serial.set_prefix(PREFIX)
        want = [serial.generate(sfx, max_new_tokens=12).token_ids for sfx in SUFFIXES]

        batched = make_engine()
        batched.set_prefix(PREFIX)
        req_ids = batched.add_requests(list(SUFFIXES), max_new_tokens=12)
        got: dict[int, list[int]] = {}
        while len(got) < len(req_ids):
            for fin in batched.step():
                got[fin.req_id] = fin.token_ids
        assert [got[r] for r in req_ids] == want

    def test_chained_chunks_match_single_steps(self):
        eng1 = make_engine()
        eng1.set_prefix(PREFIX)
        want = eng1.generate(SUFFIXES[0], max_new_tokens=20).token_ids

        eng2 = make_engine()
        eng2.set_prefix(PREFIX)
        req = eng2.add_request(SUFFIXES[0], max_new_tokens=20)
        fins = eng2.step(chunks=4)  # 32 decode steps >= 20 budget, one sync
        assert [f.req_id for f in fins] == [req]
        assert fins[0].token_ids == want

    def test_budget_exact_under_chaining(self):
        eng = make_engine()
        eng.set_prefix(PREFIX)
        eng.add_request(SUFFIXES[0], max_new_tokens=5)
        fins = eng.step(chunks=8)
        assert len(fins) == 1
        assert len(fins[0].token_ids) == 5


class TestPrefixStore:
    def test_prefix_cache_hits_on_reinstall(self):
        eng = make_engine()
        eng.set_prefix(PREFIX)
        assert eng.stats["prefix_prefills"] == 1
        eng.set_prefix(TOK.encode("other cluster state"))
        eng.set_prefix(PREFIX)  # still cached (capacity 2)
        assert eng.stats["prefix_prefills"] == 2
        assert eng.stats["prefix_hits"] == 1

    def test_prefix_lru_evicts(self):
        eng = make_engine()
        a, b, c = (TOK.encode(f"state {i} " * 8) for i in range(3))
        eng.set_prefix(a)
        pfx = next(iter(eng._prefix_cache.values()))
        # byte-budgeted cache: room for two of these prefixes, not three
        eng.PREFIX_CACHE_BYTES = int(pfx.k.nbytes + pfx.v.nbytes) * 2
        eng.set_prefix(b)
        eng.set_prefix(c)  # evicts a (budget = 2 entries)
        eng.set_prefix(a)
        assert eng.stats["prefix_prefills"] == 4
        assert eng.stats["prefix_hits"] == 0

    def test_set_prefix_requires_drained_engine(self):
        eng = make_engine()
        eng.set_prefix(PREFIX)
        eng.add_request(SUFFIXES[0], max_new_tokens=30)
        with pytest.raises(RuntimeError, match="in flight"):
            eng.set_prefix(TOK.encode("new state"))
        # drain, then switching works
        while not [f for f in eng.step()]:
            pass
        eng.set_prefix(TOK.encode("new state"))

    def test_clear_prefix(self):
        eng = make_engine()
        eng.set_prefix(PREFIX)
        eng.set_prefix(None)
        assert eng.prefix_len == 0
        fin = eng.generate(PREFIX + SUFFIXES[0], max_new_tokens=8)
        assert len(fin.token_ids) == 8


class TestPrefixCacheByteBudget:
    def test_eviction_is_byte_budgeted_and_keeps_active(self):
        """The cache cap is BYTES (an 8B-scale prefix is ~800MB; a count cap
        is the wrong unit); the newest (active) entry always survives."""
        import jax.numpy as jnp
        from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
        from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
        from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
        from k8s_llm_scheduler_tpu.models.llama import init_params
        import jax

        tok = ByteTokenizer()
        cfg = LlamaConfig(
            name="pfx-bytes", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=2048,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        eng = InferenceEngine(
            init_params(jax.random.PRNGKey(0), cfg), cfg, tok,
            num_pages=32, page_size=64, max_slots=2, max_pages_per_seq=4,
            prefill_buckets=(128, 256), chunk_steps=4, temperature=0.0,
        )
        one_prefix_bytes = None
        for i in range(4):
            eng.set_prefix(tok.encode(f"[{i}]" + "x" * 200))
            if one_prefix_bytes is None:
                pfx = next(iter(eng._prefix_cache.values()))
                one_prefix_bytes = int(pfx.k.nbytes) + int(pfx.v.nbytes)
        assert len(eng._prefix_cache) == 4  # default budget holds them all

        # shrink the budget to ~2 entries and install one more
        eng.PREFIX_CACHE_BYTES = one_prefix_bytes * 2
        eng.set_prefix(tok.encode("[5]" + "x" * 200))
        assert len(eng._prefix_cache) == 2
        assert list(eng._prefix_cache.values())[-1] is eng._prefix

        # a budget below one entry still keeps the active prefix
        eng.PREFIX_CACHE_BYTES = 1
        eng.set_prefix(tok.encode("[6]" + "x" * 200))
        assert len(eng._prefix_cache) == 1
        assert next(iter(eng._prefix_cache.values())) is eng._prefix
