"""Inference engine: tokenizer, constrained DFA, fused decode, local backend."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.engine.constrained import (
    build_decision_dfa,
    first_token_of,
)
from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.models.llama import init_params
from k8s_llm_scheduler_tpu.utils.json_extract import parse_decision_json


TOK = ByteTokenizer()

ENGINE_CFG = LlamaConfig(
    name="engine-test", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, d_ff=128, max_seq_len=2048, rope_theta=10000.0,
    dtype=jnp.float32, tie_embeddings=True,
)


def make_engine(**kwargs):
    params = init_params(jax.random.PRNGKey(0), ENGINE_CFG)
    return InferenceEngine(
        params, ENGINE_CFG, TOK,
        num_pages=128, page_size=64, max_slots=4, max_pages_per_seq=32,
        prefill_buckets=(128, 256, 512, 1024),
        chunk_steps=8, temperature=0.0, **kwargs,
    )


@pytest.fixture(scope="module")
def engine():
    return make_engine()


class TestTokenizer:
    def test_roundtrip(self):
        text = 'node-1 {"x": 0.5}'
        assert TOK.decode(TOK.encode(text)) == text

    def test_specials_not_in_byte_range(self):
        ids = TOK.chat_prompt("sys", "user")
        assert ids[0] == TOK.BOS
        assert TOK.SYSTEM in ids and TOK.USER in ids and TOK.ASSISTANT in ids
        assert TOK.decode(ids) == "sysuser"  # specials skipped

    def test_vocab_bounds(self):
        ids = TOK.encode("".join(chr(c) for c in range(32, 127)))
        assert all(1 <= i <= 256 for i in ids)
        assert TOK.vocab_size == 512


class TestNumericTokenizer:
    """Single-token integers (engine/tokenizer.NumericTokenizer) — the
    distillation-grade vocab (VERDICT r4 item 1 route b)."""

    def _tok(self):
        from k8s_llm_scheduler_tpu.engine.tokenizer import NumericTokenizer

        return NumericTokenizer()

    def test_integers_are_single_tokens(self):
        t = self._tok()
        assert t.encode("47") == [t.NUM_BASE + 47]
        assert t.encode("0") == [t.NUM_BASE + 0]
        assert t.encode("999") == [t.NUM_BASE + 999]
        # metric rendering: one token per integer part
        assert t.encode("47.3") == [t.NUM_BASE + 47, t.encode(".")[0], t.NUM_BASE + 3]

    def test_leading_zero_and_long_runs_fall_back_to_bytes(self):
        t = self._tok()
        assert all(1 <= i <= 256 for i in t.encode("007"))
        assert all(1 <= i <= 256 for i in t.encode("1234"))

    def test_roundtrip_on_prompt_surface(self):
        t = self._tok()
        for s in (
            "CPU: 47.3% used, 16.00 cores allocatable",
            "Pods: 23/110",
            '{"selected_node": "node-2", "confidence": 0.4, '
            '"reasoning": "resource balanced"}',
            "x007y 1234 0.85 100%",
        ):
            assert t.decode(t.encode(s)) == s

    def test_vocab_is_mxu_padded(self):
        t = self._tok()
        assert t.vocab_size == 1536 and t.vocab_size % 128 == 0

    def test_dfa_builds_and_digit_is_choice_point(self):
        t = self._tok()
        names = [f"node-{k}" for k in range(4)]
        dfa = build_decision_dfa(t, names, max_reason_tokens=10)
        # walk the forced skeleton to the name choice: the state after
        # '{"selected_node": "node-' must offer exactly the 4 NUM tokens
        state = dfa.start_state
        for tok in t.encode('{"selected_node": "node-'):
            state = dfa.next(state, tok)
        assert sorted(dfa.allowed_tokens(state)) == [
            t.NUM_BASE + k for k in range(4)
        ]


class TestDecisionDFA:
    NAMES = ["node-a", "node-b", "node-abc"]

    def test_every_state_has_an_out_edge(self):
        dfa = build_decision_dfa(TOK, self.NAMES, max_reason_tokens=10)
        assert all(len(out) > 0 for out in dfa.edges)

    def test_first_token_is_open_brace(self):
        dfa = build_decision_dfa(TOK, self.NAMES)
        assert first_token_of(dfa) == TOK.encode("{")[0]

    def _random_walk(self, dfa, rng, max_len=400):
        state = dfa.start_state
        out = []
        for _ in range(max_len):
            if state == dfa.done_state:
                break
            opts = dfa.allowed_tokens(state)
            tok = int(rng.choice(opts))
            out.append(tok)
            state = dfa.next(state, tok)
        assert state == dfa.done_state, "walk must reach done"
        return out

    def test_random_walks_always_parse(self):
        """ANY path through the DFA is valid JSON with a valid node name —
        the can't-fail-by-construction property replacing the reference's
        validate-then-fallback (scheduler.py:453-465)."""
        dfa = build_decision_dfa(TOK, self.NAMES, max_reason_tokens=20)
        rng = np.random.default_rng(0)
        for _ in range(50):
            toks = self._random_walk(dfa, rng)
            text = TOK.decode([t for t in toks if t != TOK.EOS])
            obj = json.loads(text)  # strict parse, no extractor needed
            assert obj["selected_node"] in self.NAMES
            assert 0.0 <= obj["confidence"] <= 1.0
            assert isinstance(obj["reasoning"], str)

    def test_prefix_names_both_reachable(self):
        """node-a is a prefix of node-abc; both must be emittable."""
        dfa = build_decision_dfa(TOK, ["node-a", "node-abc"], max_reason_tokens=5)
        rng = np.random.default_rng(1)
        seen = set()
        for _ in range(200):
            toks = self._random_walk(dfa, rng)
            text = TOK.decode([t for t in toks if t != TOK.EOS])
            seen.add(json.loads(text)["selected_node"])
        assert seen == {"node-a", "node-abc"}

    def test_reason_length_cap(self):
        dfa = build_decision_dfa(TOK, ["n1"], max_reason_tokens=5)
        rng = np.random.default_rng(2)
        for _ in range(20):
            toks = self._random_walk(dfa, rng, max_len=200)
            obj = json.loads(TOK.decode([t for t in toks if t != TOK.EOS]))
            assert len(obj["reasoning"]) <= 5

    def test_empty_names_rejected(self):
        with pytest.raises(ValueError):
            build_decision_dfa(TOK, [])


class TestEngine:
    def test_unconstrained_generate_caps_at_max_tokens(self, engine):
        prompt = TOK.chat_prompt("system", "hello world")
        fin = engine.generate(prompt, max_new_tokens=12)
        assert 1 <= len(fin.token_ids) <= 12
        assert fin.latency_ms > 0
        assert engine.free_slots == engine.max_slots  # slot released

    def test_greedy_is_deterministic(self, engine):
        prompt = TOK.chat_prompt("system", "determinism")
        a = engine.generate(prompt, max_new_tokens=10)
        b = engine.generate(prompt, max_new_tokens=10)
        assert a.token_ids == b.token_ids

    def test_constrained_generate_emits_valid_decision(self, engine):
        names = ["node-0", "node-1", "node-2"]
        engine.set_grammar(build_decision_dfa(TOK, names, max_reason_tokens=30))
        try:
            prompt = TOK.chat_prompt("pick a node", "cluster state here")
            fin = engine.generate(prompt, max_new_tokens=150)
            obj = json.loads(fin.text.replace("\x00", ""))
            assert obj["selected_node"] in names
            assert 0.0 <= obj["confidence"] <= 1.0
            parsed = parse_decision_json(fin.text)
            assert parsed is not None
        finally:
            engine.set_grammar(None)

    def test_concurrent_requests_complete(self, engine):
        names = ["node-0", "node-1"]
        engine.set_grammar(build_decision_dfa(TOK, names, max_reason_tokens=20))
        try:
            ids = [
                engine.add_request(
                    TOK.chat_prompt("sys", f"pod-{i} needs a node"), 150
                )
                for i in range(3)
            ]
            done = {}
            for _ in range(80):
                for fin in engine.step():
                    done[fin.req_id] = fin
                if len(done) == 3:
                    break
            assert set(done) == set(ids)
            for fin in done.values():
                assert json.loads(fin.text)["selected_node"] in names
        finally:
            engine.set_grammar(None)

    def test_backpressure_when_slots_full(self, engine):
        prompt = TOK.chat_prompt("s", "u")
        held = [engine.add_request(prompt, 200) for _ in range(engine.max_slots)]
        with pytest.raises(RuntimeError, match="no free slots"):
            engine.add_request(prompt, 10)
        # drain
        while engine.has_active:
            engine.step()
        assert engine.free_slots == engine.max_slots
        assert len(held) == engine.max_slots

    def test_oversized_prompt_rejected(self, engine):
        with pytest.raises(ValueError, match="exceeds largest prefill bucket"):
            engine.add_request([1] * 5000, 10)

    def test_stats_accumulate(self, engine):
        stats = engine.get_stats()
        assert stats["requests"] > 0
        assert stats["completed"] > 0
        assert stats["decode_tokens"] > 0
        assert stats["pages_free"] > 0


class TestDecideWave:
    """The fused single-dispatch decision wave (engine.decide_wave)."""

    def test_wave_matches_chunked_greedy(self, engine):
        names = ["node-0", "node-1", "node-2"]
        engine.set_grammar(build_decision_dfa(TOK, names, max_reason_tokens=20))
        try:
            prompts = [
                TOK.chat_prompt("pick a node", f"pod-{i} wants scheduling")
                for i in range(3)
            ]
            fins = engine.decide_wave(prompts, max_new_tokens=150)
            assert len(fins) == 3
            # greedy (temperature=0) chunked path must produce identical ids
            for prompt, fin in zip(prompts, fins):
                chunked = engine.generate(prompt, max_new_tokens=150)
                assert chunked.token_ids == fin.token_ids
                obj = json.loads(fin.text)
                assert obj["selected_node"] in names
        finally:
            engine.set_grammar(None)

    # what the parent of PR 31 served (block K/V scattered token by token
    # into a cap + 1 buffer, in every layer); every id below 257 is a byte + 1
    PARENT_WAVE_TEXTS = (
        '{"selected_node": "node-0", "confidence": 1.0, "reasoning": "/nnnnnnnn]n]ynnnnnnn"}',
        '{"selected_node": "node-1", "confidence": 1.0, "reasoning": "y3nnnnnnn$nCnnnnnnnn"}',
        '{"selected_node": "node-1", "confidence": 1.0, "reasoning": "dnnnnnnn]n]/nnnnnnnn"}',
    )

    @pytest.mark.parametrize("decode_matmul", ["dense", "ragged"])
    def test_wave_token_ids_are_the_parents(self, decode_matmul):
        """The generated-token cache written a window a row
        (ops/attention.write_block) serves the ids the per-token scatter
        served: 20 free reasoning tokens a row attend to it."""
        eng = make_engine(decode_matmul=decode_matmul)
        eng.set_grammar(build_decision_dfa(
            TOK, ["node-0", "node-1", "node-2"], max_reason_tokens=20))
        prompts = [
            TOK.chat_prompt("pick a node", f"pod-{i} wants scheduling")
            for i in range(3)
        ]
        fins = eng.decide_wave(prompts, max_new_tokens=150)
        assert [f.token_ids for f in fins] == [
            TOK.encode(text) + [TOK.eos_id] for text in self.PARENT_WAVE_TEXTS
        ]

    def test_wave_single_prompt(self, engine):
        prompt = TOK.chat_prompt("sys", "solo")
        fins = engine.decide_wave([prompt], max_new_tokens=10)
        assert len(fins) == 1
        assert 1 <= len(fins[0].token_ids) <= 10

    def test_wave_respects_budget_unconstrained(self, engine):
        prompt = TOK.chat_prompt("sys", "budget check")
        fins = engine.decide_wave([prompt] * 2, max_new_tokens=7)
        for fin in fins:
            assert 1 <= len(fin.token_ids) <= 7

    def test_wave_leaves_slots_untouched(self, engine):
        before = engine.free_slots
        engine.decide_wave([TOK.chat_prompt("s", "u")], max_new_tokens=5)
        assert engine.free_slots == before
        assert engine.kv.pages_free == engine.kv.num_pages - 1  # scratch only

    def test_wave_overflow_rejected(self, engine):
        prompt = TOK.chat_prompt("s", "u")
        with pytest.raises(RuntimeError, match="exceeds max_slots"):
            engine.decide_wave([prompt] * (engine.max_slots + 1), 5)

    def test_wave_runs_alongside_inflight_chunked(self, engine):
        """The wave shares nothing with slot state — it may fire while a
        chunked request is mid-decode, without corrupting it."""
        names = ["node-0", "node-1"]
        engine.set_grammar(build_decision_dfa(TOK, names, max_reason_tokens=10))
        try:
            req = engine.add_request(TOK.chat_prompt("s", "chunked pod"), 150)
            fins = engine.decide_wave([TOK.chat_prompt("s", "wave pod")], 150)
            assert json.loads(fins[0].text)["selected_node"] in names
            done = {}
            for _ in range(80):
                for fin in engine.step():
                    done[fin.req_id] = fin
                if req in done:
                    break
            assert json.loads(done[req].text)["selected_node"] in names
        finally:
            engine.set_grammar(None)


def _family_engine(family_name, buckets):
    """A toy engine of either model family whose ladder is `buckets`."""
    from k8s_llm_scheduler_tpu.models import family
    from k8s_llm_scheduler_tpu.models.configs import get_config

    cfg = ENGINE_CFG if family_name == "dense_gqa" else get_config(f"tiny-{family_name.replace('_', '-')}")
    params = family(cfg).init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(
        params, cfg, TOK, num_pages=8, page_size=64, max_slots=4,
        max_pages_per_seq=8, prefill_buckets=buckets, chunk_steps=4,
        temperature=0.0,
    )


class TestWaveWidth:
    """A wave's suffix prefill is compiled at the smallest entry of the
    ladder that holds its longest suffix: the width changes the padding,
    never the tokens served."""

    NAMES = ["node-0", "node-1", "node-2"]

    def _serve(self, eng, prompts):
        eng.set_grammar(build_decision_dfa(TOK, self.NAMES, max_reason_tokens=12))
        return eng.harvest_wave(eng.submit_wave(prompts, max_new_tokens=120))

    @pytest.mark.parametrize("family_name", ["dense_gqa", "mla_moe", "mla_scmoe"])
    def test_a_ladder_from_128_serves_what_one_from_256_serves(self, family_name):
        prompts = [
            TOK.chat_prompt("pick a node", f"pod-{i} wants " + "cpu " * (3 + 4 * i))
            for i in range(3)
        ]
        assert 64 <= max(map(len, prompts)) <= 128
        served = {}
        for first in (128, 256):
            eng = _family_engine(family_name, (first, 512))
            fins = self._serve(eng, prompts)
            assert all(json.loads(f.text)["selected_node"] in self.NAMES for f in fins)
            # 3 prompts ride the 4-row program: R x the width, padding included
            assert eng.stats["suffix_tokens_computed"] == 4 * first
            assert eng.stats["prefill_tokens"] == sum(map(len, prompts))
            served[first] = [f.token_ids for f in fins]
        assert served[128] == served[256]

    def test_one_suffix_of_129_tokens_takes_the_whole_wave_to_256(self):
        eng = _family_engine("dense_gqa", (128, 256, 512))
        short = TOK.chat_prompt("s", "u")
        long = (short + TOK.encode("x" * 200))[:129]
        handle = eng.submit_wave([short, long], max_new_tokens=8)
        assert handle.bucket == 256
        assert eng.stats["suffix_tokens_computed"] == 2 * 256
        eng.harvest_wave(handle)
        eng.harvest_wave(eng.submit_wave([short, long[:128]], max_new_tokens=8))
        assert eng.stats["suffix_tokens_computed"] == 2 * 256 + 2 * 128


class TestGrammarBudget:
    def test_zero_reason_tokens_still_valid(self):
        dfa = build_decision_dfa(TOK, ["node-1"], max_reason_tokens=0)
        rng = np.random.default_rng(3)
        state = dfa.start_state
        out = []
        for _ in range(200):
            if state == dfa.done_state:
                break
            opts = dfa.allowed_tokens(state)
            tok = int(rng.choice(opts))
            out.append(tok)
            state = dfa.next(state, tok)
        assert state == dfa.done_state
        obj = json.loads(TOK.decode([t for t in out if t != TOK.EOS]))
        assert obj["reasoning"] == ""

    def test_emission_never_exceeds_budget(self):
        """Worst-case DFA emission fits the 60+name+2 budget formula used by
        LocalLLMBackend (regression: a floor on reasoning length used to
        truncate JSON mid-decision)."""
        names = ["node-with-a-rather-long-name-123"]
        max_new = 100
        longest = max(len(TOK.encode(n)) for n in names)
        budget = max_new - (60 + longest) - 2
        dfa = build_decision_dfa(TOK, names, max_reason_tokens=budget)
        rng = np.random.default_rng(4)
        for _ in range(30):
            state = dfa.start_state
            count = 0
            while state != dfa.done_state and count < max_new + 50:
                opts = dfa.allowed_tokens(state)
                # adversarial: always pick the longest continuation (non-quote)
                tok = int(rng.choice(opts))
                state = dfa.next(state, tok)
                count += 1
            assert state == dfa.done_state
            assert count <= max_new, f"emitted {count} > {max_new}"


class TestWorkerResilience:
    def test_grammar_error_fails_request_not_worker(self):
        """A request whose grammar cannot fit the token budget must get a
        BackendError — and the worker must survive to serve the next request
        (regression: unguarded _admit killed the engine-owner thread)."""
        from k8s_llm_scheduler_tpu.engine.backend import BackendError
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend
        from conftest import make_node, make_pod

        backend = build_local_backend(
            cfg=ENGINE_CFG, max_slots=2, num_pages=64, page_size=64,
            prefill_buckets=(512, 1024), chunk_steps=8,
            temperature=0.0, max_new_tokens=20,  # too small for any decision
        )
        try:
            nodes = [make_node("node-with-a-name")]
            with pytest.raises(BackendError, match="cannot fit"):
                backend.get_scheduling_decision(make_pod(), nodes)
            # Worker survived: an unconstrained-capable config still fails the
            # same way (deterministic), and the thread is alive.
            assert backend._worker.is_alive()
            with pytest.raises(BackendError):
                backend.get_scheduling_decision(make_pod(), nodes)
        finally:
            backend.close()

    def test_close_fails_pending_requests(self):
        from k8s_llm_scheduler_tpu.engine.local import LocalLLMBackend, _WorkItem
        from k8s_llm_scheduler_tpu.engine.backend import BackendError

        params = init_params(jax.random.PRNGKey(0), ENGINE_CFG)
        engine = InferenceEngine(params, ENGINE_CFG, TOK, num_pages=32,
                                 page_size=64, max_slots=2,
                                 prefill_buckets=(128,), chunk_steps=4)
        backend = LocalLLMBackend(engine, TOK, request_timeout_s=5)
        backend.close()
        assert not backend._worker.is_alive()


class TestGrammarAcceleration:
    """forced_token_table + wave_iterations: the block-decode foundations."""

    NAMES = ["node-0", "node-1", "node-2"]

    def test_forced_table_marks_skeleton(self):
        from k8s_llm_scheduler_tpu.engine.constrained import forced_token_table

        dfa = build_decision_dfa(TOK, self.NAMES, max_reason_tokens=10)
        forced = forced_token_table(dfa)
        # start state is forced (only '{' allowed)
        assert forced[dfa.start_state] == TOK.encode("{")[0]
        # done state must never force (its pad self-loop is a sentinel)
        assert forced[dfa.done_state] == -1
        # forced states have exactly one allowed token and it matches
        for s in range(dfa.n_states):
            if s == dfa.done_state:
                continue
            if len(dfa.edges[s]) == 1:
                assert forced[s] == next(iter(dfa.edges[s]))
            else:
                assert forced[s] == -1

    def test_wave_iterations_far_below_token_count(self):
        from k8s_llm_scheduler_tpu.engine.constrained import wave_iterations

        dfa = build_decision_dfa(TOK, self.NAMES, max_reason_tokens=3)
        iters = wave_iterations(dfa, block_size=8)
        # any full decision is ~69 tokens; choice points are the name
        # branches, confidence digits, reasoning tokens and close choices
        assert 4 <= iters <= 30

    def test_wave_iterations_bounds_a_random_walk(self):
        """Simulate block consumption along random DFA walks: the DP bound
        must cover every path."""
        from k8s_llm_scheduler_tpu.engine.constrained import (
            forced_token_table,
            wave_iterations,
        )

        F = 8
        dfa = build_decision_dfa(TOK, self.NAMES, max_reason_tokens=6)
        forced = forced_token_table(dfa)
        bound = wave_iterations(dfa, F)
        rng = np.random.default_rng(0)
        for _ in range(50):
            state, iters = dfa.start_state, 0
            while state != dfa.done_state:
                iters += 1  # one sampled token
                opts = dfa.allowed_tokens(state)
                state = dfa.next(state, int(rng.choice(opts)))
                for _ in range(F - 1):  # forced continuation
                    if state == dfa.done_state or forced[state] < 0:
                        break
                    state = dfa.next(state, int(forced[state]))
                assert iters <= bound, "DP bound violated"

    def test_wave_block_one_equals_unconstrained_tokens(self, engine):
        """F=1 (unconstrained) wave must still respect budget exactly."""
        prompt = TOK.chat_prompt("sys", "block one")
        fins = engine.decide_wave([prompt], max_new_tokens=5)
        assert 1 <= len(fins[0].token_ids) <= 5


class TestChunkedPrefix:
    """Long prefixes prefill blockwise; results must match single-shot."""

    def _engine(self, buckets):
        params = init_params(jax.random.PRNGKey(0), ENGINE_CFG)
        return InferenceEngine(
            params, ENGINE_CFG, TOK,
            num_pages=64, page_size=64, max_slots=2, max_pages_per_seq=16,
            prefill_buckets=buckets, chunk_steps=4, temperature=0.0,
        )

    def test_chunked_matches_single_shot(self):
        import numpy as np

        rng = np.random.default_rng(0)
        prefix = [int(t) for t in rng.integers(1, 256, size=300)]
        # small buckets force the chunked path (largest bucket 128 < 300)
        chunked = self._engine((64, 128))
        single = self._engine((64, 128, 512))
        chunked.set_prefix(prefix)
        single.set_prefix(prefix)
        assert chunked.prefix_len == single.prefix_len == 300
        k_c = np.asarray(chunked._prefix.k[:, :300])
        k_s = np.asarray(single._prefix.k[:, :300])
        np.testing.assert_allclose(k_c, k_s, rtol=1e-5, atol=1e-5)
        # and decoding against either prefix gives identical greedy tokens
        suffix = TOK.chat_prompt("sys", "after the long prefix")
        a = chunked.decide_wave([suffix], max_new_tokens=8)[0]
        b = single.decide_wave([suffix], max_new_tokens=8)[0]
        assert a.token_ids == b.token_ids

    def test_prefix_beyond_max_seq_len_warns_but_works(self, caplog):
        import logging

        eng = self._engine((64, 128, 4096))
        # by the logger's own name: other test modules raise the package
        # logger's level at import, and every xdist worker imports them
        with caplog.at_level(
            logging.WARNING, logger="k8s_llm_scheduler_tpu.engine.engine"
        ):
            eng.set_prefix([1] * (ENGINE_CFG.max_seq_len + 10))
        assert any("max_seq_len" in r.message for r in caplog.records)
        assert eng.prefix_len == ENGINE_CFG.max_seq_len + 10


class TestSparseGrammar:
    """Sparse DFA tables: vocab-independent constrained decoding."""

    NAMES = ["node-a", "node-b", "node-abc"]

    def test_sparse_tables_match_dense(self):
        from k8s_llm_scheduler_tpu.engine.constrained import sparse_tables

        dfa = build_decision_dfa(TOK, self.NAMES, max_reason_tokens=10)
        t = sparse_tables(dfa)
        for s in range(dfa.n_states):
            sp = t.sp_tokens[s]
            sparse_toks = [int(x) for x in sp[sp >= 0]]
            assert sparse_toks == dfa.allowed_tokens(s)
            for k, tok in enumerate(sp):
                if tok >= 0:
                    assert t.sp_next[s, k] == dfa.next(s, int(tok))
        # forced_next consistency
        for s in range(dfa.n_states):
            if t.forced[s] >= 0:
                assert t.forced_next[s] == dfa.next(s, int(t.forced[s]))

    def test_large_vocab_constrained_decision(self):
        """Constrained decoding at a vocab size where dense tables would be
        gigabytes — the real-checkpoint (BPE) regime."""
        big_tok = ByteTokenizer(vocab_size=100_000)
        cfg = LlamaConfig(
            name="bigvocab", vocab_size=100_000, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=1024,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = InferenceEngine(
            params, cfg, big_tok, num_pages=32, page_size=64, max_slots=2,
            max_pages_per_seq=8, prefill_buckets=(128, 256), chunk_steps=4,
            temperature=0.0,
        )
        names = ["node-0", "node-1"]
        eng.set_grammar(build_decision_dfa(big_tok, names, max_reason_tokens=5))
        fins = eng.decide_wave(
            [big_tok.chat_prompt("sys", "pick"), big_tok.chat_prompt("sys", "pick 2")],
            max_new_tokens=120,
        )
        for fin in fins:
            obj = json.loads(fin.text)
            assert obj["selected_node"] in names
            assert 0.0 <= obj["confidence"] <= 1.0

    def test_tokenizer_smaller_than_model_vocab(self):
        """A checkpoint-shaped (padded-vocab) model served with a smaller
        domain tokenizer: the engine must accept it, constrained decoding
        stays valid, and unconstrained sampling must never emit an id past
        the tokenizer's table (bench.py runs the 1B config with the
        committed 1280-token BPE fixture through exactly this path)."""
        small_tok = ByteTokenizer()  # vocab 512
        cfg = LlamaConfig(
            name="padded-vocab", vocab_size=1024, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=1024,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = InferenceEngine(
            params, cfg, small_tok, num_pages=32, page_size=64, max_slots=2,
            max_pages_per_seq=8, prefill_buckets=(128, 256), chunk_steps=4,
            temperature=0.0,
        )
        # unconstrained: every emitted id must be decodable
        fin = eng.generate(small_tok.encode("hello"), max_new_tokens=24)
        assert all(t < small_tok.vocab_size for t in fin.token_ids)
        wave = eng.decide_wave([small_tok.encode("hi")], max_new_tokens=16)
        assert all(t < small_tok.vocab_size for t in wave[0].token_ids)
        # constrained: decision grammar built from the tokenizer still works
        names = ["node-0", "node-1"]
        eng.set_grammar(build_decision_dfa(small_tok, names, max_reason_tokens=5))
        fins = eng.decide_wave(
            [small_tok.chat_prompt("sys", "pick")], max_new_tokens=120
        )
        obj = json.loads(fins[0].text)
        assert obj["selected_node"] in names

    def test_tokenizer_larger_than_model_vocab_rejected(self):
        big_tok = ByteTokenizer(vocab_size=2048)
        cfg = LlamaConfig(
            name="small-model-vocab", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=1024,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="embedding table"):
            InferenceEngine(params, cfg, big_tok, num_pages=8, page_size=64,
                            max_slots=2, max_pages_per_seq=4)

    def test_backend_keeps_constraint_for_large_vocab(self):
        from k8s_llm_scheduler_tpu.engine.local import LocalLLMBackend

        big_tok = ByteTokenizer(vocab_size=100_000)
        cfg = LlamaConfig(
            name="bigvocab2", vocab_size=100_000, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=1024,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng = InferenceEngine(
            params, cfg, big_tok, num_pages=32, page_size=64, max_slots=2,
            max_pages_per_seq=8, prefill_buckets=(512, 1024), chunk_steps=4,
        )
        backend = LocalLLMBackend(eng, big_tok, max_new_tokens=120)
        try:
            assert backend.constrained is True
            from conftest import make_node, make_pod

            nodes = [make_node("node-x"), make_node("node-y")]
            decision = backend.get_scheduling_decision(make_pod(), nodes)
            assert decision.selected_node in ("node-x", "node-y")
        finally:
            backend.close()


class TestWavePrewarm:
    """Sibling wave geometries compile ahead of use, never mid-burst."""

    def _engine(self):
        params = init_params(jax.random.PRNGKey(0), ENGINE_CFG)
        return InferenceEngine(
            params, ENGINE_CFG, TOK,
            num_pages=32, page_size=64, max_slots=4, max_pages_per_seq=8,
            prefill_buckets=(128, 256), chunk_steps=4, temperature=0.0,
        )

    def test_backlog_and_prewarm(self):
        eng = self._engine()
        prompts = [TOK.encode(f"prompt {i}") for i in range(4)]  # full R
        eng.decide_wave(prompts, max_new_tokens=16)
        # the half-R sibling at this (bucket, budget) is not yet compiled
        assert eng.wave_prewarm_backlog() == 1
        assert eng.prewarm_wave_siblings() == 1
        assert eng.wave_prewarm_backlog() == 0
        # a real half-R wave now reuses the prewarmed variant
        before = eng.stats.get("wave_prewarms", 0)
        eng.decide_wave(prompts[:1], max_new_tokens=16)
        assert eng.wave_prewarm_backlog() == 0
        assert eng.stats.get("wave_prewarms", 0) == before

    def test_failed_prewarm_does_not_wedge_backlog(self):
        """A raising prewarm dispatch must drain from the backlog (callers
        poll wave_prewarm_backlog()==0 with a timeout; a wedged entry
        would stall them), while a real wave still works."""
        eng = self._engine()
        prompts = [TOK.encode(f"p{i}") for i in range(4)]
        eng.decide_wave(prompts, max_new_tokens=16)
        assert eng.wave_prewarm_backlog() == 1
        real_wave = eng._wave

        def boom(*a, **k):
            raise RuntimeError("transient compile failure")

        eng._wave = boom
        assert eng.prewarm_wave_siblings() == 0
        assert eng.wave_prewarm_backlog() == 0  # failed, not pending
        assert eng.stats.get("wave_prewarm_failures", 0) == 1
        eng._wave = real_wave
        # the geometry still compiles on demand for a real wave
        fins = eng.decide_wave(prompts[:1], max_new_tokens=16)
        assert fins[0].token_ids

    def test_group_switch_invalidates_keys(self):
        eng = self._engine()
        eng.decide_wave([TOK.encode("a")], max_new_tokens=8)
        eng.prewarm_wave_siblings()
        assert eng.wave_prewarm_backlog() == 0
        # a longer prefix bucket is a different executable set
        eng.set_prefix(TOK.encode("x" * 300))
        assert eng.wave_prewarm_backlog() > 0

    def test_backend_idle_prewarm(self):
        """The worker compiles sibling geometries on its own while idle."""
        import time as _time

        from k8s_llm_scheduler_tpu.engine.local import LocalLLMBackend
        from conftest import make_node, make_pod

        eng = self._engine()
        backend = LocalLLMBackend(eng, TOK, max_new_tokens=90)
        try:
            nodes = [make_node("node-x"), make_node("node-y")]
            backend.get_scheduling_decision(make_pod(), nodes)
            deadline = _time.monotonic() + 60
            while eng.wave_prewarm_backlog() > 0:
                assert _time.monotonic() < deadline, "idle prewarm never ran"
                _time.sleep(0.05)
            assert eng.stats.get("wave_prewarms", 0) >= 1
        finally:
            backend.close()


class TestIncrementalPrefix:
    """LCP-seeded chunked prefill == fresh full prefill, exactly."""

    def _engine(self):
        params = init_params(jax.random.PRNGKey(0), ENGINE_CFG)
        return InferenceEngine(
            params, ENGINE_CFG, TOK,
            num_pages=64, page_size=64, max_slots=2, max_pages_per_seq=16,
            prefill_buckets=(64, 128), chunk_steps=4, temperature=0.0,
            prefix_chunk=64,
        )

    def test_tail_change_reuses_and_matches(self):
        rng = np.random.default_rng(0)
        base = [int(t) for t in rng.integers(1, 256, size=300)]
        drifted = list(base)
        drifted[280] = (drifted[280] % 255) + 1  # change near the tail

        warm = self._engine()
        warm.set_prefix(base)
        warm.set_prefix(drifted)
        assert warm.stats.get("prefix_reused_tokens", 0) >= 280  # exact LCP

        fresh = self._engine()
        fresh.set_prefix(drifted)
        # resume chunks are unaligned vs a fresh prefill, so f32 reduction
        # splits differ — equivalence is to accumulation tolerance
        np.testing.assert_allclose(
            np.asarray(warm._prefix.k[:, :300]),
            np.asarray(fresh._prefix.k[:, :300]),
            rtol=1e-4, atol=1e-4,
        )
        # decisions against the incremental prefix match the fresh one
        suffix = TOK.chat_prompt("sys", "after drift")
        a = warm.decide_wave([suffix], max_new_tokens=8)[0]
        b = fresh.decide_wave([suffix], max_new_tokens=8)[0]
        assert a.token_ids == b.token_ids

    def test_early_change_falls_back_to_full_prefill(self):
        rng = np.random.default_rng(1)
        base = [int(t) for t in rng.integers(1, 256, size=300)]
        drifted = list(base)
        drifted[3] = (drifted[3] % 255) + 1  # change before the first chunk

        warm = self._engine()
        warm.set_prefix(base)
        before = warm.stats.get("prefix_reused_tokens", 0)
        warm.set_prefix(drifted)
        assert warm.stats.get("prefix_reused_tokens", 0) == before

        fresh = self._engine()
        fresh.set_prefix(drifted)
        np.testing.assert_allclose(
            np.asarray(warm._prefix.k[:, :300]),
            np.asarray(fresh._prefix.k[:, :300]),
            rtol=1e-6, atol=1e-6,
        )

    def test_extension_reuses_whole_old_prefix(self):
        rng = np.random.default_rng(2)
        base = [int(t) for t in rng.integers(1, 256, size=192)]  # 3 chunks
        extended = base + [int(t) for t in rng.integers(1, 256, size=100)]

        warm = self._engine()
        warm.set_prefix(base)
        warm.set_prefix(extended)
        assert warm.stats.get("prefix_reused_tokens", 0) >= 192
        fresh = self._engine()
        fresh.set_prefix(extended)
        np.testing.assert_allclose(
            np.asarray(warm._prefix.k[:, :292]),
            np.asarray(fresh._prefix.k[:, :292]),
            rtol=1e-4, atol=1e-4,
        )


class TestGrammarCapacity:
    """VERDICT r1 weak-item: no test pinned the 256-node grammar size, and a
    bigger grammar hard-failed at DFA_STATE_CAPACITY."""

    def test_256_node_grammar_fits_default_capacity(self, engine):
        from k8s_llm_scheduler_tpu.engine.constrained import build_decision_dfa

        names = [f"node-{i:03d}" for i in range(256)]
        dfa = build_decision_dfa(TOK, names, max_reason_tokens=120)
        assert dfa.n_states <= engine.DFA_STATE_CAPACITY, dfa.n_states
        engine.set_grammar(dfa)
        assert engine._sp_tokens.shape[0] == engine.DFA_STATE_CAPACITY
        engine.set_grammar(None)

    def test_oversized_grammar_buckets_up_and_decodes(self, engine):
        """600 long node names (~2x the floor in states): capacity doubles
        instead of raising, and a constrained wave still decides a live
        name."""
        from k8s_llm_scheduler_tpu.engine.constrained import build_decision_dfa
        from k8s_llm_scheduler_tpu.utils.json_extract import parse_decision_json

        # hashed tails defeat trie prefix-sharing, like real cloud node names
        names = [
            f"node-{i:03d}-{(i * 2654435761) % 16**8:08x}" for i in range(600)
        ]
        dfa = build_decision_dfa(TOK, names, max_reason_tokens=40)
        assert dfa.n_states > engine.DFA_STATE_CAPACITY
        engine.set_grammar(dfa)
        cap = engine._sp_tokens.shape[0]
        assert cap >= dfa.n_states and cap % engine.DFA_STATE_CAPACITY == 0
        try:
            engine.set_prefix(TOK.encode("cluster state: 600 nodes"))
            fin = engine.decide_wave(
                [TOK.encode("pod: tiny")], max_new_tokens=160
            )[0]
            parsed = parse_decision_json(fin.text)
            assert parsed is not None, fin.text
            assert parsed["selected_node"] in set(names)
        finally:
            engine.set_grammar(None)
            engine.set_prefix(None)


class TestGrammarNameSafety:
    def test_json_breaking_names_rejected(self):
        """Names embed raw in the forced JSON string: quotes/backslashes/
        control chars would make every decision unparseable, and none of
        them can appear in a legal DNS-1123 node name."""
        from k8s_llm_scheduler_tpu.engine.constrained import build_decision_dfa

        for bad in ('no"de', "back\\slash", "ctrl\x01char", "new\nline"):
            with pytest.raises(ValueError, match="JSON-breaking"):
                build_decision_dfa(TOK, ["node-ok", bad], max_reason_tokens=10)
        # legal DNS-1123-ish names still fine
        dfa = build_decision_dfa(TOK, ["node-ok", "a.b-c"], max_reason_tokens=10)
        assert dfa.n_states > 0
