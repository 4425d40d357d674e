"""Real-tokenizer (BPE) path: HFTokenizerAdapter + grammar + end-to-end.

VERDICT round 1 item 5: the claim that constrained decoding "works
unchanged at BPE vocabs" (engine/constrained.py) was untested. These tests
run the committed assets/bpe4k fixture — a genuine HuggingFace fast
tokenizer (byte-level BPE, Llama-3-style chat template, built by
tools/build_bpe_fixture.py) — through the adapter, the decision DFA over
multi-token node names, and a full LocalLLMBackend decision.
"""

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

# Everything here jit-compiles models/kernels (seconds per test):
# full-suite only, excluded from the fast tier (TESTING.md).
pytestmark = pytest.mark.slow

FIXTURE = str(
    Path(__file__).resolve().parent.parent
    / "k8s_llm_scheduler_tpu" / "assets" / "bpe4k"
)


@pytest.fixture(scope="module")
def adapter():
    from k8s_llm_scheduler_tpu.engine.tokenizer import HFTokenizerAdapter

    return HFTokenizerAdapter(FIXTURE)


class TestHFTokenizerAdapter:
    def test_pad_and_eos_sentinels(self, adapter):
        # <|pad|> is id 0 in the fixture; eos is <|eot_id|>
        assert adapter.pad_id == 0
        assert adapter.eos_id == adapter._tok.token_to_id("<|eot_id|>")
        assert adapter.pad_id != adapter.eos_id
        assert adapter.vocab_size % 128 == 0  # MXU-friendly embedding rows

    def test_encode_decode_roundtrip(self, adapter):
        sample = "Node: node-17\n  CPU: 37.0% used, 16.00 cores allocatable\n"
        ids = adapter.encode(sample)
        # real BPE: multi-char tokens, meaningful compression
        assert len(ids) < len(sample) / 2
        assert adapter.decode(ids) == sample

    def test_chat_prompt_parts_concatenation(self, adapter):
        """prefix + suffix must RENDER to the same string as the unsplit
        prompt (the token-boundary caveat allows the token lists to differ,
        never the text the model conditions on)."""
        system = "You are a Kubernetes scheduler."
        cluster = "CLUSTER STATE:\n\nNode: node-1\n  CPU: 10.0% used\n"
        pod = "POD TO SCHEDULE:\n  Name: default/x\n"
        pfx, sfx = adapter.chat_prompt_parts(system, cluster, pod)
        assert pfx and sfx
        joint = adapter._tok.decode(
            adapter.chat_prompt(system, cluster + pod), skip_special_tokens=False
        )
        split = adapter._tok.decode(pfx + sfx, skip_special_tokens=False)
        assert split == joint
        # the prefix must end before the pod text so a burst shares it
        assert "POD TO SCHEDULE" not in adapter._tok.decode(
            pfx, skip_special_tokens=False
        )

    def test_chat_prompt_parts_memo_hit_is_identical(self, adapter):
        """The burst's 2nd..Nth pods hit the prefix-encode memo; the
        memoized path must produce exactly the cold path's tokens."""
        system = "sys prompt"
        cluster = "CLUSTER STATE:\n" + "Node: node-7\n" * 40
        adapter._prefix_encode_memo.clear()
        cold = [
            adapter.chat_prompt_parts(system, cluster, f"POD {i}: spec\n")
            for i in range(3)
        ]
        adapter._prefix_encode_memo.clear()
        # re-run in reverse so each call that WAS a memo hit is now cold
        warm = [
            adapter.chat_prompt_parts(system, cluster, f"POD {i}: spec\n")
            for i in reversed(range(3))
        ]
        assert cold == list(reversed(warm))

    def test_split_rejects_suffix_text_recurring_in_tail(self, adapter):
        """A suffix whose text also appears later in the render (e.g. it
        ends with the template's own tail text) must not be mis-split —
        the split validates user_suffix follows user_prefix verbatim."""
        # suffix deliberately equal to a string that also appears in the
        # template tail region
        pfx, sfx = adapter.chat_prompt_parts(
            "sys", "CLUSTER:\nNode: n1\n", "POD: x<|eot_id|>"
        )
        joint = adapter._tok.decode(
            adapter.chat_prompt("sys", "CLUSTER:\nNode: n1\nPOD: x<|eot_id|>"),
            skip_special_tokens=False,
        )
        split = adapter._tok.decode(pfx + sfx, skip_special_tokens=False)
        assert split == joint

    def test_chat_prompt_parts_degrades_without_suffix(self, adapter):
        pfx, sfx = adapter.chat_prompt_parts("sys", "cluster", "")
        assert pfx == []
        assert sfx == adapter.chat_prompt("sys", "cluster")

    def test_pad_sentinel_reserved_fallback(self, tmp_path):
        """A tokenizer dir WITHOUT a pad token falls back to a reserved
        special token (never to id 0, which is real text in Llama vocabs)."""
        from k8s_llm_scheduler_tpu.engine.tokenizer import HFTokenizerAdapter

        shutil.copy(Path(FIXTURE) / "tokenizer.json", tmp_path / "tokenizer.json")
        config = json.loads((Path(FIXTURE) / "tokenizer_config.json").read_text())
        del config["pad_token"]
        (tmp_path / "tokenizer_config.json").write_text(json.dumps(config))
        adapter = HFTokenizerAdapter(str(tmp_path))
        name = adapter._tok.id_to_token(adapter.pad_id)
        assert "reserved" in name or "pad" in name
        assert adapter.pad_id != adapter.eos_id


class TestDecisionDFAOverBPE:
    def test_multi_token_names_reachable(self, adapter):
        """Every node name — each several BPE tokens — has a complete path
        through the DFA, and the forced-run tables keep the JSON skeleton
        single-choice."""
        from k8s_llm_scheduler_tpu.engine.constrained import (
            build_decision_dfa,
            forced_token_table,
            wave_iterations,
        )

        names = [f"node-{i}" for i in range(24)] + ["gpu-pool-a100-7"]
        assert all(len(adapter.encode(n)) >= 2 for n in names[:5])
        dfa = build_decision_dfa(adapter, names, max_reason_tokens=40)
        forced = forced_token_table(dfa)
        assert len(forced) == dfa.n_states
        iters = wave_iterations(dfa, 24)
        # completion must be bounded and far below per-token decoding
        assert 0 < iters < 60

    def test_backend_decision_end_to_end(self):
        """Full decision through LocalLLMBackend with the BPE tokenizer and
        a random-init model: grammar guarantees a live node name."""
        from conftest import make_node, make_pod
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend
        from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
        from k8s_llm_scheduler_tpu.types import DecisionSource

        cfg = LlamaConfig(
            name="bpe-e2e", vocab_size=1280, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=8192,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        backend = build_local_backend(
            cfg=cfg, tokenizer_path=FIXTURE,
            max_slots=2, num_pages=64, page_size=64,
            prefill_buckets=(128, 256, 512, 1024, 2048, 4096),
            chunk_steps=8, temperature=0.0, max_new_tokens=120,
        )
        try:
            assert backend.tokenizer.vocab_size == cfg.vocab_size
            nodes = [make_node(f"node-{i}", cpu_pct=20.0 + i * 30) for i in range(3)]
            decision = backend.get_scheduling_decision(make_pod(), nodes)
            assert decision.source is DecisionSource.LLM
            assert decision.selected_node in {n.name for n in nodes}
            assert 0.0 <= decision.confidence <= 1.0
        finally:
            backend.close()
