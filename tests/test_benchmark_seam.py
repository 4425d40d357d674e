"""Tier 1's reach into benchmark/: the fast cases of
benchmark/tests/test_arch_seam.py and all of benchmark/tests/test_scope_trace.py
and test_counter_readers.py run here as they stand (loaded from their files, the way
tests/test_tracing_scopes.py reaches benchmark/), so that a PR which breaks
the architecture seam or the scope reduction fails the suite the driver
runs and not only `pytest benchmark/tests`. Beside them: the FLOP counts of
arch/mla_moe.py pinned by hand, and a toy-size run of reference/mla_moe.py
in both of its modes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# The whole run of a twin architecture through run_cell (a minute and more
# on the CPU) stays with `pytest benchmark/tests`.
SLOW = {"test_a_twin_architecture_runs_through_the_seam_by_files_alone"}

for _file in ("test_scope_trace.py", "test_arch_seam.py", "test_counter_readers.py"):
    _mod = _load(BENCH / "tests" / _file, f"bench_tests_{_file[:-3]}")
    # tests and the fixtures they ask for, under their own names
    globals().update({k: v for k, v in vars(_mod).items()
                      if not k.startswith("__") and k not in SLOW and k != "BENCH"})

ARCH = _load(BENCH / "arch" / "mla_moe.py", "bench_arch_mla_moe_pins")
REF = _load(BENCH / "reference" / "mla_moe.py", "bench_reference_mla_moe_pins")


@pytest.fixture(scope="module")
def glm():
    return json.loads((BENCH / "configs" / "glm-4_7-flash.json").read_text())


class TestMlaMoeFlopPins:
    """One token through the cut model, by hand, at the published widths."""

    ATTN = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 + 20 * 256 * 2048  # 21.76 M
    DENSE_FFN = 3 * 2048 * 10240                                                       # 62.9 M
    ROUTER, EXPERT = 2048 * 64, 3 * 2048 * 1536                                        # 0.13 M, 9.44 M

    def test_a_token_without_the_head(self, glm):
        by_hand = 2 * (7 * self.ATTN + self.DENSE_FFN + 6 * (self.ROUTER + 5 * self.EXPERT))
        assert by_hand == 998_244_352  # 998 MFLOP: 4 routed + 1 shared expert, never 64
        assert ARCH.flops_per_token(glm, with_head=False) == by_hand

    def test_the_head(self, glm):
        assert (ARCH.flops_per_token(glm, with_head=True)
                - ARCH.flops_per_token(glm, with_head=False)) == 2 * 2048 * 154_880

    def test_attention_is_counted_as_the_program_runs_it(self, glm):
        # absorbed, on every segment: 7 layers x 20 heads x 2 x (512 + 64 for
        # the score against the latent + 512 for the latent summed) a key
        assert ARCH.attention_flops(glm, 1, 1) == 7 * 20 * 2 * (576 + 512)
        assert ARCH.attention_flops(glm, 8, 1500.0) == 8 * 1500 * 304_640

    def test_the_programs_own_books_count_the_same_token(self, glm):
        """observability/profiler.py asks the config; the config and the
        benchmark's arch file agree on what a token needs."""
        from k8s_llm_scheduler_tpu.models.configs import MlaMoeConfig
        from k8s_llm_scheduler_tpu.observability.profiler import matmul_flops_per_token

        cfg = MlaMoeConfig.from_hf(glm["name"], glm)
        assert matmul_flops_per_token(cfg) == ARCH.flops_per_token(glm, with_head=True)
        assert cfg.attn_flops_per_key() == ARCH.attention_flops(glm, 1, 1)
        assert cfg.n_dense_layers == 1 and cfg.n_moe_layers == 6 and cfg.experts_held == 64

    def test_a_grouped_kernel_call_is_bound_by_the_touched_experts_bytes(self, glm):
        # 72 rows that touch 40 experts: gate and up of 40 experts, read once
        flops, moved = ARCH.grouped_kernel_cost(72, 40, 2048, 1536, 2, 2)
        assert flops == 2.0 * 72 * 2048 * 1536 * 2
        assert moved == 40 * 2048 * 1536 * 2 * 2 + 72 * (2048 * 2 + 1536 * 2)
        assert moved / 819e9 > 50 * flops / 197e12

    def test_the_configuration_file_holds_the_published_row(self, glm):
        """Every number of the catalog row under its own key; the depth and
        the MTP module the only cuts; no width touched."""
        published = {
            "hidden_size": 2048, "intermediate_size": 10240, "moe_intermediate_size": 1536,
            "num_attention_heads": 20, "num_key_value_heads": 20, "n_routed_experts": 64,
            "n_shared_experts": 1, "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
            "first_k_dense_replace": 1, "q_lora_rank": 768, "kv_lora_rank": 512,
            "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
            "vocab_size": 154880, "rope_theta": 1000000, "rms_norm_eps": 1e-05,
            "max_position_embeddings": 202752, "n_group": 1, "topk_group": 1,
        }
        assert {k: glm[k] for k in published} == published
        assert glm["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
        assert (glm["num_hidden_layers"], glm["num_nextn_predict_layers"]) == (7, 0)
        assert glm["published"]["num_hidden_layers"] == 47


def test_reference_runs_in_both_modes_and_int8_differs():
    """reference/mla_moe.py at a toy size: `f32` and the `int8` control see
    the same wave and give different logits (a control that equalled the
    reference would prove nothing), both finite, one row a predicted token."""
    toy = {
        "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
        "routed_scaling_factor": 1.8, "norm_topk_prob": True, "vocab_size": 512,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    }
    weights = REF.init_weights(toy, 3)
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, 500, 40).tolist()
    tails = [rng.integers(1, 500, n).tolist() for n in (12, 9)]
    spans = [(7, 5), (5, 4)]
    f32 = REF.wave_logits(toy, weights, prefix, tails, spans, "f32", 300)
    low = REF.wave_logits(toy, weights, prefix, tails, spans, "int8", 300)
    assert f32.shape == low.shape == (9, 300)
    assert np.isfinite(f32).all() and np.isfinite(low).all()
    # the same forward less precisely: it moves, and stays the same forward
    assert float(np.max(np.abs(f32 - low))) > 1e-3
    assert float(np.mean(np.abs(f32 - low))) < 0.25 * float(np.std(f32))
    # a tail sees the prefix and itself alone: the other row's tokens do not matter
    alone = REF.wave_logits(toy, weights, prefix, tails[:1], spans[:1], "f32", 300)
    np.testing.assert_allclose(alone, f32[:5], rtol=1e-4, atol=1e-5)
