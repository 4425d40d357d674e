"""Tier 1's reach into benchmark/: the fast cases of
benchmark/tests/test_arch_seam.py and all of benchmark/tests/test_scope_trace.py,
test_counter_readers.py, test_moe_bounded_share.py (less the one case PR 37's
appended entries outdate, below) and test_state_readers.py run here as they stand
(loaded from their files, the way
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
# benchmark/tests/test_moe_bounded_share.py holds that `moe_bounded_share.tput`
# is the LAST per-layer entry and lists the third cell alone. ISSUE 37 appends
# the fourth cell to that list and five entries behind it, and a PR may edit
# no file the benchmark has: the case stays in its file for a benchmark PR to
# bring up to date, and what still holds of it is held below.
OUTDATED = {"test_the_entry_is_the_third_cells_alone"}
# benchmark/tests/test_state_readers.py holds that PR 37's five entries close
# the per-layer list; ISSUE 39 appends the five set-up entries behind them.
# What still holds of it is held below (`test_the_state_entries_...`).
OUTDATED |= {"test_the_five_entries_close_the_list_and_name_the_cell_alone"}

for _file in ("test_scope_trace.py", "test_arch_seam.py", "test_counter_readers.py",
              "test_moe_bounded_share.py", "test_state_readers.py"):
    _mod = _load(BENCH / "tests" / _file, f"bench_tests_{_file[:-3]}")
    # tests and the fixtures they ask for, under their own names
    globals().update({k: v for k, v in vars(_mod).items()
                      if not k.startswith("__") and k not in SLOW | OUTDATED and k != "BENCH"})


def test_the_bounded_share_entry_keeps_its_keys_and_its_first_cell():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == "moe_bounded_share.tput")
    assert entry == {"name": "moe_bounded_share.tput", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "model", "moves": "binds_per_s",
                     "workloads": ["longcat_flash-backlog20", "qwen3_next-backlog20", COHERE_CELL]}

ARCH = _load(BENCH / "arch" / "mla_moe.py", "bench_arch_mla_moe_pins")
REF = _load(BENCH / "reference" / "mla_moe.py", "bench_reference_mla_moe_pins")
SC_REF = _load(BENCH / "reference" / "mla_scmoe.py", "bench_reference_mla_scmoe_pins")
GDN_REF = _load(BENCH / "reference" / "gdn_moe.py", "bench_reference_gdn_moe_pins")


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


class TestLongcatThroughTheSeam:
    """benchmark/configs/longcat-flash-chat.json loaded the way run.py loads
    it: `"architecture": "mla_scmoe"` selects arch/ and reference/, `register`
    hands the program a config of its own type, and the arch file's count of
    what a token needs is the config type's books."""

    ATTN = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 64 * 128 * 6144  # 90.57 M
    DENSE_FFN, ROUTER, EXPERT = 3 * 6144 * 12288, 6144 * 768, 3 * 6144 * 2048          # 226.5 M, 4.72 M, 37.75 M

    @pytest.fixture(scope="class")
    def loaded(self):
        from harness import seam

        conf = seam.load_config(BENCH / "configs" / "longcat-flash-chat.json")
        return conf, seam.program(conf), seam.reference(conf)

    def test_the_file_selects_its_architecture_and_registers_its_own_config_type(self, loaded):
        from k8s_llm_scheduler_tpu.models import family, mla_scmoe
        from k8s_llm_scheduler_tpu.models.configs import MlaScmoeConfig, get_config

        conf, arch, ref = loaded
        assert arch.__file__.endswith("arch/mla_scmoe.py") and ref.__file__.endswith("reference/mla_scmoe.py")
        cfg = get_config(arch.register(conf))
        assert isinstance(cfg, MlaScmoeConfig) and family(cfg) is mla_scmoe
        assert (cfg.n_layers, mla_scmoe.cache_layers(cfg), cfg.experts_held, cfg.expert_first) == (4, 8, 16, 0)
        assert (cfg.n_routed_experts, cfg.n_zero_experts, cfg.n_experts_per_tok) == (512, 256, 12)
        assert (cfg.q_lora_scale, round(cfg.kv_lora_scale**2), cfg.routed_scaling_factor) == (2.0, 12, 6.0)
        assert not cfg.norm_topk_prob and cfg.router_score == "softmax"

    def test_a_token_by_hand_is_the_arch_files_count_and_the_config_types_books(self, loaded):
        from k8s_llm_scheduler_tpu.models.configs import get_config
        from k8s_llm_scheduler_tpu.observability.profiler import matmul_flops_per_token

        conf, arch, _ = loaded
        # 12 picks x 16 held / 768 outputs = a quarter of an expert a layer a
        # token: identity experts multiply nothing, 496 experts are elsewhere
        assert arch.held_picks_per_token(conf) == 0.25
        by_hand = 2 * 4 * (2 * self.ATTN + 2 * self.DENSE_FFN + self.ROUTER + self.EXPERT // 4)
        assert by_hand == 5_186_256_896  # 5.19 GFLOP a token through 4 double layers
        assert arch.flops_per_token(conf, with_head=False) == by_hand
        head = arch.flops_per_token(conf, with_head=True) - by_hand
        assert head == 2 * 6144 * 16_384
        cfg = get_config(arch.register(conf))
        assert matmul_flops_per_token(cfg) == arch.flops_per_token(conf, with_head=True)
        assert cfg.attn_params() == self.ATTN
        # absorbed, 8 attention sublayers x 64 heads x 2 x (576 + 512) a key
        assert arch.attention_flops(conf, 1, 1) == cfg.attn_flops_per_key() == 8 * 64 * 2 * (576 + 512)

    def test_a_grouped_kernel_call_is_bound_by_the_touched_experts_bytes(self, loaded):
        _, arch, _ = loaded
        flops, moved = arch.grouped_kernel_cost(5, 4, 6144, 2048, 2, 2)
        assert flops == 2.0 * 5 * 6144 * 2048 * 2
        assert moved == 4 * 6144 * 2048 * 2 * 2 + 5 * (6144 * 2 + 2048 * 2)
        assert moved / 819e9 > 50 * flops / 197e12

    def test_the_configuration_file_holds_the_published_row(self, loaded):
        """Every number of the catalog row under its own key; depth, experts
        held and vocabulary the only cuts; no width touched."""
        conf, _, _ = loaded
        published = {
            "hidden_size": 6144, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
            "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
            "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
            "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
            "n_routed_experts": 512, "zero_expert_num": 256, "zero_expert_type": "identity",
            "moe_topk": 12, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
            "rope_theta": 10000000, "attention_method": "MLA", "attention_bias": False,
        }
        assert {k: conf[k] for k in published} == published
        assert conf["reduced"] == ["num_layers", "experts_held", "vocab_size"]
        assert (conf["num_layers"], conf["experts_held"], conf["vocab_size"]) == (4, 16, 16384)
        assert conf["published"] == {**conf["published"], "num_layers": 28, "experts_held": 512,
                                     "vocab_size": 131072}
        entry = next(c for c in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["configs"]
                     if c["name"] == conf["name"])
        assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
        by_hand = 4 * (2 * self.ATTN + 2 * self.DENSE_FFN + self.ROUTER + 16 * self.EXPERT) + 2 * 16384 * 6144
        assert conf["parameters"] == by_hand  # 5.17 B matrix parameters, 10.35 GB bf16

    def test_the_reference_imports_nothing_of_the_program_or_the_harness(self):
        text = (BENCH / "reference" / "mla_scmoe.py").read_text()
        imports = [ln for ln in text.splitlines() if ln.startswith(("import ", "from "))]
        assert imports == ["from __future__ import annotations", "import functools", "import jax",
                           "import jax.numpy as jnp", "import numpy as np"]

    def test_the_cell_is_listed_where_its_readers_find_something(self):
        bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
        cell = "longcat_flash-backlog20"
        assert [w for w in bench["workloads"] if w["name"] == cell] == [
            {**next(w for w in bench["workloads"] if w["name"] == cell),
             "config": "longcat-flash-chat", "traffic": "backlog20_pool80", "chips": 1}]
        listed = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
        new = {"dense_ffn_device_ms_per_bind.tput", "moe_zero_device_ms_per_bind.tput",
               "zero_expert_share.tput", "experts_here_share.tput",
               "scmoe_grouped_swiglu_roofline.tput", "scmoe_grouped_matmul_roofline.tput"}
        assert new <= listed
        # readers that assume the second family's keys or a whole expert set
        assert not listed & {"moe_shared_device_ms_per_bind.tput", "expert_load_max_over_mean.tput",
                             "moe_grouped_swiglu_roofline.tput", "moe_grouped_matmul_roofline.tput",
                             "prefix_attn_roofline.tput"}
        for m in bench["per_layer"]:
            if m["name"] in new:
                assert m["workloads"] == [cell] and m["moves"] == "binds_per_s"
        assert cell in next(m for m in bench["end_to_end"] if m["name"] == "binds_per_s")["workloads"]


class TestQwen3NextThroughTheSeam:
    """benchmark/configs/qwen3-next-80b-a3b.json loaded the way run.py loads
    it: `"architecture": "gdn_moe"` selects arch/ and reference/, `register`
    hands the program a config of its own type, and the arch file's count of
    what a token needs is the config type's books."""

    GDN = 2048 * 12288 + 2048 * 64 + 4096 * 2048                      # 33.69 M: W_qkvz, W_ba, W_o
    ATTN = 2048 * 16 * 512 + 2 * 2048 * 2 * 256 + 16 * 256 * 2048     # 27.26 M: W_q with its gate, W_k, W_v, W_o
    ROUTER, EXPERT, SHARED = 2048 * 512, 3 * 2048 * 512, 3 * 2048 * 512 + 2048
    STATE = 3 * 2 * 32 * 128 * 128                                    # S^T k, k delta^T, S^T q a value head

    @pytest.fixture(scope="class")
    def loaded(self):
        from harness import seam

        conf = seam.load_config(BENCH / "configs" / "qwen3-next-80b-a3b.json")
        return conf, seam.program(conf), seam.reference(conf)

    def test_the_file_selects_its_architecture_and_registers_its_own_config_type(self, loaded):
        from k8s_llm_scheduler_tpu.models import family, gdn_moe
        from k8s_llm_scheduler_tpu.models.configs import GdnMoeConfig, get_config

        conf, arch, ref = loaded
        assert arch.__file__.endswith("arch/gdn_moe.py") and ref.__file__.endswith("reference/gdn_moe.py")
        cfg = get_config(arch.register(conf))
        assert isinstance(cfg, GdnMoeConfig) and family(cfg) is gdn_moe
        assert (cfg.n_layers, cfg.n_periods, gdn_moe.cache_layers(cfg), gdn_moe.state_layers(cfg)) == (12, 3, 3, 3)
        assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_first, cfg.n_experts_per_tok) == (512, 128, 0, 10)
        assert cfg.norm_topk_prob and cfg.router_score == "softmax" and cfg.rotary_dim == 64
        members = [m[0] for m in gdn_moe.state_shapes(cfg)]  # a member a delta-rule position of the period
        assert members == [(32, 128, 128)] * 3 + [(3, 8192)] * 3  # 2.1 MB and 98 KB a sequence a layer
        from k8s_llm_scheduler_tpu.models.mla_moe import held_bound
        assert [held_bound(t * 10, 128, 512) for t in (8 * 24, 8 * 128, 2048)] == [1024, 5120, 10240]  # half the rows

    def test_a_token_by_hand_is_the_arch_files_count_and_the_config_types_books(self, loaded):
        from k8s_llm_scheduler_tpu.models.configs import get_config
        from k8s_llm_scheduler_tpu.observability.profiler import matmul_flops_per_token

        conf, arch, _ = loaded
        assert arch.held_picks_per_token(conf) == 2.5  # 10 picks x 128 held / 512 outputs
        moe = self.ROUTER + 2.5 * self.EXPERT + self.SHARED
        by_hand = 2 * (9 * self.GDN + 3 * self.ATTN + 12 * moe) + 9 * self.STATE
        assert by_hand == 1_087_684_608  # 1.09 GFLOP a token through 12 layers, 28 MFLOP of it state products
        assert arch.flops_per_token(conf, with_head=False) == by_hand
        assert arch.flops_per_token(conf, with_head=True) - by_hand == 2 * 2048 * 37_984
        cfg = get_config(arch.register(conf))
        assert matmul_flops_per_token(cfg) == arch.flops_per_token(conf, with_head=True)
        assert (cfg.gdn_params(), cfg.attn_params()) == (self.GDN, self.ATTN)
        assert cfg.gdn_state_flops_per_token() == arch.gdn_state_flops_per_token(conf) == self.STATE
        # the three layers that attend alone: 16 heads x 2 x 2 x 256 a key
        assert arch.attention_flops(conf, 1, 1) == cfg.attn_flops_per_key() == 3 * 16 * 4 * 256

    def test_a_grouped_kernel_call_is_bound_by_the_touched_experts_bytes(self, loaded):
        _, arch, _ = loaded
        flops, moved = arch.grouped_kernel_cost(45, 14, 2048, 512, 2, 2)
        assert flops == 2.0 * 45 * 2048 * 512 * 2
        assert moved == 14 * 2048 * 512 * 2 * 2 + 45 * (2048 * 2 + 512 * 2)
        assert moved / 819e9 > 50 * flops / 197e12

    def test_the_configuration_file_holds_the_published_row(self, loaded):
        """Every number of the catalog row under its own key; depth, experts
        held and vocabulary the only cuts; no width touched."""
        conf, _, _ = loaded
        published = {
            "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
            "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
            "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
            "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
            "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
            "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
            "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
            "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
            "tie_word_embeddings": False, "use_sliding_window": False,
        }
        assert {k: conf[k] for k in published} == published
        assert conf["reduced"] == ["num_hidden_layers", "experts_held", "vocab_size"]
        assert (conf["num_hidden_layers"], conf["experts_held"], conf["vocab_size"]) == (12, 128, 37984)
        assert conf["published"] == {**conf["published"], "num_hidden_layers": 48, "experts_held": 512,
                                     "vocab_size": 151936}
        assert conf["vocab_size"] * 4 == 151936 and "16 chips" in conf["deployment"]
        entry = next(c for c in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["configs"]
                     if c["name"] == conf["name"])
        assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
        by_hand = (9 * (self.GDN + 4 * 8192 + 2 * 32 + 128) + 3 * (self.ATTN + 2 * 256)
                   + 12 * (2 * 2048 + self.ROUTER + 128 * self.EXPERT + self.SHARED)
                   + 2 * 37984 * 2048 + 2048)
        assert conf["parameters"] == by_hand  # 5.42 B parameters, 10.85 GB bf16

    def test_the_reference_imports_nothing_of_the_program_or_the_harness(self):
        text = (BENCH / "reference" / "gdn_moe.py").read_text()
        imports = [ln for ln in text.splitlines() if ln.startswith(("import ", "from "))]
        assert imports == ["from __future__ import annotations", "import functools", "import jax",
                           "import jax.numpy as jnp", "import numpy as np"]
        assert "lax.scan(step, s0" in text and "solve_triangular" not in text  # the recurrence, not the chunked form

    def test_the_cell_is_listed_where_its_readers_find_something(self):
        bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
        cell = "qwen3_next-backlog20"
        entry = next(w for w in bench["workloads"] if w["name"] == cell)
        assert entry == {**entry, "config": "qwen3-next-80b-a3b", "traffic": "backlog20_pool80", "chips": 1}
        listed = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
        new = {"gdn_device_ms_per_bind.tput", "gdn_scan_device_ms_per_bind.tput",
               "full_attn_device_ms_per_bind.tput", "state_carry_device_ms_per_bind.tput",
               "state_valid_share.tput"}
        shared = {"moe_experts_device_ms_per_bind.tput", "moe_router_device_ms_per_bind.tput",
                  "moe_shared_device_ms_per_bind.tput", "experts_hit_per_layer_call.tput",
                  "moe_bounded_share.tput", "moe_grouped_swiglu_roofline.tput",
                  "moe_grouped_matmul_roofline.tput"}
        assert new | shared <= listed
        # readers of another family's scopes, keys or whole expert set
        assert not listed & {"expert_load_max_over_mean.tput", "mla_proj_device_ms_per_bind.tput",
                             "dense_ffn_device_ms_per_bind.tput", "moe_zero_device_ms_per_bind.tput",
                             "zero_expert_share.tput", "experts_here_share.tput",
                             "scmoe_grouped_swiglu_roofline.tput", "scmoe_grouped_matmul_roofline.tput",
                             "prefix_attn_roofline.tput"}
        for m in bench["per_layer"]:
            if cell in m["workloads"]:  # appended, nothing moved: behind it the fifth and sixth cells alone
                assert m["workloads"][m["workloads"].index(cell) + 1:] in (
                    [], [GRANITE_CELL], [COHERE_CELL], [GRANITE_CELL, COHERE_CELL])
        rate = next(m for m in bench["end_to_end"] if m["name"] == "binds_per_s")["workloads"]
        assert rate[-3:] == [cell, GRANITE_CELL, COHERE_CELL]
        assert [w["name"] for w in bench["workloads"]] == CELLS + [GRANITE_CELL, COHERE_CELL]
        assert all(w["chips"] == 1 for w in bench["workloads"])


@pytest.mark.parametrize("name, stats, want", [
    ("zero_expert_share.tput", {"moe_zero_assignments": 400, "moe_ffn_assignments": 800}, 100 / 3),
    ("zero_expert_share.tput", {}, None),  # a parent: no such counters
    ("experts_here_share.tput", {"moe_assignments": 25, "moe_ffn_assignments": 800}, 3.125),
    ("experts_here_share.tput", {"moe_assignments": 25}, None),
])
def test_the_new_counter_readers(name, stats, want):
    import run as bench_run

    got = bench_run.reader_for(name)(window_ctx({}, stats))  # noqa: F821 (from test_counter_readers.py)
    assert got == pytest.approx(want) if want is not None else got is None


def test_scmoe_reference_runs_in_both_modes_and_int8_differs():
    """reference/mla_scmoe.py at a toy size: `f32` and the `int8` control see
    the same wave and give different logits, both finite; a tail sees the
    prefix and itself alone."""
    toy = {
        "hidden_size": 64, "num_layers": 2, "num_attention_heads": 4, "q_lora_rank": 32,
        "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "n_routed_experts": 8,
        "zero_expert_num": 4, "moe_topk": 3, "routed_scaling_factor": 6, "norm_topk_prob": False,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "vocab_size": 512,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "experts_held": 4, "expert_first": 2,
    }
    weights = SC_REF.init_weights(toy, 3)
    assert weights["layers"]["we_gate"].shape == (2, 4, 64, 32)  # the share's experts alone
    assert weights["layers"]["router"].shape == (2, 64, 12)      # the router's whole width
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, 500, 40).tolist()
    tails = [rng.integers(1, 500, n).tolist() for n in (12, 9)]
    spans = [(7, 5), (5, 4)]
    f32 = SC_REF.wave_logits(toy, weights, prefix, tails, spans, "f32", 300)
    low = SC_REF.wave_logits(toy, weights, prefix, tails, spans, "int8", 300)
    assert f32.shape == low.shape == (9, 300)
    assert np.isfinite(f32).all() and np.isfinite(low).all()
    assert float(np.max(np.abs(f32 - low))) > 1e-3
    assert float(np.mean(np.abs(f32 - low))) < 0.25 * float(np.std(f32))
    alone = SC_REF.wave_logits(toy, weights, prefix, tails[:1], spans[:1], "f32", 300)
    np.testing.assert_allclose(alone, f32[:5], rtol=1e-4, atol=1e-5)


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


def test_gdn_reference_runs_in_both_modes_and_int8_differs():
    """reference/gdn_moe.py at a toy size: `f32` and the `int8` control see
    the same wave and give different logits, both finite; a tail is seeded
    from the prefix's state and sees the prefix and itself alone; and the
    prefix's padding (its length is rounded up to few programs) is not
    there."""
    toy = {
        "hidden_size": 64, "num_hidden_layers": 4, "full_attention_interval": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 16, "linear_value_head_dim": 16,
        "linear_conv_kernel_dim": 4, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_experts": 16, "num_experts_per_tok": 3, "norm_topk_prob": True, "vocab_size": 512,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "experts_held": 4, "expert_first": 4,
    }
    weights = GDN_REF.init_weights(toy, 3)
    assert weights["layers"]["we_gate"].shape == (4, 4, 64, 32)   # the share's experts alone
    assert weights["layers"]["router"].shape == (4, 64, 16)       # the router's whole width
    assert weights["gdn"]["w_qkvz"].shape == (3, 64, 2 * 32 + 2 * 64) and weights["attn"]["wq"].shape == (1, 64, 256)
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, 500, 40).tolist()
    tails = [rng.integers(1, 500, n).tolist() for n in (12, 9)]
    spans = [(7, 5), (5, 4)]
    f32 = GDN_REF.wave_logits(toy, weights, prefix, tails, spans, "f32", 300)
    low = GDN_REF.wave_logits(toy, weights, prefix, tails, spans, "int8", 300)
    assert f32.shape == low.shape == (9, 300)
    assert np.isfinite(f32).all() and np.isfinite(low).all()
    assert float(np.max(np.abs(f32 - low))) > 1e-3
    assert float(np.mean(np.abs(f32 - low))) < 0.25 * float(np.std(f32))
    alone = GDN_REF.wave_logits(toy, weights, prefix, tails[:1], spans[:1], "f32", 300)
    np.testing.assert_allclose(alone, f32[:5], rtol=1e-4, atol=1e-5)
    # a shorter prefix is another state and another answer (the tail is seeded from it)
    shorter = GDN_REF.wave_logits(toy, weights, prefix[:-1], tails[:1], spans[:1], "f32", 300)
    assert float(np.max(np.abs(shorter - alone))) > 1e-2


# ------------------------------------------------------- set-up (PR 39)
CELLS = ["internlm1_8b-backlog20", "glm4_7_flash-backlog20", "longcat_flash-backlog20", "qwen3_next-backlog20"]
GRANITE_CELL = "granite4_h_micro-backlog20"
GRANITE_METRICS = ["ssm_device_ms_per_bind.tput", "ssm_scan_device_ms_per_bind.tput", "ssd_chunk_scan_roofline.tput"]
COHERE_CELL = "command_a_plus-backlog128"
COHERE_METRICS = ["swa_attn_device_ms_per_bind.tput", "window_attn_roofline.tput", "window_kv_read_share.tput"]
SETUP_METRICS = {"setup_build_s": "build_s", "setup_params_s": "params_s",
                 "setup_trace_lower_s": "trace_lower_s", "setup_load_compile_s": "load_compile_s",
                 "setup_programs_compiled": "programs_compiled"}


def test_the_state_entries_follow_one_another_and_name_their_cells_alone():
    """The fourth configuration's five entries; the three that read any
    family with a state and an attention under `full_attn` list the fifth
    cell behind the fourth, the two of the delta rule's scopes the fourth
    alone; `full_attn` the sixth cell's global layer last."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("gdn_device_ms_per_bind.tput")
    entries = bench["per_layer"][first:first + 5]
    assert [m["name"] for m in entries] == [
        "gdn_device_ms_per_bind.tput", "gdn_scan_device_ms_per_bind.tput", "full_attn_device_ms_per_bind.tput",
        "state_carry_device_ms_per_bind.tput", "state_valid_share.tput"]
    for m in entries:
        assert m["workloads"] == ["qwen3_next-backlog20"] + ([GRANITE_CELL] if m["name"] in (
            "full_attn_device_ms_per_bind.tput", "state_carry_device_ms_per_bind.tput",
            "state_valid_share.tput") else []) + ([COHERE_CELL] if m["name"] == "full_attn_device_ms_per_bind.tput"
                                                   else [])
        assert m["moves"] == "binds_per_s" and m["layer"] == "model"
    assert [m["source"] for m in entries] == ["device_trace"] * 4 + ["program_counter"]
    assert entries[-1]["unit"] == "%" and entries[-1]["better"] == "higher"
    # nothing but the set-up entries after them, then the fifth cell's three and the sixth's
    assert names[first + 5:] == list(SETUP_METRICS) + GRANITE_METRICS + COHERE_METRICS


def test_the_set_up_entries_follow_the_state_entries_and_move_setup_s():
    """The five set-up entries, every cell listed, the fifth and sixth
    cells last; every other entry moves `binds_per_s`."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("setup_build_s")
    entries = bench["per_layer"][first:first + 5]
    assert [m["name"] for m in entries] == list(SETUP_METRICS)
    assert [w["name"] for w in bench["workloads"]] == CELLS + [GRANITE_CELL, COHERE_CELL]
    for m in entries:
        assert m == {"name": m["name"], "unit": "count" if m["name"] == "setup_programs_compiled" else "s",
                     "better": "lower", "source": "program_counter",
                     "layer": "entry" if m["name"] in ("setup_build_s", "setup_params_s") else "device",
                     "moves": "setup_s", "workloads": CELLS + [GRANITE_CELL, COHERE_CELL]}
    assert all(m["moves"] == "binds_per_s" for m in bench["per_layer"] if m not in entries)


def setup_ctx(setup: dict | None):
    """The snapshot at the window's open, as run.py takes it: `setup` under
    the engine's stats, or no such key (a parent of PR 39)."""
    import run as bench_run
    from types import SimpleNamespace

    engine = {"waves": 3} if setup is None else {"waves": 3, "setup": setup}
    before = {"sched": {"client": {"engine": engine}}, "compiles": {"programs": 40}}
    return bench_run.Ctx(outcome=SimpleNamespace(before=before, after=before))


RECORD = {"build_s": 23.5, "params_s": 21.25, "programs": 23, "programs_compiled": 0, "programs_loaded": 23,
          "trace_lower_s": 6.5, "load_compile_s": 12.0, "retrieval_s": 1.5}


@pytest.mark.parametrize("name", list(SETUP_METRICS))
def test_a_set_up_reader_reads_the_record_and_none_without_it(name):
    import run as bench_run

    read = bench_run.reader_for(name)
    assert read(setup_ctx(RECORD)) == float(RECORD[SETUP_METRICS[name]])
    assert read(setup_ctx(None)) is None  # the parent
    assert read(setup_ctx({k: v for k, v in RECORD.items() if k != SETUP_METRICS[name]})) is None


# ------------------------------------------------------- granite-4.0-h-micro
GRANITE_REF = _load(BENCH / "reference" / "mamba2_hybrid.py", "bench_reference_mamba2_hybrid_pins")


class TestGraniteThroughTheSeam:
    """benchmark/configs/granite-4_0-h-micro.json loaded the way run.py loads
    it: `"architecture": "mamba2_hybrid"` selects arch/ and reference/,
    `register` hands the program a config of its own type, and the arch
    file's count of what a token needs is the config type's books."""

    IN = 2048 * (4096 + 4352 + 64)        # 17.43 M: W_in [z | x B C | dt]
    OUT = 4096 * 2048                     # 8.39 M: W_out
    ATTN = 2 * 2048 * 2048 + 2 * 2048 * 512   # 10.49 M: W_q, W_o, W_k, W_v
    MLP = 3 * 2048 * 8192                 # 50.33 M: [gate | up], down
    STATE = 2 * 2 * 64 * 64 * 128         # x B^T and S C a head, 2 FLOPs a multiply-add

    @pytest.fixture(scope="class")
    def loaded(self):
        from harness import seam

        conf = seam.load_config(BENCH / "configs" / "granite-4_0-h-micro.json")
        return conf, seam.program(conf), seam.reference(conf)

    def test_the_file_selects_its_architecture_and_registers_its_own_config_type(self, loaded):
        from k8s_llm_scheduler_tpu.models import family, mamba2_hybrid
        from k8s_llm_scheduler_tpu.models.configs import Mamba2HybridConfig, get_config

        conf, arch, ref = loaded
        assert arch.__file__.endswith("arch/mamba2_hybrid.py")
        assert ref.__file__.endswith("reference/mamba2_hybrid.py")
        cfg = get_config(arch.register(conf))
        assert isinstance(cfg, Mamba2HybridConfig) and family(cfg) is mamba2_hybrid
        assert (cfg.n_layers, cfg.period, cfg.attn_position, cfg.n_periods, cfg.n_ssm_layers) == (40, 10, 5, 4, 36)
        assert (cfg.head_dim, cfg.ssm_inner, cfg.conv_width, cfg.d_ff) == (64, 4096, 4352, 8192)
        assert mamba2_hybrid.cache_layers(cfg) == 4 and mamba2_hybrid.state_layers(cfg) == 4
        members = mamba2_hybrid.state_shapes(cfg)  # a member a Mamba-2 position of the period
        assert [m[0] for m in members] == [(64, 64, 128)] * 9 + [(3, 4352)] * 9
        per_sequence = 4 * sum(4 * int(np.prod(m[0])) for m in members)
        assert per_sequence == 77_377_536  # 77.4 MB of float32 a sequence; 619 MB a wave of 8 rows

    def test_a_token_by_hand_is_the_arch_files_count_and_the_config_types_books(self, loaded):
        from k8s_llm_scheduler_tpu.models.configs import get_config
        from k8s_llm_scheduler_tpu.observability.profiler import matmul_flops_per_token

        conf, arch, _ = loaded
        by_hand = 2 * (36 * (self.IN + self.OUT) + 4 * self.ATTN + 40 * self.MLP) + 36 * self.STATE
        assert by_hand == 6_045_040_640  # 6.05 GFLOP a token through 40 layers, 75 MFLOP of it state products
        assert arch.flops_per_token(conf, with_head=False) == by_hand
        assert arch.flops_per_token(conf, with_head=True) - by_hand == 2 * 2048 * 100_352
        cfg = get_config(arch.register(conf))
        assert matmul_flops_per_token(cfg) == arch.flops_per_token(conf, with_head=True)
        assert (cfg.ssm_params(), cfg.attn_params()) == (self.IN + self.OUT, self.ATTN)
        assert cfg.ssm_state_flops_per_token() == arch.ssm_state_flops_per_token(conf) == self.STATE
        # the four layers that attend alone: 32 heads x 2 x 2 x 64 a key
        assert arch.attention_flops(conf, 1, 1) == cfg.attn_flops_per_key() == 4 * 32 * 4 * 64

    def test_a_scan_call_reads_and_writes_each_rows_state_once(self, loaded):
        conf, arch, _ = loaded
        state = 64 * 64 * 128 * 4
        flops, moved = arch.ssd_kernel_cost(8, 2.5, 24, conf)   # a decode call: 8 rows, ~2.5 valid positions
        assert moved == 8 * 2 * state + 20 * (2 * 64 * 64 + 2 * 128 + 3 * 64) * 4
        assert flops == 20 * (64 * (4.0 * 64 * 128 + 24 * 64) + 24 * 128)
        assert moved / 819e9 > 10 * flops / 197e12   # byte-bound in decode
        _, empty = arch.ssd_kernel_cost(8, 0, 24, conf)   # a call with no valid position still moves the state
        assert empty == 8 * 2 * state

    def test_the_configuration_file_holds_the_published_row(self, loaded):
        """Every number of the catalog row under its own key; nothing cut:
        all 40 layers, the whole vocabulary, every width."""
        conf, _, _ = loaded
        published = {
            "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
            "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
            "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
            "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
            "mamba_proj_bias": False, "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
            "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 0,
            "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
            "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
            "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192,
            "tie_word_embeddings": True, "vocab_size": 100352,
        }
        assert {k: conf[k] for k in published} == published
        assert [i for i, t in enumerate(conf["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
        assert len(conf["layer_types"]) == 40 and conf["reduced"] == []
        entry = next(c for c in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["configs"]
                     if c["name"] == conf["name"])
        assert entry["reduced"] == [] and entry["source"] == conf["source"]
        mamba = self.IN + self.OUT + 4 * 4352 + 4352 + 3 * 64 + 4096 + self.MLP + 2 * 2048
        attn = self.ATTN + self.MLP + 2 * 2048
        assert conf["parameters"] == 36 * mamba + 4 * attn + 100_352 * 2048 + 2048 == 3_191_396_096

    def test_the_reference_imports_nothing_of_the_program_or_the_harness(self):
        text = (BENCH / "reference" / "mamba2_hybrid.py").read_text()
        imports = [ln for ln in text.splitlines() if ln.startswith(("import ", "from "))]
        assert imports == ["from __future__ import annotations", "import functools", "import jax",
                           "import jax.numpy as jnp", "import numpy as np"]
        assert "lax.scan(step, s0" in text and "pallas" not in text  # the recurrence, not the chunked form

    def test_the_cell_is_listed_where_its_readers_find_something(self):
        bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
        cell = GRANITE_CELL
        # the fifth cell, the sixth behind it
        assert bench["workloads"][-2] == {**bench["workloads"][-2], "name": cell, "config": "granite-4_0-h-micro",
                                          "traffic": "backlog20_pool80", "chips": 1}
        assert bench["configs"][-2]["name"] == "granite-4_0-h-micro"
        listed = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
        assert set(GRANITE_METRICS) <= listed and [m["name"] for m in bench["per_layer"][-6:-3]] == GRANITE_METRICS
        for m in bench["per_layer"][-6:-3]:
            assert m["workloads"] == [cell] and m["moves"] == "binds_per_s"
        assert {"full_attn_device_ms_per_bind.tput", "state_carry_device_ms_per_bind.tput",
                "state_valid_share.tput", "prefix_attn_roofline.tput", "model_mfu.tput", *SETUP_METRICS} <= listed
        # readers of another family's scopes or counters
        assert not listed & {"gdn_device_ms_per_bind.tput", "gdn_scan_device_ms_per_bind.tput",
                             "moe_bounded_share.tput", "experts_hit_per_layer_call.tput",
                             "moe_experts_device_ms_per_bind.tput", "mla_proj_device_ms_per_bind.tput",
                             "dense_ffn_device_ms_per_bind.tput", "moe_grouped_swiglu_roofline.tput"}
        for m in bench["per_layer"]:
            if cell in m["workloads"]:  # appended, nothing moved: behind it the sixth cell alone
                assert m["workloads"][m["workloads"].index(cell) + 1:] in ([], [COHERE_CELL])
        assert next(m for m in bench["end_to_end"] if m["name"] == "binds_per_s")["workloads"][-2] == cell


def test_mamba2_reference_runs_in_both_modes_and_int8_differs():
    """reference/mamba2_hybrid.py at a toy size: `f32` and the `int8`
    control see the same wave and give different logits, both finite; a
    tail is seeded from the prefix's state and sees the prefix and itself
    alone; the prefix's padding is not there."""
    toy = {
        "hidden_size": 64, "layer_types": ["mamba", "attention", "mamba"], "num_attention_heads": 4,
        "num_key_value_heads": 2, "shared_intermediate_size": 96, "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 32, "mamba_d_conv": 4, "vocab_size": 512, "rms_norm_eps": 1e-5,
        "embedding_multiplier": 12, "residual_multiplier": 0.22, "attention_multiplier": 1 / 16,
        "logits_scaling": 8,
    }
    weights = GRANITE_REF.init_weights(toy, 3)
    assert weights["ssm"]["w_in"].shape == (2, 64, 128 + 192 + 8) and weights["attn"]["wq"].shape == (1, 64, 64)
    assert weights["layers"]["w_in"].shape == (3, 64, 192) and "lm_head" not in weights  # the table is tied
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, 500, 40).tolist()
    tails = [rng.integers(1, 500, n).tolist() for n in (12, 9)]
    spans = [(7, 5), (5, 4)]
    f32 = GRANITE_REF.wave_logits(toy, weights, prefix, tails, spans, "f32", 300)
    low = GRANITE_REF.wave_logits(toy, weights, prefix, tails, spans, "int8", 300)
    assert f32.shape == low.shape == (9, 300)
    assert np.isfinite(f32).all() and np.isfinite(low).all()
    assert float(np.max(np.abs(f32 - low))) > 1e-3
    assert float(np.mean(np.abs(f32 - low))) < 0.25 * float(np.std(f32))
    alone = GRANITE_REF.wave_logits(toy, weights, prefix, tails[:1], spans[:1], "f32", 300)
    np.testing.assert_allclose(alone, f32[:5], rtol=1e-4, atol=1e-5)
    # a shorter prefix is another state and another answer (the tail is seeded from it): 100 x the
    # tolerance above (each mixer's output joins the stream times residual_multiplier 0.22)
    shorter = GRANITE_REF.wave_logits(toy, weights, prefix[:-1], tails[:1], spans[:1], "f32", 300)
    assert float(np.max(np.abs(shorter - alone))) > 1e-3


@pytest.mark.parametrize("name", GRANITE_METRICS)
def test_the_granite_readers_read_none_without_a_trace(name):
    """A run without a trace, or a program without the counters (a parent),
    reads None and raises nothing."""
    import run as bench_run
    from types import SimpleNamespace

    engine = {"waves": 3}
    snap = {"sched": {"client": {"engine": engine}}, "compiles": {"programs": 40}}
    ctx = bench_run.Ctx(outcome=SimpleNamespace(before=snap, after=snap, trace_span=None), profile=None,
                        xplane_path=None, conf={"architecture": "mamba2_hybrid"})
    assert bench_run.reader_for(name)(ctx) is None


def test_the_roofline_reader_reads_the_kernels_events_inside_wave_runs():
    """`ssd_chunk_scan_roofline.tput` on a hand-made trace: two kernel events
    inside a `jit_wave` run (a decode call [8, 64, 1, 64, 24] and a suffix
    call [8, 64, 2, 64, 64]: Y as the kernel leaves it, [rows, heads, chunks,
    head width, chunk]) and one of a prefix prefill outside any, which
    is not counted; least time by `ssd_kernel_cost` at the window's valid
    share, over the two events' device time."""
    import run as bench_run
    from types import SimpleNamespace

    from harness import seam

    conf = seam.load_config(BENCH / "configs" / "granite-4_0-h-micro.json")
    ev = lambda name, start, dur: SimpleNamespace(name=name, start_ns=start, duration_ns=dur)  # noqa: E731
    ops = [ev("%ssd_chunk_scan.3 = (f32[8,64,1,64,24]{4,3,2,1,0}, f32[4,8,64,64,128]) custom-call(...)", 100, 200_000),
           ev("%ssd_chunk_scan.1 = (f32[8,64,2,64,64]{4,3,2,1,0}, f32[4,8,64,64,128]) custom-call(...)", 300_000,
              500_000),
           ev("%ssd_chunk_scan.9 = (f32[1,64,32,64,64]{4,3,2,1,0}, f32[4,1,64,64,128]) custom-call(...)",
              5_000_000, 900_000)]
    runs = [ev("jit_wave(123)", 0, 1_000_000), ev("jit_prefix_prefill_kv(9)", 4_000_000, 2_000_000)]
    profile = SimpleNamespace(planes=[SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Ops", events=ops), SimpleNamespace(name="XLA Modules", events=runs)])])
    before = {"sched": {"client": {"engine": {"state_tokens_valid": 0, "state_tokens_computed": 0}}}}
    after = {"sched": {"client": {"engine": {"state_tokens_valid": 200, "state_tokens_computed": 1000}}}}
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = bench_run.Ctx(outcome=SimpleNamespace(before=before, after=after), profile=profile, conf=conf, peaks=peaks)
    arch = seam.program(conf)
    least = sum(max(f / 197e12, b / 819e9) for f, b in (
        arch.ssd_kernel_cost(8, 0.2 * 24, 24, conf), arch.ssd_kernel_cost(8, 0.2 * 128, 64, conf)))
    got = bench_run.reader_for("ssd_chunk_scan_roofline.tput")(ctx)
    assert got == pytest.approx(100.0 * least / 700e-6)
    assert 0 < got <= 100


# ---------------------------------------------------- command-a-plus-05-2026
COHERE_REF = _load(BENCH / "reference" / "cohere2_moe.py", "bench_reference_cohere2_moe_pins")


class TestCommandAPlusThroughTheSeam:
    """benchmark/configs/command-a-plus-05-2026.json loaded the way run.py
    loads it: `"architecture": "cohere2_moe"` selects arch/ and reference/,
    `register` hands the program a config of its own type, and the arch
    file's count of what a token needs is the config type's books."""

    ATTN = 2 * 4096 * 16384 + 2 * 4096 * 1024   # 142.6 M: W_q, W_o (128 heads of 128); W_k, W_v (8)
    ROUTER = 4096 * 128
    EXPERT = 3 * 4096 * 4096                    # 50.3 M: gate, up, down of one expert

    @pytest.fixture(scope="class")
    def loaded(self):
        from harness import seam

        conf = seam.load_config(BENCH / "configs" / "command-a-plus-05-2026.json")
        return conf, seam.program(conf), seam.reference(conf)

    def test_the_file_selects_its_architecture_and_registers_its_own_config_type(self, loaded):
        from k8s_llm_scheduler_tpu.models import cohere2_moe, family
        from k8s_llm_scheduler_tpu.models.configs import Cohere2MoeConfig, get_config

        conf, arch, ref = loaded
        assert arch.__file__.endswith("arch/cohere2_moe.py")
        assert ref.__file__.endswith("reference/cohere2_moe.py")
        cfg = get_config(arch.register(conf))
        assert isinstance(cfg, Cohere2MoeConfig) and family(cfg) is cohere2_moe
        assert (cfg.n_layers, cfg.global_layers, cfg.window, cfg.experts_held, cfg.vocab_size) == (
            4, (3,), 4096, 16, 32768)
        assert (cfg.shared_scale, cfg.d_ff_shared, cfg.logit_scale, cfg.norm_eps) == (0.25, 16384, 1.0, 1e-5)
        with pytest.raises(ValueError, match="rotary"):
            arch.register({**conf, "position_embedding_type": "rope"})

    def test_a_token_by_hand_is_the_arch_files_count_and_the_config_types_books(self, loaded):
        from k8s_llm_scheduler_tpu.models.configs import get_config

        conf, arch, _ = loaded
        layer = self.ATTN + self.ROUTER + (8 * 16 / 128 + 4) * self.EXPERT
        body = 2.0 * 4 * layer
        assert arch.flops_per_token(conf, with_head=False) == body
        assert arch.flops_per_token(conf, with_head=True) == body + 2.0 * 4096 * 32768
        cfg = get_config(arch.register(conf))
        assert cfg.matmul_flops_per_token() == arch.flops_per_token(conf, with_head=True)
        # the global layer sees every key, a window layer 4,096 of them at most
        per_key = 4.0 * 128 * 128
        assert arch.attention_flops(conf, 10, 3000) == 10 * per_key * 4 * 3000
        assert arch.attention_flops(conf, 10, 10000) == 10 * per_key * (10000 + 3 * 4096)
        assert cfg.attn_flops_per_token(10000) == per_key * (10000 + 3 * 4096)
        assert cfg.attn_flops_per_token(3000) == cfg.attn_flops_per_key() * 3000

    def test_a_grouped_kernel_call_is_bound_by_the_touched_experts_bytes(self, loaded):
        _, arch, _ = loaded
        flops, moved = arch.grouped_kernel_cost(16, 8, 4096, 4096, 2, 2)
        assert flops == 2.0 * 16 * 4096 * 4096 * 2
        assert moved == 8 * 4096 * 4096 * 2 * 2 + 16 * (4096 * 2 + 4096 * 2)
        assert moved / 819e9 > 10 * flops / 197e12

    def test_the_configuration_file_holds_the_published_row(self, loaded):
        """Every number of the catalog row under its own key, the nested rope
        group whole and the 32 layer types as published; three cuts, each in
        `reduced`: depth, experts held, vocabulary."""
        conf, _, _ = loaded
        published = {
            "attention_bias": False, "expert_selection_fn": "sigmoid", "first_k_dense_replace": 0, "head_dim": 128,
            "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
            "layer_switch": 4, "logit_scale": 1, "max_position_embeddings": 200000, "model_type": "cohere2_moe",
            "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128, "num_experts_per_tok": 8,
            "num_key_value_heads": 8, "num_shared_experts": 4, "order_of_interleaved_layers": "local_attn_first",
            "position_embedding_type": "rope_gptj", "prefix_dense_intermediate_size": 16384,
            "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
            "rope_parameters": {"rope_theta": 50000, "rope_type": "default"}, "rope_theta": 50000, "rotary_pct": 1,
            "shared_expert_combination_strategy": "average", "sliding_window": 4096, "tf_legacy_loss": False,
            "tie_word_embeddings": True, "use_embedding_sharing": True, "use_gated_activation": True,
            "use_parallel_block": True, "use_parallel_embedding": False, "use_qk_norm": False,
        }
        assert {k: conf[k] for k in published} == published
        assert conf["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 8
        assert conf["reduced"] == ["num_hidden_layers", "experts_held", "vocab_size"]
        assert (conf["num_hidden_layers"], conf["experts_held"], conf["vocab_size"]) == (4, 16, 32768)
        assert conf["published"] == {**conf["published"], "num_hidden_layers": 32, "experts_held": 128,
                                     "vocab_size": 262144}
        entry = next(c for c in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["configs"]
                     if c["name"] == conf["name"])
        assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
        layer = self.ATTN + self.ROUTER + 3 * 4096 * 16384 + 4096 + 16 * self.EXPERT
        assert conf["parameters"] == 4 * layer + 32768 * 4096 + 4096 == 4_733_292_544

    def test_the_reference_imports_nothing_of_the_program_or_the_harness(self):
        text = (BENCH / "reference" / "cohere2_moe.py").read_text()
        imports = [ln for ln in text.splitlines() if ln.startswith(("import ", "from "))]
        assert imports == ["from __future__ import annotations", "import functools", "import jax",
                           "import jax.numpy as jnp", "import numpy as np"]
        assert "pallas" not in text

    def test_the_cell_is_listed_where_its_readers_find_something(self):
        bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
        cell = COHERE_CELL
        assert bench["workloads"][-1] == {**bench["workloads"][-1], "name": cell,
                                          "config": "command-a-plus-05-2026", "traffic": "backlog", "chips": 1}
        assert bench["configs"][-1]["name"] == "command-a-plus-05-2026"
        listed = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
        assert [m["name"] for m in bench["per_layer"][-3:]] == COHERE_METRICS
        for m in bench["per_layer"][-3:]:
            assert m["workloads"] == [cell] and m["moves"] == "binds_per_s"
        assert {"full_attn_device_ms_per_bind.tput", "moe_experts_device_ms_per_bind.tput",
                "moe_router_device_ms_per_bind.tput", "moe_shared_device_ms_per_bind.tput",
                "experts_hit_per_layer_call.tput", "moe_bounded_share.tput", "moe_grouped_swiglu_roofline.tput",
                "moe_grouped_matmul_roofline.tput", "window_compiles.tput", "model_mfu.tput",
                "prefix_attn_roofline.tput", *SETUP_METRICS} <= listed
        # readers of another family's scopes or counters
        assert not listed & {"gdn_device_ms_per_bind.tput", "ssm_device_ms_per_bind.tput",
                             "state_valid_share.tput", "state_carry_device_ms_per_bind.tput",
                             "mla_proj_device_ms_per_bind.tput", "dense_ffn_device_ms_per_bind.tput",
                             "zero_expert_share.tput", "scmoe_grouped_swiglu_roofline.tput"}
        for m in bench["per_layer"]:
            if cell in m["workloads"]:
                assert m["workloads"][-1] == cell  # appended, nothing moved
        assert next(m for m in bench["end_to_end"] if m["name"] == "binds_per_s")["workloads"][-1] == cell


def test_cohere2_reference_runs_in_both_modes_and_int8_differs():
    """reference/cohere2_moe.py at a toy size: `f32` and the `int8` control
    see the same wave and give different logits, both finite; a tail sees
    the prefix and itself alone; a window shorter than the prefix is another
    answer than one wider than it."""
    toy = {
        "hidden_size": 64, "num_hidden_layers": 4, "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
        "intermediate_size": 32, "num_experts": 16, "num_shared_experts": 4, "num_experts_per_tok": 4,
        "experts_held": 4, "expert_first": 0, "norm_topk_prob": True, "logit_scale": 1, "vocab_size": 512,
        "rope_theta": 10000, "layer_norm_eps": 1e-5,
    }
    weights = COHERE_REF.init_weights(toy, 3)
    assert weights["layers"]["we_gate"].shape == (4, 4, 64, 32) and weights["layers"]["ws_gate"].shape == (4, 64, 128)
    assert "lm_head" not in weights  # the table is tied
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, 500, 40).tolist()
    tails = [rng.integers(1, 500, n).tolist() for n in (12, 9)]
    spans = [(7, 5), (5, 4)]
    f32 = COHERE_REF.wave_logits(toy, weights, prefix, tails, spans, "f32", 300)
    low = COHERE_REF.wave_logits(toy, weights, prefix, tails, spans, "int8", 300)
    assert f32.shape == low.shape == (9, 300)
    assert np.isfinite(f32).all() and np.isfinite(low).all()
    assert float(np.max(np.abs(f32 - low))) > 1e-3
    assert float(np.mean(np.abs(f32 - low))) < 0.25 * float(np.std(f32))
    alone = COHERE_REF.wave_logits(toy, weights, prefix, tails[:1], spans[:1], "f32", 300)
    np.testing.assert_allclose(alone, f32[:5], rtol=1e-4, atol=1e-5)
    wide = COHERE_REF.wave_logits({**toy, "sliding_window": 4096}, weights, prefix, tails[:1], spans[:1], "f32", 300)
    assert float(np.max(np.abs(wide - alone))) > 1e-3


@pytest.mark.parametrize("name", COHERE_METRICS)
def test_the_window_readers_read_none_without_a_trace(name):
    """A run without a trace, or a program without the counters (a parent),
    reads None and raises nothing."""
    import run as bench_run
    from types import SimpleNamespace

    engine = {"waves": 3}
    snap = {"sched": {"client": {"engine": engine}}, "compiles": {"programs": 40}}
    ctx = bench_run.Ctx(outcome=SimpleNamespace(before=snap, after=snap, trace_span=None), profile=None,
                        xplane_path=None, conf={"architecture": "cohere2_moe", "sliding_window": 4096},
                        waves=[], trace_waves=[])
    assert bench_run.reader_for(name)(ctx) is None


def test_the_window_share_reader_reads_the_counters():
    import run as bench_run
    from types import SimpleNamespace

    before = {"sched": {"client": {"engine": {"window_keys_read": 100, "window_keys_causal": 200}}}}
    after = {"sched": {"client": {"engine": {"window_keys_read": 4196, "window_keys_causal": 10200}}}}
    ctx = bench_run.Ctx(outcome=SimpleNamespace(before=before, after=after))
    assert bench_run.reader_for("window_kv_read_share.tput")(ctx) == pytest.approx(100 * 4096 / 10000)


def test_the_window_roofline_reader_counts_the_keys_in_the_window():
    """`window_attn_roofline.tput` on a hand-made trace: two kernel events
    inside a `jit_wave` run (a suffix call of 8 x 16 x 128 query rows a KV
    head, a decode call of 8 x 16 x 24) and one of a chunked prefix prefill
    outside any, which is not counted; least time by
    `_window.window_kernel_cost` over the keys the waves' queries see
    through the window, over the two events' device time. The full
    kernel's reader does not see these events."""
    import run as bench_run
    from types import SimpleNamespace

    from harness import seam
    from metrics import _window

    conf = seam.load_config(BENCH / "configs" / "command-a-plus-05-2026.json")
    ev = lambda name, start, dur: SimpleNamespace(name=name, start_ns=start, duration_ns=dur)  # noqa: E731
    ops = [ev("%window_prefix_attention.3 = (f32[8,16384,128]{2,1,0}, f32[8,16384,128]) custom-call(...)",
              100, 3_000_000),
           ev("%window_prefix_attention.1 = (f32[8,3072,128]{2,1,0}, f32[8,3072,128]) custom-call(...)",
              3_100_000, 1_000_000),
           ev("%window_prefix_attention.9 = (f32[8,32768,128]{2,1,0}, f32[8,32768,128]) custom-call(...)",
              9_000_000, 6_000_000)]
    runs = [ev("jit_wave(123)", 0, 5_000_000), ev("jit_suffix_dense(9)", 8_000_000, 8_000_000)]
    profile = SimpleNamespace(planes=[SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Ops", events=ops), SimpleNamespace(name="XLA Modules", events=runs)])])
    waves = [{"prefix_ids": [0] * 10000, "prompts": [[1] * 70, [1] * 50], "served": [[2] * 40, [2] * 42]}]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = bench_run.Ctx(profile=profile, conf=conf, peaks=peaks, waves=waves, trace_waves=waves)
    offsets = list(range(110)) + list(range(92))
    keys = sum(4095 - o for o in offsets) / len(offsets)
    assert _window.prefix_keys_per_query(waves, 4096) == pytest.approx(keys)
    least = sum(max(f / 197e12, b / 819e9) for f, b in (
        _window.window_kernel_cost(8, 16384, 128, keys, 4095), _window.window_kernel_cost(8, 3072, 128, keys, 4095)))
    got = bench_run.reader_for("window_attn_roofline.tput")(ctx)
    assert got == pytest.approx(100.0 * least / 4e-3)
    assert 0 < got <= 100
    assert bench_run.reader_for("prefix_attn_roofline.tput")(ctx) is None
