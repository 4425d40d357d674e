"""Gated-delta-rule linear attention and gated softmax attention, three
layers to one, over sparse experts with a gated shared expert (`qwen3_next`:
Qwen3-Next-80B-A3B), one chip's share of an expert-parallel deployment, on
the program's side of the seam. arch/README.md says what a file here owes
the harness. The counts follow the program
(k8s_llm_scheduler_tpu/models/gdn_moe.py): what a token needs as it is run
THERE, on this share, never more.
"""

from __future__ import annotations


def register(conf: dict) -> str:
    """Register the configuration file's sizes with the program's model
    registry (models/configs.py is not edited) and return its name. A
    program without the config type (a parent of the PR that brought it)
    stops here with an ImportError, before anything is built."""
    from k8s_llm_scheduler_tpu.models import configs
    from k8s_llm_scheduler_tpu.models.configs import GdnMoeConfig

    if conf["torch_dtype"] != "bfloat16" or conf["hidden_act"] != "silu" or conf["attention_bias"]:
        raise ValueError(f"{conf['name']}: only bias-free bf16 SwiGLU models run through GdnMoeConfig")
    cfg = GdnMoeConfig.from_hf(
        conf["name"], conf, expert_first=conf["expert_first"], expert_count=conf["experts_held"])
    configs._REGISTRY[cfg.name] = cfg
    return cfg.name


# ------------------------------------------------------------ what a token needs
def _gdn_params(conf: dict) -> int:
    """Matrix parameters of one delta-rule mixer: W_qkvz, W_ba, W_o."""
    d = conf["hidden_size"]
    kw = conf["linear_num_key_heads"] * conf["linear_key_head_dim"]
    vw = conf["linear_num_value_heads"] * conf["linear_value_head_dim"]
    return d * (2 * kw + 2 * vw) + d * 2 * conf["linear_num_value_heads"] + vw * d


def _attention_params(conf: dict) -> int:
    """Matrix parameters of one gated attention: W_q (query and gate), W_k,
    W_v, W_o."""
    d, h, hkv, hd = (conf["hidden_size"], conf["num_attention_heads"], conf["num_key_value_heads"],
                     conf["head_dim"])
    return d * h * 2 * hd + 2 * d * hkv * hd + h * hd * d


def gdn_state_flops_per_token(conf: dict) -> float:
    """What a token costs ONE delta-rule layer beside its projections,
    COUNTED PER TOKEN AS THE RECURRENCE STATES IT: S^T k, the rank-one
    update k delta^T and S^T q, 2 x dk x dv each a value head. (The chunked
    form the program runs spends more: the C x C products and the solve of
    a chunk are the price of reading the state once a call, not work the
    model needs.)"""
    return 3 * 2.0 * conf["linear_num_value_heads"] * conf["linear_key_head_dim"] * conf["linear_value_head_dim"]


def held_picks_per_token(conf: dict) -> float:
    """Of a token's `num_experts_per_tok` picks, those that fall on an
    expert held HERE, as the expectation under a level router: 10 x 128 /
    512 = 2.5 at the cell's share. The measured counterpart is the wave
    counter `moe_assignments`."""
    return conf["num_experts_per_tok"] * conf["experts_held"] / conf["num_experts"]


def _layer_counts(conf: dict) -> tuple[int, int]:
    attn = conf["num_hidden_layers"] // conf["full_attention_interval"]
    return conf["num_hidden_layers"] - attn, attn


def flops_per_token(conf: dict, with_head: bool) -> float:
    """Matrix-multiply FLOPs of one token through every layer as this share
    runs it: a delta-rule mixer's projections and its state products (a
    layer with a fixed-size state counts here, arch/README.md) in three
    layers of four, the gated attention's projections in the fourth; in
    every layer the router over all its outputs, `held_picks_per_token`
    experts at `moe_intermediate_size`, the shared expert and its gate; plus
    the output head over the rows held where the call computes logits."""
    d = conf["hidden_size"]
    n_gdn, n_attn = _layer_counts(conf)
    moe = (d * conf["num_experts"] + held_picks_per_token(conf) * 3 * d * conf["moe_intermediate_size"]
           + 3 * d * conf["shared_expert_intermediate_size"] + d)
    per_token = 2.0 * (n_gdn * _gdn_params(conf) + n_attn * _attention_params(conf)
                       + conf["num_hidden_layers"] * moe) + n_gdn * gdn_state_flops_per_token(conf)
    return per_token + (2.0 * d * conf["vocab_size"] if with_head else 0.0)


def attention_flops(conf: dict, queries: float, keys_per_query: float) -> float:
    """Scores and values for `queries` tokens that each see `keys_per_query`
    keys, in the layers that attend ALONE (one in `full_attention_interval`):
    2 x 2 x head_dim a query head a key (models/configs.py
    `attn_flops_per_key` is the same count; tests/test_benchmark_seam.py
    holds the two equal)."""
    _, n_attn = _layer_counts(conf)
    return 4.0 * n_attn * conf["num_attention_heads"] * conf["head_dim"] * queries * keys_per_query


# ------------------------------------------------------- the grouped-matmul kernels
def grouped_kernel_cost(rows: float, groups_hit: float, k: int, n: int, n_weights: int,
                        out_bytes: int, weight_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one call of `moe_grouped_swiglu` (n_weights 2, bf16
    out) or `moe_grouped_matmul` (n_weights 1, f32 out) needs at this
    configuration's widths ([2048, 512] and [512, 2048]): `rows` valid rows
    held here, each against its own expert's [k, n] weights; the weights of
    the `groups_hit` experts that have a row read once; the valid rows of x
    read and of the output written once."""
    flops = 2.0 * rows * k * n * n_weights
    moved = groups_hit * k * n * n_weights * weight_bytes + rows * (k * 2 + n * out_bytes)
    return flops, float(moved)
