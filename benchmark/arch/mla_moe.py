"""Latent attention (MLA) with a sigmoid-routed sparse-expert feed-forward
behind leading dense layers (`glm4_moe_lite`: GLM-4.7-Flash) on the
program's side of the seam. arch/README.md says what a file here owes the
harness. The counts follow the program (k8s_llm_scheduler_tpu/models/
mla_moe.py): what a token needs as it is run there, never more.
"""

from __future__ import annotations


def register(conf: dict) -> str:
    """Register the configuration file's sizes with the program's model
    registry (models/configs.py is not edited) and return its name."""
    from k8s_llm_scheduler_tpu.models import configs

    if conf["torch_dtype"] != "bfloat16" or conf["hidden_act"] != "silu" or conf["attention_bias"]:
        raise ValueError(f"{conf['name']}: only bias-free bf16 SwiGLU models run through MlaMoeConfig")
    if conf["rope_scaling"] is not None or conf["partial_rotary_factor"] != 1:
        raise ValueError(f"{conf['name']}: rotary scaling / a partial rotary factor is not served")
    if conf["num_key_value_heads"] != conf["num_attention_heads"]:
        raise ValueError(f"{conf['name']}: latent attention serves one latent for all heads")
    if conf["num_nextn_predict_layers"] != 0:
        raise ValueError(f"{conf['name']}: the multi-token-prediction module is not served (reduced to 0)")
    cfg = configs.MlaMoeConfig.from_hf(conf["name"], conf)
    configs._REGISTRY[cfg.name] = cfg
    return cfg.name


# ------------------------------------------------------------ what a token needs
def _attention_params(conf: dict) -> int:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    dq, dc = conf["q_lora_rank"], conf["kv_lora_rank"]
    dn, dr, dv = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"]
    return d * dq + dq * h * (dn + dr) + d * (dc + dr) + dc * h * (dn + dv) + h * dv * d


def flops_per_token(conf: dict, with_head: bool) -> float:
    """Matrix-multiply FLOPs of one token through every layer: the five
    attention projections (W_dq, W_uq, W_dkv, W_ukv or its absorbed halves:
    the same count, W_o); in a dense layer gate, up, down at
    `intermediate_size`; in an expert layer the router, the
    `num_experts_per_tok` experts the token is sent to and the shared
    experts at `moe_intermediate_size`; plus the output head where the call
    computes logits for it."""
    d = conf["hidden_size"]
    n_dense = conf["first_k_dense_replace"]
    n_moe = conf["num_hidden_layers"] - n_dense
    expert = 3 * d * conf["moe_intermediate_size"]
    moe = d * conf["n_routed_experts"] + (conf["num_experts_per_tok"] + conf["n_shared_experts"]) * expert
    per_token = (conf["num_hidden_layers"] * _attention_params(conf)
                 + n_dense * 3 * d * conf["intermediate_size"] + n_moe * moe)
    return 2.0 * per_token + (2.0 * d * conf["vocab_size"] if with_head else 0.0)


def attention_flops(conf: dict, queries: float, keys_per_query: float) -> float:
    """Scores and values for `queries` tokens that each see `keys_per_query`
    keys, in the ABSORBED form the program runs on every segment: per head
    2 x (kv_lora_rank + dr) for the score against the latent and 2 x
    kv_lora_rank for the latent summed, 20 x 2 x (576 + 512) a key at the
    published widths (models/configs.py `attn_flops_per_key` is the same
    count; tests/test_benchmark_seam.py holds the two equal)."""
    per_key = 2.0 * (2 * conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
    return conf["num_hidden_layers"] * conf["num_attention_heads"] * per_key * queries * keys_per_query


# ------------------------------------------------------- the grouped-matmul kernels
def grouped_kernel_cost(rows: int, groups_hit: int, k: int, n: int, n_weights: int,
                        out_bytes: int, weight_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one call of `moe_grouped_swiglu` (n_weights 2, bf16
    out) or `moe_grouped_matmul` (n_weights 1, f32 out) needs: `rows` valid
    rows, each against its own expert's [k, n] weights; the weights of the
    `groups_hit` experts that have a row read once; the valid rows of x
    read and of the output written once."""
    flops = 2.0 * rows * k * n * n_weights
    moved = groups_hit * k * n * n_weights * weight_bytes + rows * (k * 2 + n * out_bytes)
    return flops, float(moved)
