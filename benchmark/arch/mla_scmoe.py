"""Shortcut-connected double layers over latent attention with identity
experts (`longcat_flash`: LongCat-Flash-Chat), one chip's share of an
expert-parallel deployment, on the program's side of the seam.
arch/README.md says what a file here owes the harness. The counts follow the
program (k8s_llm_scheduler_tpu/models/mla_scmoe.py): what a token needs as
it is run THERE, on this share, never more.
"""

from __future__ import annotations


def register(conf: dict) -> str:
    """Register the configuration file's sizes with the program's model
    registry (models/configs.py is not edited) and return its name. A
    program without the config type (a parent of the PR that brought it)
    stops here with an ImportError, before anything is built."""
    from k8s_llm_scheduler_tpu.models import configs

    if conf["torch_dtype"] != "bfloat16" or conf["hidden_act"] != "silu" or conf["attention_bias"]:
        raise ValueError(f"{conf['name']}: only bias-free bf16 SwiGLU models run through MlaScmoeConfig")
    if conf["norm_topk_prob"] or conf.get("router_bias", False):
        raise ValueError(f"{conf['name']}: the router is served without a logit bias and without renormalised weights")
    cfg = configs.MlaScmoeConfig.from_hf(
        conf["name"], conf, expert_first=conf["expert_first"], expert_count=conf["experts_held"])
    configs._REGISTRY[cfg.name] = cfg
    return cfg.name


# ------------------------------------------------------------ what a token needs
def _attention_params(conf: dict) -> int:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    dq, dc = conf["q_lora_rank"], conf["kv_lora_rank"]
    dn, dr, dv = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"]
    return d * dq + dq * h * (dn + dr) + d * (dc + dr) + dc * h * (dn + dv) + h * dv * d


def held_picks_per_token(conf: dict) -> float:
    """Of a token's `moe_topk` picks, those that fall on a feed-forward
    expert held HERE, as the expectation under a level router: 12 x 16 / 768
    = 0.25 at the cell's share. An identity expert multiplies nothing; an
    expert held on another chip is that chip's. The measured counterpart is
    the wave counter `moe_assignments` (experts_here_share.tput)."""
    outputs = conf["n_routed_experts"] + conf["zero_expert_num"]
    return conf["moe_topk"] * conf["experts_held"] / outputs


def flops_per_token(conf: dict, with_head: bool) -> float:
    """Matrix-multiply FLOPs of one token through every double layer as
    this share runs it: two attention sublayers (five projections each), two
    dense feed-forwards at `ffn_hidden_size`, the router over all its
    outputs, and `held_picks_per_token` experts at `expert_ffn_hidden_size`;
    plus the output head over the rows held where the call computes
    logits."""
    d = conf["hidden_size"]
    layer = (2 * _attention_params(conf) + 2 * 3 * d * conf["ffn_hidden_size"]
             + d * (conf["n_routed_experts"] + conf["zero_expert_num"])
             + held_picks_per_token(conf) * 3 * d * conf["expert_ffn_hidden_size"])
    return 2.0 * conf["num_layers"] * layer + (2.0 * d * conf["vocab_size"] if with_head else 0.0)


def attention_flops(conf: dict, queries: float, keys_per_query: float) -> float:
    """Scores and values for `queries` tokens that each see `keys_per_query`
    keys, in the ABSORBED form the program runs in each of its 2 x
    `num_layers` attention sublayers: per head 2 x (kv_lora_rank + dr) for
    the score against the latent and 2 x kv_lora_rank for the latent summed
    (models/configs.py `attn_flops_per_key` is the same count;
    tests/test_benchmark_seam.py holds the two equal)."""
    per_key = 2.0 * (2 * conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
    return 2 * conf["num_layers"] * conf["num_attention_heads"] * per_key * queries * keys_per_query


# ------------------------------------------------------- the grouped-matmul kernels
def grouped_kernel_cost(rows: float, groups_hit: float, k: int, n: int, n_weights: int,
                        out_bytes: int, weight_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one call of `moe_grouped_swiglu` (n_weights 2, bf16
    out) or `moe_grouped_matmul` (n_weights 1, f32 out) needs at this
    configuration's widths ([6144, 2048] and [2048, 6144]): `rows` valid
    rows held here, each against its own expert's [k, n] weights; the
    weights of the `groups_hit` experts that have a row read once; the
    valid rows of x read and of the output written once."""
    flops = 2.0 * rows * k * n * n_weights
    moved = groups_hit * k * n * n_weights * weight_bytes + rows * (k * 2 + n * out_bytes)
    return flops, float(moved)
