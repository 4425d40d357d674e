"""Window attention and global attention without position encoding, three
layers to one, each beside a sparse-expert feed-forward with four averaged
shared experts in a parallel block (`cohere2_moe`: Command A+ 218B-A25B),
one chip's share of an expert-parallel deployment, on the program's side of
the seam. arch/README.md says what a file here owes the harness. The counts
follow the program (k8s_llm_scheduler_tpu/models/cohere2_moe.py): what a
token needs as it is run THERE, on this share, never more.
"""

from __future__ import annotations


def register(conf: dict) -> str:
    """Register the configuration file's sizes with the program's model
    registry (models/configs.py is not edited) and return its name. A
    program without the config type (a parent of the PR that brought it)
    stops here with an ImportError, before anything is built. The file's
    `derived` keys, which the published config does not have, join `conf`
    here: the grouped-kernel readers read the expert width as
    `moe_intermediate_size`."""
    from k8s_llm_scheduler_tpu.models import configs
    from k8s_llm_scheduler_tpu.models.configs import Cohere2MoeConfig

    for key, value in conf.get("derived", {}).items():
        conf.setdefault(key, value)

    if conf["torch_dtype"] != "bfloat16" or conf["position_embedding_type"] != "rope_gptj":
        raise ValueError(f"{conf['name']}: only bf16 models with rotary window layers run through "
                         f"Cohere2MoeConfig")
    cfg = Cohere2MoeConfig.from_hf(
        conf["name"], conf, expert_first=conf["expert_first"], expert_count=conf["experts_held"])
    configs._REGISTRY[cfg.name] = cfg
    return cfg.name


# ------------------------------------------------------------ what a token needs
def _layer_counts(conf: dict) -> tuple[int, int]:
    """(window layers, global layers) of the layers run."""
    types = conf["layer_types"][: conf["num_hidden_layers"]]
    return types.count("sliding_attention"), types.count("full_attention")


def _attention_params(conf: dict) -> int:
    """Matrix parameters of one attention: W_q, W_k, W_v, W_o."""
    d, h, hkv, hd = (conf["hidden_size"], conf["num_attention_heads"], conf["num_key_value_heads"],
                     conf["head_dim"])
    return 2 * d * h * hd + 2 * d * hkv * hd


def held_picks_per_token(conf: dict) -> float:
    """Of a token's `num_experts_per_tok` picks, those that fall on an
    expert held HERE, as the expectation under a level router: 8 x 16 / 128
    = 1 at the cell's share. The measured counterpart is the wave counter
    `moe_assignments`."""
    return conf["num_experts_per_tok"] * conf["experts_held"] / conf["num_experts"]


def flops_per_token(conf: dict, with_head: bool) -> float:
    """Matrix-multiply FLOPs of one token through every layer as this share
    runs it: the attention's four projections, the router over all its
    outputs, `held_picks_per_token` experts and the four shared experts, all
    at `intermediate_size`; plus the tied head over the rows held where the
    call computes logits."""
    d, fe = conf["hidden_size"], conf["intermediate_size"]
    layer = (_attention_params(conf) + d * conf["num_experts"]
             + (held_picks_per_token(conf) + conf["num_shared_experts"]) * 3 * d * fe)
    return 2.0 * conf["num_hidden_layers"] * layer + (2.0 * d * conf["vocab_size"] if with_head else 0.0)


def attention_flops(conf: dict, queries: float, keys_per_query: float) -> float:
    """Scores and values for `queries` tokens that each see `keys_per_query`
    keys: 2 x 2 x head_dim a query head a key in every global layer, and in
    every window layer for at most `sliding_window` of them. The contract
    hands the MEAN keys of a run of queries (harness/flops.py: a prefix
    prefill's mean of p over its positions, a wave's prefix + half its
    tail); min(mean, W) stands for the mean of min(p, W) and equals it while
    every query of the run lies on one side of W, as a wave's all do behind
    a ~10k-token prefix (each window query sees W) and as the chunks of a
    prefix prefill on the 2,048 grid do. A run whose positions straddle W
    (a chunk resumed off the grid from an LCP seed) is where it reads above
    the sum, by at most a chunk's spread about W."""
    n_window, n_global = _layer_counts(conf)
    per_key = 4.0 * conf["num_attention_heads"] * conf["head_dim"] * queries
    return per_key * (n_global * keys_per_query + n_window * min(keys_per_query, conf["sliding_window"]))


# ------------------------------------------------------- the grouped-matmul kernels
def grouped_kernel_cost(rows: float, groups_hit: float, k: int, n: int, n_weights: int,
                        out_bytes: int, weight_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one call of `moe_grouped_swiglu` (n_weights 2, bf16
    out) or `moe_grouped_matmul` (n_weights 1, f32 out) needs at this
    configuration's widths ([4096, 4096] both ways): `rows` valid rows held
    here, each against its own expert's [k, n] weights; the weights of the
    `groups_hit` experts that have a row read once; the valid rows of x read
    and of the output written once."""
    flops = 2.0 * rows * k * n * n_weights
    moved = groups_hit * k * n * n_weights * weight_bytes + rows * (k * 2 + n * out_bytes)
    return flops, float(moved)
