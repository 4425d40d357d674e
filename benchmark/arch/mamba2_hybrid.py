"""Mamba-2 state-space mixers and softmax attention without position
encoding, nine layers to one, over dense SwiGLUs (`granitemoehybrid` with
no experts: Granite 4.0-H Micro), the whole model on one chip, on the
program's side of the seam. arch/README.md says what a file here owes the
harness. The counts follow the program
(k8s_llm_scheduler_tpu/models/mamba2_hybrid.py): what a token needs as it is
run THERE, never more.
"""

from __future__ import annotations


def register(conf: dict) -> str:
    """Register the configuration file's sizes with the program's model
    registry (models/configs.py is not edited) and return its name. A
    program without the config type (a parent of the PR that brought it)
    stops here with an ImportError, before anything is built."""
    from k8s_llm_scheduler_tpu.models import configs
    from k8s_llm_scheduler_tpu.models.configs import Mamba2HybridConfig

    if (conf["torch_dtype"] != "bfloat16" or conf["hidden_act"] != "silu" or conf["attention_bias"]
            or conf["normalization_function"] != "rmsnorm"):
        raise ValueError(f"{conf['name']}: only bias-free bf16 SwiGLU models with RMS norms run through "
                         f"Mamba2HybridConfig")
    cfg = Mamba2HybridConfig.from_hf(conf["name"], conf)
    configs._REGISTRY[cfg.name] = cfg
    return cfg.name


# ------------------------------------------------------------ what a token needs
def _counts(conf: dict) -> tuple[int, int]:
    """(Mamba-2 layers, attention layers)."""
    types = conf["layer_types"]
    return types.count("mamba"), types.count("attention")


def _ssm_params(conf: dict) -> int:
    """Matrix parameters of one Mamba-2 mixer: W_in ([z | x B C | dt]) and
    W_out."""
    d, inner = conf["hidden_size"], conf["mamba_n_heads"] * conf["mamba_d_head"]
    conv = inner + 2 * conf["mamba_n_groups"] * conf["mamba_d_state"]
    return d * (inner + conv + conf["mamba_n_heads"]) + inner * d


def _attention_params(conf: dict) -> int:
    """Matrix parameters of one attention: W_q, W_k, W_v, W_o."""
    d, h, hkv = conf["hidden_size"], conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // h
    return 2 * d * h * hd + 2 * d * hkv * hd


def ssm_state_flops_per_token(conf: dict) -> float:
    """What a token costs ONE Mamba-2 layer beside its projections, COUNTED
    PER TOKEN AS THE RECURRENCE STATES IT: the update x B^T and the
    read-out S C, 2 x head_dim x d_state each a head (the decay is
    elementwise). The chunked form the program runs spends more: the T x T
    products of a chunk are the price of reading the state once a call, not
    work the model needs."""
    return 2 * 2.0 * conf["mamba_n_heads"] * conf["mamba_d_head"] * conf["mamba_d_state"]


def flops_per_token(conf: dict, with_head: bool) -> float:
    """Matrix-multiply FLOPs of one token through every layer: a Mamba-2
    mixer's projections and its state products (a layer with a fixed-size
    state counts here, arch/README.md) in nine layers of ten, the
    attention's projections in the tenth; every layer's SwiGLU at
    `shared_intermediate_size`; plus the tied head over every row where the
    call computes logits."""
    d = conf["hidden_size"]
    n_ssm, n_attn = _counts(conf)
    per_token = (2.0 * (n_ssm * _ssm_params(conf) + n_attn * _attention_params(conf)
                        + (n_ssm + n_attn) * 3 * d * conf["shared_intermediate_size"])
                 + n_ssm * ssm_state_flops_per_token(conf))
    return per_token + (2.0 * d * conf["vocab_size"] if with_head else 0.0)


def attention_flops(conf: dict, queries: float, keys_per_query: float) -> float:
    """Scores and values for `queries` tokens that each see `keys_per_query`
    keys, in the layers that attend ALONE: 2 x 2 x head_dim a query head a
    key (models/configs.py `attn_flops_per_key` is the same count;
    tests/test_benchmark_seam.py holds the two equal)."""
    _, n_attn = _counts(conf)
    hd = conf["hidden_size"] // conf["num_attention_heads"]
    return 4.0 * n_attn * conf["num_attention_heads"] * hd * queries * keys_per_query


# ------------------------------------------------------------- the scan's kernel
def ssd_kernel_cost(rows: float, positions: float, chunk: int, conf: dict) -> tuple[float, float]:
    """(FLOPs, bytes) one call of `ssd_chunk_scan` needs in ONE Mamba-2
    layer: `rows` rows, each over `positions` valid positions in chunks of
    `chunk` (a row's chunk of C B^T and its product with dt x are counted
    over its valid positions, as a chunk with none is passed over). FLOPs:
    per position and head the read-out C S_0^T and the update's x^T B (2 P N
    each), per position the intra-chunk (tril * C B^T)(dt x), 2 x chunk x P a
    head over the chunk's lower half, and C B^T once for the heads, 2 x chunk
    x N over half a chunk. Bytes: one read and one write of the row's state
    (H x P x N float32), x and y (H x P float32 a position each way) and B,
    C, dt and the decay (2 N + 3 H float32 a position)."""
    H, P, N = conf["mamba_n_heads"], conf["mamba_d_head"], conf["mamba_d_state"]
    tokens = rows * positions
    flops = tokens * (H * (2 * 2.0 * P * N + 2.0 * chunk * P / 2) + 2.0 * chunk * N / 2)
    moved = rows * 2 * H * P * N * 4 + tokens * (2 * H * P + 2 * N + 3 * H) * 4
    return float(flops), float(moved)
