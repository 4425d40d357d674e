"""The dense grouped-query decoder (RMSNorm, rotary, GQA, SwiGLU, no biases,
untied head: InternLM2.5) on the program's side of the seam. arch/README.md
says what a file here owes the harness.
"""

from __future__ import annotations


def register(conf: dict) -> str:
    """Register the configuration file's sizes with the program's model
    registry (models/configs.py is not edited) and return its name."""
    import jax.numpy as jnp

    from k8s_llm_scheduler_tpu.models import configs

    if conf["torch_dtype"] != "bfloat16" or conf["hidden_act"] != "silu" or conf["bias"]:
        raise ValueError(f"{conf['name']}: only bias-free bf16 SwiGLU models run through LlamaConfig")
    if conf["head_dim"] * conf["num_attention_heads"] != conf["hidden_size"]:
        raise ValueError(f"{conf['name']}: head_dim x heads != hidden_size")
    cfg = configs.LlamaConfig(
        name=conf["name"], vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_ff=conf["intermediate_size"],
        max_seq_len=conf["max_position_embeddings"], rope_theta=conf["rope_theta"],
        rope_scaling=None, rms_eps=conf["rms_norm_eps"], dtype=jnp.bfloat16,
        tie_embeddings=conf["tie_word_embeddings"],
    )
    configs._REGISTRY[cfg.name] = cfg
    return cfg.name


def flops_per_token(conf: dict, with_head: bool) -> float:
    """Matrix-multiply FLOPs of one token through every layer (q, k, v, o,
    gate, up, down), plus the output head where the call computes logits
    for it."""
    h, hd = conf["hidden_size"], conf["head_dim"]
    nq, nkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    ff, layers = conf["intermediate_size"], conf["num_hidden_layers"]
    per_layer = 2 * h * (nq * hd) + 2 * 2 * h * (nkv * hd) + 2 * (nq * hd) * h + 3 * 2 * h * ff
    return layers * per_layer + (2 * h * conf["vocab_size"] if with_head else 0)


def attention_flops(conf: dict, queries: float, keys_per_query: float) -> float:
    """QK^T and PV for `queries` tokens that each see `keys_per_query` keys."""
    return (conf["num_hidden_layers"] * conf["num_attention_heads"]
            * 4 * conf["head_dim"] * queries * keys_per_query)
