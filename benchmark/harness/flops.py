"""Operations and bytes the algorithm needs, from shapes. The yardstick for
`model_mfu` and `prefix_attn_roofline`: kept here, where no later PR can
change it. The arithmetic follows observability/profiler.py's per-call
counts (2 FLOPs a multiply-add; attention counted over the keys a query may
see), written out again so that nothing of the program is imported.
"""

from __future__ import annotations


def dense_flops_per_token(conf: dict, with_head: bool) -> float:
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


def prefix_prefill_flops(conf: dict, new_tokens: int, total_tokens: int) -> float:
    """Prefilling `new_tokens` at the end of a prefix of `total_tokens`
    (the reused head is not recomputed): causal, so a new token at position
    p sees p keys."""
    first = total_tokens - new_tokens
    mean_keys = first + (new_tokens + 1) / 2
    return (new_tokens * dense_flops_per_token(conf, with_head=False)
            + attention_flops(conf, new_tokens, mean_keys))


def wave_flops(conf: dict, suffix_lens: list[int], served_lens: list[int],
               prefix_len: int) -> float:
    """One decision wave over its real rows: suffix prefill against the
    prefix (logits for each row's last token only), then every served token
    through the model once with logits. Padding rows, padding tokens and
    the block's unused width are not work the model needs."""
    total = 0.0
    for s, d in zip(suffix_lens, served_lens):
        total += s * dense_flops_per_token(conf, with_head=False)
        total += attention_flops(conf, s, prefix_len + (s + 1) / 2)
        total += 2 * conf["hidden_size"] * conf["vocab_size"]
        total += d * dense_flops_per_token(conf, with_head=True)
        total += attention_flops(conf, d, prefix_len + s + (d + 1) / 2)
    return total


def prefix_kernel_cost(n_kv: int, queries_per_kv: int, head_dim: int, prefix_len: int,
                       kv_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one call of the flash-prefix kernel needs: every
    query row against `prefix_len` keys and values, K and V read once per
    KV head, q read and (o, m, l) written in f32 (m and l lane-broadcast to
    128, as the kernel emits them)."""
    flops = 4.0 * n_kv * queries_per_kv * prefix_len * head_dim
    kv = 2.0 * n_kv * prefix_len * head_dim * kv_bytes
    q_io = n_kv * queries_per_kv * (2 * head_dim + 2 * 128) * 4.0
    return flops, kv + q_io
