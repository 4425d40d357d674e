"""Operations and bytes the algorithm needs, from shapes. The yardstick for
`model_mfu` and `prefix_attn_roofline`: kept here, where no later PR can
change it. The arithmetic follows observability/profiler.py's per-call
counts (2 FLOPs a multiply-add; attention counted over the keys a query may
see), written out again so that nothing of the program is imported. What
one token needs is the architecture's to say (arch/<architecture>.py
`flops_per_token`, `attention_flops`); what a wave and a prefix prefill
are made of is said here, once for all of them.
"""

from __future__ import annotations

from harness import seam


def prefix_prefill_flops(conf: dict, new_tokens: int, total_tokens: int) -> float:
    """Prefilling `new_tokens` at the end of a prefix of `total_tokens`
    (the reused head is not recomputed): causal, so a new token at position
    p sees p keys."""
    arch = seam.program(conf)
    first = total_tokens - new_tokens
    mean_keys = first + (new_tokens + 1) / 2
    return (new_tokens * arch.flops_per_token(conf, with_head=False)
            + arch.attention_flops(conf, new_tokens, mean_keys))


def wave_flops(conf: dict, suffix_lens: list[int], served_lens: list[int],
               prefix_len: int) -> float:
    """One decision wave over its real rows: suffix prefill against the
    prefix (logits for each row's last token only), then every served token
    through the model once with logits. Padding rows, padding tokens and
    the block's unused width are not work the model needs."""
    arch = seam.program(conf)
    body, whole = arch.flops_per_token(conf, with_head=False), arch.flops_per_token(conf, with_head=True)
    total = 0.0
    for s, d in zip(suffix_lens, served_lens):
        total += s * body
        total += arch.attention_flops(conf, s, prefix_len + (s + 1) / 2)
        total += whole - body  # the head, for the row's last suffix token
        total += d * whole
        total += arch.attention_flops(conf, d, prefix_len + s + (d + 1) / 2)
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
