"""What the windowed prefix kernel's reader needs (window_attn_roofline.py):
the prefix keys a wave's queries see through a window, and the kernel's
operations and bytes over those keys alone. Kept here, beside the reader:
harness/flops.py `prefix_kernel_cost` counts every prefix key of a call, as
the full kernel reads them.

A query `o` tokens behind a prefix of P tokens sees the prefix keys from P +
o - W + 1 on: min(P, W - 1 - o) of them. A wave's queries are its rows'
suffix tokens and served tokens, at offsets 0 .. s + d - 1.
"""

from __future__ import annotations

import re

# the kernel's first result, o [n_kv, query rows, head width] f32
SHAPE = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


def _offsets(wave) -> list[int]:
    out = []
    for suffix, served in zip(wave["prompts"], wave.get("served", [])):
        out.extend(range(len(suffix) + len(served)))
    return out


def prefix_keys_per_query(waves, window: int) -> float:
    """Mean prefix keys a wave query sees, over the waves' queries."""
    seen = n = 0
    for wave in waves:
        plen = len(wave["prefix_ids"])
        for o in _offsets(wave):
            seen += max(min(plen, window - 1 - o), 0)
            n += 1
    return seen / n if n else 0.0


def prefix_keys_read(waves, window: int) -> float:
    """Mean prefix keys a call has to read once a KV head: those its first
    query sees (every later query sees a part of them)."""
    spans = [min(len(w["prefix_ids"]), window - 1) for w in waves]
    return sum(spans) / len(spans) if spans else 0.0


def window_kernel_cost(n_kv: int, rows: int, head_dim: int, keys: float, span: float,
                       kv_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one call of the windowed kernel needs: `rows` query
    rows a KV head, each against `keys` prefix keys (scores and values, 2 x
    2 x head_dim a key); K and V of the `span` keys in the window read once
    a KV head; q read and (o, m, l) written in f32 (m and l lane-broadcast
    to 128, as the kernel emits them)."""
    flops = 4.0 * n_kv * rows * keys * head_dim
    kv = 2.0 * n_kv * span * head_dim * kv_bytes
    q_io = n_kv * rows * (2 * head_dim + 2 * 128) * 4.0
    return flops, kv + q_io
