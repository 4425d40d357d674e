"""Attention ops for prefill and paged decode.

These are the XLA-compiled reference paths; ops/pallas_paged_attention.py
provides the hand-tiled TPU decode kernel behind the same signature. Both
paths are jit-compatible: static shapes, no Python control flow on traced
values (everything masks instead of branching).

Replaces the remote attention the reference rents from the HF-hosted 70B
(reference scheduler.py:425-433) with in-tree compute.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from k8s_llm_scheduler_tpu.ops import pallas_interpret

NEG_INF = -1e30  # large-negative mask value; avoids NaN from -inf * 0

# Default shared-prefix attention implementation: "auto" picks the Pallas
# flash kernel (ops/pallas_prefix_attention.py) on TPU when the shapes meet
# its tiling constraints, else the XLA einsum path. "xla" forces the einsum
# path; "pallas" forces the kernel (interpret-mode on CPU — parity tests).
# The engine passes an AttnImpl instead of a string: it carries the mesh
# for tp-sharded serving (GSPMD cannot partition a pallas_call, so the
# kernel is wrapped in shard_map over the tp-sharded kv-head axis — per
# shard it is embarrassingly parallel, no collectives) and the record of
# what each call site resolved to.
PREFIX_ATTN_IMPL = "auto"


@dataclasses.dataclass(frozen=True)
class AttnImpl:
    """One engine's attention-impl choice, bound into its jitted programs.

    `kind` is the same auto/xla/pallas preference as the string form. On a
    tp-sharded mesh, `mesh`+`axis` let the dispatch wrap the Pallas
    kernels in shard_map over the kv-head axis instead of dropping to
    XLA. `resolved` is written at TRACE time: (call site, q shape, kv
    length) -> the implementation the preference actually resolved to
    there ("xla", or "pallas" with "_shard_map" on a mesh and "_interpret"
    when the kernel runs in the interpreter rather than compiled by
    Mosaic) — the selection is fine, being invisible is not."""

    kind: str = "auto"
    mesh: Mesh | None = None
    axis: str = "tp"
    resolved: dict[tuple, str] = dataclasses.field(
        default_factory=dict, compare=False
    )

    def resolved_counts(self) -> dict[str, dict[str, int]]:
        """{call site: {implementation: traced geometries}} — the form
        get_stats carries: names from two small fixed sets, so the record
        adds a bounded handful of series to /metrics however many wave
        geometries are traced."""
        out: dict[str, dict[str, int]] = {}
        for (site, *_), how in list(self.resolved.items()):
            by_impl = out.setdefault(site, {})
            by_impl[how] = by_impl.get(how, 0) + 1
        return out


def _resolve_impl(impl) -> tuple[str, Mesh | None, str | None, int, dict | None]:
    """Normalize str | AttnImpl | None -> (kind, mesh, axis, shards, record)."""
    if impl is None:
        impl = PREFIX_ATTN_IMPL
    if isinstance(impl, AttnImpl):
        shards = impl.mesh.shape.get(impl.axis, 1) if impl.mesh is not None else 1
        if shards > 1:
            return impl.kind, impl.mesh, impl.axis, shards, impl.resolved
        return impl.kind, None, None, 1, impl.resolved
    return impl, None, None, 1, None


def _note_resolved(record, site, q_shape, kv_len, use_pallas, mesh) -> None:
    if record is None:
        return
    how = "xla"
    if use_pallas:
        how = "pallas"
        if mesh is not None:
            how += "_shard_map"
        if pallas_interpret():
            how += "_interpret"
    record[(site, tuple(q_shape), int(kv_len))] = how


def set_prefix_attn_impl(impl: str) -> None:
    global PREFIX_ATTN_IMPL
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown prefix attention impl {impl!r}")
    PREFIX_ATTN_IMPL = impl


def prefix_attend_parts(q, qg, prefix_k, prefix_v, prefix_len, impl=None, window=None):
    """Flash partials (o, m, l) of queries vs the shared dense prefix.

    `q` is [B, S, n_heads, hd] post-RoPE (kernel layout); `qg` is the same
    queries pre-scaled and grouped [B, S, n_kv, g, hd] (einsum layout) —
    callers already have both, so the dispatch costs nothing. `impl`
    overrides the module default per call site (the engine plumbs its
    per-instance setting through; None falls back to PREFIX_ATTN_IMPL).

    `window` = (size, key_lo [B, S] int32): each query sees the prefix keys
    from key_lo[b, s] on alone, at most `size` positions back
    (window_prefix_attend_parts). None: every valid key.
    """
    if window is not None:
        return window_prefix_attend_parts(q, qg, prefix_k, prefix_v, prefix_len, impl, *window)
    kind, mesh, axis, shards, record = _resolve_impl(impl)
    use_pallas = False
    if kind == "pallas" or (kind == "auto" and jax.default_backend() == "tpu"):
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            prefix_attention_supported,
        )

        # "pallas" forces the kernel wherever the tiling supports it (incl.
        # interpret mode off-TPU — parity tests); unsupported shapes always
        # take the einsum path. On a sharded mesh the check runs on the
        # PER-SHARD shapes (kv heads divided over the tp axis).
        use_pallas = prefix_attention_supported(
            q.shape, prefix_k.shape[1], prefix_k.shape[0], shards=shards
        )
    _note_resolved(record, "prefix", q.shape, prefix_k.shape[0], use_pallas, mesh)
    if use_pallas:
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            flash_prefix_attention_parts,
            flash_prefix_attention_parts_shmap,
        )

        if mesh is not None:
            return flash_prefix_attention_parts_shmap(
                q, prefix_k, prefix_v, prefix_len, mesh, axis
            )
        return flash_prefix_attention_parts(q, prefix_k, prefix_v, prefix_len)
    Sp = prefix_k.shape[0]
    pre_mask = (jnp.arange(Sp) < prefix_len)[None, None, None, None, :]
    return attend_part(qg, prefix_k, prefix_v, pre_mask, "bqkgh,skh->bkgqs")


def window_prefix_attend_parts(q, qg, prefix_k, prefix_v, prefix_len, impl, size: int, key_lo):
    """prefix_attend_parts for queries that each see the prefix keys
    key_lo[b, s] <= j < prefix_len (a window of `size` positions ending at
    the query, which lies behind the prefix): the Pallas kernel that visits
    only the key blocks a window reaches (ops/pallas_prefix_attention.py
    window_prefix_attention), or the einsum with the window in its mask."""
    use_pallas, mesh, record = _window_uses_pallas(q.shape, prefix_k.shape, impl)
    _note_resolved(record, "window_prefix", q.shape, prefix_k.shape[0], use_pallas, mesh)
    if use_pallas:
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            window_prefix_attention,
        )

        return window_prefix_attention(q, prefix_k, prefix_v, prefix_len, key_lo, window=size)
    j = jnp.arange(prefix_k.shape[0])
    mask = (j < prefix_len) & (j >= key_lo[:, :, None])  # [B, S, Sp]
    return attend_part(qg, prefix_k, prefix_v, mask[:, None, None], "bqkgh,skh->bkgqs")


def _window_uses_pallas(q_shape, prefix_shape, impl):
    """(whether window_prefix_attend_parts takes the kernel, mesh, record)."""
    kind, mesh, _axis, _shards, record = _resolve_impl(impl)
    use_pallas = False
    if kind == "pallas" or (kind == "auto" and jax.default_backend() == "tpu"):
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            prefix_attention_supported,
        )

        use_pallas = mesh is None and prefix_attention_supported(
            q_shape, prefix_shape[1], prefix_shape[0]
        )
    return use_pallas, mesh, record


def window_prefix_keys_read(q_shape, prefix_shape, size: int, impl=None) -> int:
    """Prefix keys window_prefix_attend_parts reads for each query row, a
    static count: the key blocks the kernel's grid visits, or the whole
    buffer, which the einsum masks."""
    if _window_uses_pallas(q_shape, prefix_shape, impl)[0]:
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            window_keys_visited,
        )

        return window_keys_visited(size, prefix_shape[0])
    return prefix_shape[0]


def causal_chunk_attend_parts(q, qg, k_chunk, v_chunk, chunk_lens, impl=None):
    """Flash partials (o, m, l) of causal in-chunk self-attention.

    Same dispatch contract as prefix_attend_parts: `q` [B, S, n_heads, hd]
    post-RoPE for the kernel, `qg` the pre-scaled grouped layout for the
    einsum fallback."""
    kind, mesh, axis, shards, record = _resolve_impl(impl)
    use_pallas = False
    if kind == "pallas" or (kind == "auto" and jax.default_backend() == "tpu"):
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            causal_attention_supported,
        )

        use_pallas = causal_attention_supported(
            q.shape, k_chunk.shape[2], shards=shards
        )
    _note_resolved(record, "causal_chunk", q.shape, q.shape[1], use_pallas, mesh)
    if use_pallas:
        from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import (
            flash_causal_attention_parts,
            flash_causal_attention_parts_shmap,
        )

        if mesh is not None:
            return flash_causal_attention_parts_shmap(
                q, k_chunk, v_chunk, chunk_lens, mesh, axis
            )
        return flash_causal_attention_parts(q, k_chunk, v_chunk, chunk_lens)
    S = q.shape[1]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    valid = pos[None, :] < chunk_lens[:, None]
    chunk_mask = causal[None, None, None, :, :] & valid[:, None, None, None, :]
    return attend_part(qg, k_chunk, v_chunk, chunk_mask, "bqkgh,bskh->bkgqs")


def causal_prefill_attention(
    q: jax.Array,  # [B, S, n_heads, head_dim]
    k: jax.Array,  # [B, S, n_kv_heads, head_dim]
    v: jax.Array,  # [B, S, n_kv_heads, head_dim]
    seq_lens: jax.Array,  # [B] valid lengths (padding beyond)
) -> jax.Array:
    """Causal self-attention over a (padded) prompt chunk, GQA-aware.

    One fused einsum chain — XLA tiles this well onto the MXU; bf16 inputs,
    f32 softmax accumulation.
    """
    B, S, n_heads, head_dim = q.shape
    n_kv = k.shape[2]
    q_per_kv = n_heads // n_kv

    # Group heads: [B, S, n_kv, q_per_kv, hd]
    qg = q.reshape(B, S, n_kv, q_per_kv, head_dim)
    scale = head_dim**-0.5
    logits = jnp.einsum(
        "bqkgh,bskh->bkgqs", qg.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )  # [B, n_kv, q_per_kv, S_q, S_kv]

    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]  # [S_q, S_kv]
    valid = pos[None, :] < seq_lens[:, None]  # [B, S_kv]
    mask = causal[None, None, None, :, :] & valid[:, None, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)

    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", weights, v.astype(jnp.float32))
    return out.reshape(B, S, n_heads, head_dim).astype(q.dtype)


def gather_pages(
    cache: jax.Array,  # [num_pages, page_size, n_kv, head_dim]
    page_table: jax.Array,  # [B, max_pages]
) -> jax.Array:
    """Gather each sequence's pages into a contiguous view
    [B, max_pages*page_size, n_kv, head_dim]."""
    gathered = cache[page_table]  # [B, max_pages, page_size, n_kv, hd]
    B, P, psize, n_kv, hd = gathered.shape
    return gathered.reshape(B, P * psize, n_kv, hd)


def merge_attention_parts(parts):
    """Flash-style merge of partial-softmax attention parts.

    Each part is (o, m, l): o = exp(logits - m) @ V (unnormalized output),
    m = rowwise max logit, l = rowwise sum of exp(logits - m). Fully-masked
    parts contribute m = NEG_INF and therefore weight exp(NEG_INF - m*) = 0.
    """
    o_acc, m_acc, l_acc = parts[0]
    for o, m, l in parts[1:]:
        m_new = jnp.maximum(m_acc, m)
        w_acc = jnp.exp(m_acc - m_new)
        w = jnp.exp(m - m_new)
        o_acc = o_acc * w_acc[..., None] + o * w[..., None]
        l_acc = l_acc * w_acc + l * w
        m_acc = m_new
    return o_acc / jnp.maximum(l_acc, 1e-30)[..., None]


def attend_part(q_scaled, k, v, mask, kv_eq):
    """One softmax part: returns (o, m, l) for merge_attention_parts.

    q_scaled: [..., hd] f32 (already scaled); k/v: keys/values; mask selects
    valid kv positions. `kv_eq` is the einsum equation mapping q x k -> logits
    with the kv axis LAST; the output equation is derived by swapping k->v.
    """
    logits = jnp.einsum(kv_eq, q_scaled, k.astype(jnp.float32))
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    # weights @ v over the kv axis (rhs's last letter); o aligns with m/l
    # dims plus a trailing head_dim so merge_attention_parts can broadcast.
    lhs, rhs = kv_eq.split("->")
    k_spec = lhs.split(",")[1]
    o = jnp.einsum(f"{rhs},{k_spec}->{rhs[:-1]}h", p, v.astype(jnp.float32))
    return o, m, l


def write_block(buf: jax.Array, tail: jax.Array, block: jax.Array) -> jax.Array:
    """Append one decode block to a wave's generated-token cache.

    buf [L, R, cap + F, *token] holds row r's earlier tokens at slots
    < tail[r]; block [L, R, F, *token] is what every layer just computed
    for the block, valid tokens left-aligned. Row r's whole F-wide window
    goes to slot tail[r]: one dense copy a row (R is static), once a model
    call, where a per-token scatter in every layer cost 13% of the device
    and held up the layer scan's weight prefetch (PERF.md §6, PR 31).

    The padded positions' values land at and past the row's new tail. No
    mask exposes a slot >= tail, the next block's window starts at the new
    tail and overwrites them, and a finished row's tail never moves. The
    buffer is cap + F long so that a window never overruns its end:
    dynamic_update_slice would move such a window back, onto valid tokens.
    Nothing here knows what a cached token is made of."""
    block = block.astype(buf.dtype)
    zeros = (0,) * (block.ndim - 3)
    for r in range(block.shape[1]):
        buf = jax.lax.dynamic_update_slice(
            buf, block[:, r : r + 1], (0, r, tail[r], *zeros)
        )
    return buf


def chunk_attention_with_prefix(
    q: jax.Array,  # [B, S, n_heads, head_dim] — suffix chunk queries
    k_chunk: jax.Array,  # [B, S, n_kv, head_dim]
    v_chunk: jax.Array,  # [B, S, n_kv, head_dim]
    chunk_lens: jax.Array,  # [B] valid suffix tokens per row
    prefix_k: jax.Array,  # [Sp, n_kv, head_dim] — SHARED dense prefix KV
    prefix_v: jax.Array,  # [Sp, n_kv, head_dim]
    prefix_len: jax.Array,  # scalar — valid prefix tokens
    prefix_impl: str | None = None,  # static — see prefix_attend_parts
) -> jax.Array:
    """Suffix-chunk attention with a shared dense prefix (cascade attention).

    Every suffix token attends to (a) the whole valid prefix — one einsum
    against a batch-free [Sp, ...] buffer, so the prefix KV is read from HBM
    once for the whole batch instead of once per sequence — and (b) causally
    within its own suffix chunk. The two softmax parts merge exactly via the
    log-sum-exp trick. prefix_len == 0 degrades to plain causal attention.

    This is the TPU-first answer to the burst-shared cluster-state prompt
    (core/prompt.py cluster_prefix; reference cache-key equivalence,
    reference scheduler.py:265-271): shared tokens become a dense MXU matmul
    instead of per-sequence paged gathers.
    """
    B, S, n_heads, head_dim = q.shape
    n_kv = k_chunk.shape[2]
    q_per_kv = n_heads // n_kv
    qg = (q.astype(jnp.float32) * head_dim**-0.5).reshape(B, S, n_kv, q_per_kv, head_dim)

    o_p, m_p, l_p = prefix_attend_parts(
        q, qg, prefix_k, prefix_v, prefix_len, impl=prefix_impl
    )  # o: [B, n_kv, g, S_q, hd]

    o_c, m_c, l_c = causal_chunk_attend_parts(
        q, qg, k_chunk, v_chunk, chunk_lens, impl=prefix_impl
    )

    out = merge_attention_parts([(o_p, m_p, l_p), (o_c, m_c, l_c)])  # [B,n_kv,g,S,hd]
    out = jnp.moveaxis(out, 3, 1)  # [B, S, n_kv, g, hd]
    return out.reshape(B, S, n_heads, head_dim).astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,  # [B, n_heads, head_dim] — one new token per sequence
    k_cache: jax.Array,  # [num_pages, page_size, n_kv, head_dim]
    v_cache: jax.Array,  # [num_pages, page_size, n_kv, head_dim]
    page_table: jax.Array,  # [B, max_pages] page ids per sequence
    seq_lens: jax.Array,  # [B] length INCLUDING the new token
) -> jax.Array:
    """One decode step of attention against the paged KV cache.

    The new token's K/V must already be scattered into the cache (the model
    layer does that before calling). XLA path: gather pages then masked
    attention. The Pallas kernel version streams pages without
    materializing the gather.
    """
    B, n_heads, head_dim = q.shape
    n_kv = k_cache.shape[2]
    q_per_kv = n_heads // n_kv

    k = gather_pages(k_cache, page_table)  # [B, L, n_kv, hd]
    v = gather_pages(v_cache, page_table)
    L = k.shape[1]

    qg = q.reshape(B, n_kv, q_per_kv, head_dim)
    scale = head_dim**-0.5
    logits = jnp.einsum(
        "bkgh,blkh->bkgl", qg.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )  # [B, n_kv, q_per_kv, L]

    valid = jnp.arange(L)[None, :] < seq_lens[:, None]  # [B, L]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)

    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgl,blkh->bkgh", weights, v.astype(jnp.float32))
    return out.reshape(B, n_heads, head_dim).astype(q.dtype)
