"""Hand-tiled Pallas TPU kernel for shared-prefix flash attention.

The XLA cascade path (ops/attention.attend_part on the prefix) materializes
a [B, n_kv, g, Sq, Sp] f32 score tensor per layer — at burst geometry
(16 rows x 512-token suffixes against a ~14k-token cluster-state prefix)
that is ~3 GB of HBM traffic per layer, and it dominates the decision-wave
latency (engine/engine.py _wave_impl). This kernel streams the prefix KV in
blocks with an online softmax instead: the grid walks
(kv_head, query_block, key_block), scores for one (q_block x k_block) tile
live in VMEM only, and a flash accumulator (m, l, acc scratch) folds each
key block into the output. Nothing [.., Sq, Sp]-shaped ever exists.

Emits UNNORMALIZED flash partials (o, m, l) in exactly the shapes
ops/attention.attend_part produces for the prefix part, so the caller
merges them with the in-chunk part via merge_attention_parts — the cascade
semantics (and tests) stay shared with the XLA path. Used by both cascade
callsites: the suffix prefill (models/llama._suffix_layer via
chunk_attention_with_prefix) and the wave block decode
(models/llama.forward_block_decode).

Replaces the remote prefill the reference pays per pod (reference
scheduler.py:425-433) with an in-tree flash kernel on the burst hot path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_llm_scheduler_tpu.ops import pallas_interpret

NEG_INF = -1e30


def _largest_divisor(n: int, cap: int, multiple: int) -> int | None:
    """Largest d <= cap with n % d == 0 and d % multiple == 0."""
    for d in range(min(cap, n), multiple - 1, -1):
        if n % d == 0 and d % multiple == 0:
            return d
    return None


def _prefix_kernel(
    # scalar prefetch
    plen_ref,  # [1] int32 (SMEM) — valid prefix tokens
    # blocked inputs
    q_ref,  # [1, q_block, hd] f32, pre-scaled
    k_ref,  # [1, k_block, hd]
    v_ref,  # [1, k_block, hd]
    # blocked outputs
    o_ref,  # [1, q_block, hd] f32 (unnormalized flash acc)
    m_ref,  # [1, q_block, 128] f32 (running max, lane-broadcast)
    l_ref,  # [1, q_block, 128] f32 (running denom, lane-broadcast)
    # scratch
    m_scr,  # [q_block, 128]
    l_scr,  # [q_block, 128]
    acc_scr,  # [q_block, hd]
):
    kb = pl.program_id(2)
    k_block = k_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = kb * k_block
    valid = plen_ref[0] - start  # prefix tokens inside this key block

    @pl.when(valid > 0)
    def _attend():
        # bf16 operands, f32 accumulation: the MXU's native mode (f32xf32
        # runs at a fraction of the rate). Standard flash practice; the
        # parity tests bound the error.
        q = q_ref[0].astype(jnp.bfloat16)  # [q_block, hd] (scaled by caller)
        k = k_ref[0].astype(jnp.bfloat16)  # [k_block, hd]
        v = v_ref[0].astype(jnp.bfloat16)
        scores = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [q_block, k_block]
        inblk = jax.lax.broadcasted_iota(jnp.int32, (1, k_block), 1) < valid
        scores = jnp.where(inblk, scores, NEG_INF)

        m_prev = m_scr[:, :1]  # [q_block, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)
        probs = jnp.where(inblk, probs, 0.0)  # exp(NEG_INF-NEG_INF)=1 guard

        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(probs, axis=1, keepdims=True),
            l_scr.shape,
        )
        pv = jax.lax.dot_general(
            probs.astype(jnp.bfloat16), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [q_block, hd]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = acc_scr[:]
        m_ref[0] = m_scr[:]
        l_ref[0] = l_scr[:]


def prefix_attention_supported(
    q_shape: tuple[int, ...], n_kv: int, prefix_cap: int, shards: int = 1
) -> bool:
    """Whether the kernel's tiling constraints hold for these static shapes.

    `shards` > 1 checks the PER-SHARD shapes of a shard_map over the
    kv-head axis (heads divided over tp; nq is unchanged since the GQA
    group size survives the division)."""
    B, S, n_heads, hd = q_shape
    if n_heads % shards or n_kv % shards:
        return False
    n_heads //= shards
    n_kv //= shards
    if n_heads % n_kv:
        return False
    nq = B * (n_heads // n_kv) * S  # query rows per kv head
    return (
        _largest_divisor(nq, 2048, 8) is not None
        and _largest_divisor(prefix_cap, 512, 128) is not None
    )


def _causal_kernel(
    # scalar prefetch
    lens_ref,  # [B] int32 (SMEM) — valid kv tokens per row
    # blocked inputs
    q_ref,  # [1, 1, q_block, hd] f32, pre-scaled
    k_ref,  # [1, 1, k_block, hd]
    v_ref,  # [1, 1, k_block, hd]
    # blocked outputs
    o_ref,  # [1, 1, q_block, hd] f32 (unnormalized flash acc)
    m_ref,  # [1, 1, q_block, 128]
    l_ref,  # [1, 1, q_block, 128]
    # scratch
    m_scr,  # [q_block, 128]
    l_scr,  # [q_block, 128]
    acc_scr,  # [q_block, hd]
    *,
    S: int,
    q_block: int,
    k_block: int,
):
    b = pl.program_id(0)
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # q rows are (g, s) flattened with q_block | S, so one block spans one
    # contiguous position range [p0, p0 + q_block) of a single query group.
    p0 = (qb * q_block) % S
    start = kb * k_block
    # contributes iff some kv position < min(lens, causal end)
    limit = jnp.minimum(lens_ref[b], p0 + q_block)

    @pl.when(limit > start)
    def _attend():
        q = q_ref[0, 0].astype(jnp.bfloat16)  # [q_block, hd] (scaled)
        k = k_ref[0, 0].astype(jnp.bfloat16)
        v = v_ref[0, 0].astype(jnp.bfloat16)
        scores = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [q_block, k_block]
        qpos = p0 + jax.lax.broadcasted_iota(jnp.int32, (q_block, 1), 0)
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, k_block), 1)
        mask = (kpos <= qpos) & (kpos < lens_ref[b])
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)
        probs = jnp.where(mask, probs, 0.0)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(probs, axis=1, keepdims=True),
            l_scr.shape,
        )
        pv = jax.lax.dot_general(
            probs.astype(jnp.bfloat16), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kb == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0, 0] = acc_scr[:]
        m_ref[0, 0] = m_scr[:]
        l_ref[0, 0] = l_scr[:]


def causal_attention_supported(
    q_shape: tuple[int, ...], n_kv: int, shards: int = 1
) -> bool:
    B, S, n_heads, hd = q_shape
    if n_heads % shards or n_kv % shards:
        return False
    if (n_heads // shards) % (n_kv // shards):
        return False
    return (
        _largest_divisor(S, 1024, 8) is not None
        and _largest_divisor(S, 1024, 128) is not None
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_causal_attention_parts(  # graftlint: ok[unconstrained-sharding] — single-device pallas kernel: the engine refuses this path on tp>1 meshes, there is nothing for GSPMD to partition
    q: jax.Array,  # [B, S, n_heads, hd] post-RoPE queries (UNscaled)
    k: jax.Array,  # [B, S, n_kv, hd]
    v: jax.Array,
    lens: jax.Array,  # [B] int32 — valid kv tokens per row
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partials of causal self-attention within a chunk.

    Returns (o, m, l) shaped like
    ops.attention.attend_part(qg, k, v, mask, "bqkgh,bskh->bkgqs") —
    [B, n_kv, g, S, hd] f32 and [B, n_kv, g, S] — for
    merge_attention_parts. Upper-triangle key blocks are skipped entirely
    (~2x fewer tiles than a dense mask), and nothing [.., S, S]-shaped is
    materialized — the per-layer in-chunk score block of the chunked
    long-context prefill is ~540 MB at 1B/2048 on the XLA path.
    """
    B, S, n_heads, hd = q.shape
    n_kv = k.shape[2]
    g = n_heads // n_kv
    interpret = pallas_interpret(interpret)
    q_block = _largest_divisor(S, 1024, 8)
    k_block = _largest_divisor(S, 1024, 128)
    if q_block is None or k_block is None:
        raise ValueError(f"unsupported chunk length {S} for flash causal attention")

    # [B, S, n_kv, g, hd] -> [B, n_kv, g, S, hd] -> [B, n_kv, g*S, hd]
    qr = q.reshape(B, S, n_kv, g, hd).transpose(0, 2, 3, 1, 4)
    qr = (qr.astype(jnp.float32) * hd**-0.5).reshape(B, n_kv, g * S, hd)
    kt = k.transpose(0, 2, 1, 3)  # [B, n_kv, S, hd]
    vt = v.transpose(0, 2, 1, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_kv, g * S // q_block, S // k_block),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, hd), lambda b, kv, qb, kb, l_: (b, kv, qb, 0)),
            pl.BlockSpec((1, 1, k_block, hd), lambda b, kv, qb, kb, l_: (b, kv, kb, 0)),
            pl.BlockSpec((1, 1, k_block, hd), lambda b, kv, qb, kb, l_: (b, kv, kb, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, q_block, hd), lambda b, kv, qb, kb, l_: (b, kv, qb, 0)),
            pl.BlockSpec((1, 1, q_block, 128), lambda b, kv, qb, kb, l_: (b, kv, qb, 0)),
            pl.BlockSpec((1, 1, q_block, 128), lambda b, kv, qb, kb, l_: (b, kv, qb, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        functools.partial(_causal_kernel, S=S, q_block=q_block, k_block=k_block),
        name="flash_causal_attention_parts",
        out_shape=(
            jax.ShapeDtypeStruct((B, n_kv, g * S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, g * S, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, g * S, 128), jnp.float32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(lens.astype(jnp.int32), qr, kt, vt)
    o = o.reshape(B, n_kv, g, S, hd)
    m = m[..., 0].reshape(B, n_kv, g, S)
    l = l[..., 0].reshape(B, n_kv, g, S)
    return o, m, l


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_prefix_attention_parts(  # graftlint: ok[unconstrained-sharding] — single-device pallas kernel: the engine refuses this path on tp>1 meshes, there is nothing for GSPMD to partition
    q: jax.Array,  # [B, S, n_heads, hd] post-RoPE queries (UNscaled)
    prefix_k: jax.Array,  # [Sp, n_kv, hd] shared dense prefix KV
    prefix_v: jax.Array,
    prefix_len: jax.Array,  # scalar int32 — valid prefix tokens
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partials of suffix-queries vs the shared prefix.

    Returns (o, m, l) shaped ([B, n_kv, g, S, hd] f32, [B, n_kv, g, S],
    [B, n_kv, g, S]) — bit-compatible with
    ops.attention.attend_part(qg, prefix_k, prefix_v, mask, "bqkgh,skh->bkgqs")
    for merge_attention_parts. A fully-masked prefix (prefix_len == 0)
    reports m = NEG_INF, l = 0 (the merge then weights it to zero).
    """
    B, S, n_heads, hd = q.shape
    Sp, n_kv, _ = prefix_k.shape
    g = n_heads // n_kv
    interpret = pallas_interpret(interpret)

    nq = B * g * S
    q_block = _largest_divisor(nq, 1024, 8)
    k_block = _largest_divisor(Sp, 1024, 128)
    if q_block is None or k_block is None:
        raise ValueError(
            f"unsupported shapes for flash prefix attention: nq={nq}, Sp={Sp}"
        )

    # [B, S, n_kv, g, hd] -> [n_kv, B, g, S, hd] -> [n_kv, nq, hd]
    # (row index = (b*g + gi)*S + s; inverted exactly on the way out)
    qr = q.reshape(B, S, n_kv, g, hd).transpose(2, 0, 3, 1, 4)
    qr = (qr.astype(jnp.float32) * hd**-0.5).reshape(n_kv, nq, hd)
    # kv-head-major KV so key blocks tile (1, k_block, hd) — the Pallas TPU
    # lowering requires the last two block dims divisible by (8, 128) or
    # equal to the array dims. ~tens of MB of relayout vs the GBs of score
    # traffic the kernel eliminates.
    pk_t = prefix_k.transpose(1, 0, 2)  # [n_kv, Sp, hd]
    pv_t = prefix_v.transpose(1, 0, 2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_kv, nq // q_block, Sp // k_block),
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda kv, qb, kb, pl_: (kv, qb, 0)),
            pl.BlockSpec((1, k_block, hd), lambda kv, qb, kb, pl_: (kv, kb, 0)),
            pl.BlockSpec((1, k_block, hd), lambda kv, qb, kb, pl_: (kv, kb, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, q_block, hd), lambda kv, qb, kb, pl_: (kv, qb, 0)),
            pl.BlockSpec((1, q_block, 128), lambda kv, qb, kb, pl_: (kv, qb, 0)),
            pl.BlockSpec((1, q_block, 128), lambda kv, qb, kb, pl_: (kv, qb, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        _prefix_kernel,
        # explicit: the compiled operation, and every event of it in a
        # device trace, is `<name>.<n>`; a trace reader finds it by that
        # stem (benchmark/metrics/prefix_attn_roofline.py KERNEL)
        name="flash_prefix_attention_parts",
        out_shape=(
            jax.ShapeDtypeStruct((n_kv, nq, hd), jnp.float32),
            jax.ShapeDtypeStruct((n_kv, nq, 128), jnp.float32),
            jax.ShapeDtypeStruct((n_kv, nq, 128), jnp.float32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(
        jnp.asarray(prefix_len, dtype=jnp.int32).reshape(1),
        qr, pk_t, pv_t,
    )
    # [n_kv, nq, ...] -> [n_kv, B, g, S, ...] -> [B, n_kv, g, S, ...]
    o = o.reshape(n_kv, B, g, S, hd).transpose(1, 0, 2, 3, 4)
    m = m[:, :, 0].reshape(n_kv, B, g, S).transpose(1, 0, 2, 3)
    l = l[:, :, 0].reshape(n_kv, B, g, S).transpose(1, 0, 2, 3)
    return o, m, l


# ------------------------------------------------- window over the prefix
def _window_kernel(
    # scalar prefetch
    plen_ref,   # [1] int32 (SMEM) — valid prefix tokens
    first_ref,  # [n_qb] int32 (SMEM) — first key block a query block visits
    lo_ref,     # [n_qb] int32 (SMEM) — lowest key any row of the block sees
    # blocked inputs
    q_ref,      # [1, q_block, hd] f32, pre-scaled
    k_ref,      # [1, k_block, hd]
    v_ref,      # [1, k_block, hd]
    row_lo_ref,  # [q_block, 1] int32 — each row's lowest visible key
    # blocked outputs
    o_ref, m_ref, l_ref,
    # scratch
    m_scr, l_scr, acc_scr,
):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    k_block = k_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = (first_ref[qb] + kb) * k_block

    # a block wholly past the prefix or wholly below every row's window
    # holds nothing the block's rows see
    @pl.when((start < plen_ref[0]) & (start + k_block > lo_ref[qb]))
    def _attend():
        q = q_ref[0].astype(jnp.bfloat16)
        k = k_ref[0].astype(jnp.bfloat16)
        v = v_ref[0].astype(jnp.bfloat16)
        scores = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [q_block, k_block]
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, k_block), 1)
        mask = (kpos < plen_ref[0]) & (kpos >= row_lo_ref[...])
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(probs, axis=1, keepdims=True),
            l_scr.shape,
        )
        pv = jax.lax.dot_general(
            probs.astype(jnp.bfloat16), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = acc_scr[:]
        m_ref[0] = m_scr[:]
        l_ref[0] = l_scr[:]


def window_key_blocks(window: int, k_block: int, n_blocks: int) -> int:
    """Key blocks a query block of the windowed kernel visits: the keys its
    rows see lie in [lowest row's bound, prefix_len), at most window - 1 of
    them (every query lies behind the prefix), so they span at most
    ceil((window - 1) / k_block) + 1 blocks."""
    return min(n_blocks, -(-(window - 1) // k_block) + 1)


def window_keys_visited(window: int, prefix_cap: int) -> int:
    """Prefix keys the windowed kernel reads for each query row: the key
    blocks its grid visits, whole, in a prefix buffer of `prefix_cap`."""
    k_block = _largest_divisor(prefix_cap, 1024, 128)
    return window_key_blocks(window, k_block, prefix_cap // k_block) * k_block


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def window_prefix_attention(  # graftlint: ok[unconstrained-sharding] — single-device pallas kernel, as flash_prefix_attention_parts
    q: jax.Array,  # [B, S, n_heads, hd] post-RoPE queries (UNscaled)
    prefix_k: jax.Array,  # [Sp, n_kv, hd] shared dense prefix KV
    prefix_v: jax.Array,
    prefix_len: jax.Array,  # scalar int32 — valid prefix tokens
    key_lo: jax.Array,  # [B, S] int32 — lowest prefix key each query sees
    *,
    window: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """flash_prefix_attention_parts for queries that see a WINDOW of the
    prefix: query (b, s) sees prefix keys key_lo[b, s] <= j < prefix_len.
    The queries lie behind the prefix and see at most `window` positions
    back (key_lo >= prefix_len - window + 1), so a query block's keys lie
    in at most `window_key_blocks` key blocks: the grid walks those alone,
    from the first block its lowest row sees, and never visits (nor reads)
    a key block wholly below the window; the block at the window's edge is
    masked row by row. Same (o, m, l) as the full kernel.

    Its own name, apart from the full kernel's: the full kernel's readers
    (benchmark/metrics/prefix_attn_roofline.py) count every prefix key of
    each of its calls."""
    B, S, n_heads, hd = q.shape
    Sp, n_kv, _ = prefix_k.shape
    g = n_heads // n_kv
    interpret = pallas_interpret(interpret)
    nq = B * g * S
    q_block = _largest_divisor(nq, 1024, 8)
    k_block = _largest_divisor(Sp, 1024, 128)
    if q_block is None or k_block is None:
        raise ValueError(
            f"unsupported shapes for window prefix attention: nq={nq}, Sp={Sp}"
        )
    n_blocks = Sp // k_block
    n_kb = window_key_blocks(window, k_block, n_blocks)
    n_qb = nq // q_block

    qr = q.reshape(B, S, n_kv, g, hd).transpose(2, 0, 3, 1, 4)
    qr = (qr.astype(jnp.float32) * hd**-0.5).reshape(n_kv, nq, hd)
    pk_t = prefix_k.transpose(1, 0, 2)
    pv_t = prefix_v.transpose(1, 0, 2)
    # rows in the kernel's order (b, g, s), as qr's
    row_lo = jnp.broadcast_to(key_lo.astype(jnp.int32)[:, None, :], (B, g, S)).reshape(nq)
    lo = jnp.min(row_lo.reshape(n_qb, q_block), axis=1)
    first = jnp.clip(lo // k_block, 0, n_blocks - n_kb)

    def kv_block(kv, qb, kb, plen, first, lo):
        return (kv, first[qb] + kb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_kv, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda kv, qb, kb, *_: (kv, qb, 0)),
            pl.BlockSpec((1, k_block, hd), kv_block),
            pl.BlockSpec((1, k_block, hd), kv_block),
            pl.BlockSpec((q_block, 1), lambda kv, qb, kb, *_: (qb, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, q_block, hd), lambda kv, qb, kb, *_: (kv, qb, 0)),
            pl.BlockSpec((1, q_block, 128), lambda kv, qb, kb, *_: (kv, qb, 0)),
            pl.BlockSpec((1, q_block, 128), lambda kv, qb, kb, *_: (kv, qb, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        _window_kernel,
        name="window_prefix_attention",
        out_shape=(
            jax.ShapeDtypeStruct((n_kv, nq, hd), jnp.float32),
            jax.ShapeDtypeStruct((n_kv, nq, 128), jnp.float32),
            jax.ShapeDtypeStruct((n_kv, nq, 128), jnp.float32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(
        jnp.asarray(prefix_len, dtype=jnp.int32).reshape(1), first, lo,
        qr, pk_t, pv_t, row_lo[:, None],
    )
    o = o.reshape(n_kv, B, g, S, hd).transpose(1, 0, 2, 3, 4)
    m = m[:, :, 0].reshape(n_kv, B, g, S).transpose(1, 0, 2, 3)
    l = l[:, :, 0].reshape(n_kv, B, g, S).transpose(1, 0, 2, 3)
    return o, m, l


# ------------------------------------------------ tp-sharded (shard_map)
# GSPMD cannot partition a pallas_call, but both kernels are embarrassingly
# parallel over the kv-head axis — exactly the axis Megatron tp shards
# (parallel/sharding.py: wq/wk/wv column-parallel). Wrapping the kernel in
# shard_map over that axis runs one per-shard kernel per device with zero
# collectives; the flash partials come back kv-head-sharded, which is the
# layout merge_attention_parts and the wo row-parallel matmul expect.
# check_vma=False: pallas_call carries no varying-axis rule, and the wrap
# is collective-free by construction.


def flash_prefix_attention_parts_shmap(
    q, prefix_k, prefix_v, prefix_len, mesh, axis: str = "tp", interpret=None
):
    """flash_prefix_attention_parts with heads sharded over `mesh[axis]`."""
    P = jax.sharding.PartitionSpec
    fn = functools.partial(flash_prefix_attention_parts, interpret=interpret)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(None, None, axis, None),  # q [B, S, n_heads, hd]
            P(None, axis, None),        # prefix_k [Sp, n_kv, hd]
            P(None, axis, None),
            P(),                        # prefix_len scalar
        ),
        out_specs=(
            P(None, axis, None, None, None),  # o [B, n_kv, g, S, hd]
            P(None, axis, None, None),        # m [B, n_kv, g, S]
            P(None, axis, None, None),
        ),
        check_vma=False,
    )(q, prefix_k, prefix_v, jnp.asarray(prefix_len, jnp.int32))


def flash_causal_attention_parts_shmap(
    q, k, v, lens, mesh, axis: str = "tp", interpret=None
):
    """flash_causal_attention_parts with heads sharded over `mesh[axis]`."""
    P = jax.sharding.PartitionSpec
    fn = functools.partial(flash_causal_attention_parts, interpret=interpret)
    head_spec = P(None, None, axis, None)  # [B, S, heads, hd]
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(head_spec, head_spec, head_spec, P(None)),
        out_specs=(
            P(None, axis, None, None, None),
            P(None, axis, None, None),
            P(None, axis, None, None),
        ),
        check_vma=False,
    )(q, k, v, lens)
