"""Hand-tiled Pallas TPU kernel for paged decode attention.

The XLA reference path (ops/attention.paged_decode_attention) materializes a
[B, max_pages*page_size, n_kv, hd] gather of every sequence's pages before
attending — an extra HBM round trip of the whole working set per decode
step. This kernel streams pages instead: the grid walks (sequence, page),
the page id comes from a SCALAR-PREFETCHED page table so Pallas can issue
the HBM->VMEM DMA for exactly the page each program needs (BlockSpec
index_map over the prefetch ref), and a flash-style running softmax
(m, l, acc scratch in VMEM) folds each page into the output without ever
materializing the gathered KV.

Semantics match paged_decode_attention exactly (same masking, GQA
handling, f32 accumulation); tests/test_pallas_attention.py asserts
equivalence against the XLA path. On the cpu backend the kernel runs in
interpreter mode (ops.pallas_interpret), so the hermetic CPU test suite
exercises the same kernel body Mosaic compiles on the chip.

Replaces the remote attention the reference rents from the HF-hosted 70B
(reference scheduler.py:425-433) with an in-tree kernel on the hot decode
loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_llm_scheduler_tpu.ops import pallas_interpret

NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch
    page_table_ref,  # [B, max_pages] int32 (SMEM)
    seq_lens_ref,    # [B] int32 (SMEM)
    # blocked inputs
    q_ref,   # [1, n_heads, hd]
    k_ref,   # [1, page_size, n_kv, hd] — the page this program attends to
    v_ref,   # [1, page_size, n_kv, hd]
    # blocked output(s): normalized [1, n_heads, hd], or with
    # normalize=False the flash partials (acc, m, l) for cascade merging
    *out_refs,
    normalize: bool,
):
    if normalize:
        (o_ref,), (m_scr, l_scr, acc_scr) = out_refs[:1], out_refs[1:]
    else:
        (acc_ref, m_ref, l_ref), (m_scr, l_scr, acc_scr) = (
            out_refs[:3],
            out_refs[3:],
        )
    b = pl.program_id(0)
    p = pl.program_id(1)
    page_size = k_ref.shape[1]

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    seq_len = seq_lens_ref[b]
    start = p * page_size
    valid = seq_len - start  # tokens of this page inside the sequence

    @pl.when(valid > 0)
    def _attend():
        q = q_ref[0].astype(jnp.float32)  # [n_heads, hd]
        k = k_ref[0].astype(jnp.float32)  # [ps, n_kv, hd]
        v = v_ref[0].astype(jnp.float32)
        n_heads, hd = q.shape
        n_kv = k.shape[1]
        q_per_kv = n_heads // n_kv

        # GQA via a static per-KV-head loop of 2D matmuls (Mosaic lowers 2D
        # dot_general onto the MXU; 3D batched contractions don't lower).
        # Query head ordering matches the XLA path's reshape(n_kv, q_per_kv).
        scale = hd**-0.5
        score_blocks = []
        for kv in range(n_kv):
            q_blk = q[kv * q_per_kv : (kv + 1) * q_per_kv] * scale  # [qpk, hd]
            k_blk = k[:, kv, :]  # [ps, hd]
            score_blocks.append(
                jax.lax.dot_general(
                    q_blk, k_blk,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [qpk, ps]
            )
        scores = jnp.concatenate(score_blocks, axis=0)  # [n_heads, ps]

        inpage = jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1) < valid
        scores = jnp.where(inpage, scores, NEG_INF)

        m_prev = m_scr[:, :1]  # [n_heads, 1]
        m_page = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_page)
        alpha = jnp.exp(m_prev - m_new)  # rescale old accumulators
        probs = jnp.exp(scores - m_new)  # [n_heads, ps]
        probs = jnp.where(inpage, probs, 0.0)

        l_new = l_scr[:, :1] * alpha + jnp.sum(probs, axis=1, keepdims=True)
        pv_blocks = []
        for kv in range(n_kv):
            p_blk = probs[kv * q_per_kv : (kv + 1) * q_per_kv]  # [qpk, ps]
            v_blk = v[:, kv, :]  # [ps, hd]
            pv_blocks.append(
                jax.lax.dot_general(
                    p_blk, v_blk,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [qpk, hd]
            )
        pv = jnp.concatenate(pv_blocks, axis=0)  # [n_heads, hd]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        if normalize:
            denom = jnp.maximum(l_scr[:, :1], 1e-30)
            o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        else:
            acc_ref[0] = acc_scr[:]
            m_ref[0] = m_scr[:]
            l_ref[0] = l_scr[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(  # graftlint: ok[unconstrained-sharding] — single-device pallas kernel: the engine refuses this path on tp>1 meshes, there is nothing for GSPMD to partition
    q: jax.Array,  # [B, n_heads, head_dim] — one new token per sequence
    k_cache: jax.Array,  # [num_pages, page_size, n_kv, head_dim]
    v_cache: jax.Array,
    page_table: jax.Array,  # [B, max_pages] page ids per sequence
    seq_lens: jax.Array,  # [B] length INCLUDING the new token
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Drop-in Pallas replacement for ops.attention.paged_decode_attention.

    Streams each sequence's pages HBM->VMEM via scalar-prefetched page ids
    and merges them with an on-chip flash accumulator — no gathered
    [B, max_pages*page_size, ...] intermediate.
    """
    return _paged_call(
        q, k_cache, v_cache, page_table, seq_lens,
        normalize=True, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_parts(  # graftlint: ok[unconstrained-sharding] — single-device pallas kernel: the engine refuses this path on tp>1 meshes, there is nothing for GSPMD to partition
    q: jax.Array,  # [B, n_heads, head_dim]
    k_cache: jax.Array,  # [num_pages, page_size, n_kv, head_dim]
    v_cache: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B] valid tokens in the paged region
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash PARTIALS over the paged region: (o, m, l) shaped
    ([B, n_kv, g, hd], [B, n_kv, g], [B, n_kv, g]) for
    ops.attention.merge_attention_parts — this is how the kernel joins the
    cascade (dense shared prefix | paged own tokens | chunk buffer) inside
    the engine's chunked decode without a materialized page gather.
    A fully-masked region (seq_len 0) reports m = NEG_INF, weight 0."""
    B, n_heads, head_dim = q.shape
    n_kv = k_cache.shape[2]
    g = n_heads // n_kv
    acc, m, l = _paged_call(
        q, k_cache, v_cache, page_table, seq_lens,
        normalize=False, interpret=interpret,
    )
    o = acc.reshape(B, n_kv, g, head_dim)
    return o, m[:, :, 0].reshape(B, n_kv, g), l[:, :, 0].reshape(B, n_kv, g)


def paged_decode_attention_parts_shmap(
    q, k_cache, v_cache, page_table, seq_lens, mesh, axis: str = "tp",
    interpret=None,
):
    """paged_decode_attention_parts with kv heads sharded over `mesh[axis]`.

    The paged KV cache shards its kv-head dim over tp
    (parallel/sharding.kv_cache_spec); page tables and seq lens replicate.
    Per shard the kernel is unchanged and collective-free, so shard_map is
    a pure layout wrapper (check_vma=False: pallas_call has no varying-axis
    rule)."""
    P = jax.sharding.PartitionSpec
    fn = functools.partial(paged_decode_attention_parts, interpret=interpret)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(None, axis, None),        # q [B, n_heads, hd]
            P(None, None, axis, None),  # caches [pages, ps, n_kv, hd]
            P(None, None, axis, None),
            P(None, None),              # page_table [B, max_pages]
            P(None),                    # seq_lens [B]
        ),
        out_specs=(
            P(None, axis, None, None),  # o [B, n_kv, g, hd]
            P(None, axis, None),        # m [B, n_kv, g]
            P(None, axis, None),
        ),
        check_vma=False,
    )(q, k_cache, v_cache, page_table, seq_lens)


def _paged_call(q, k_cache, v_cache, page_table, seq_lens, *, normalize, interpret):
    B, n_heads, head_dim = q.shape
    num_pages, page_size, n_kv, _ = k_cache.shape
    max_pages = page_table.shape[1]
    interpret = pallas_interpret(interpret)

    if normalize:
        out_shape = jax.ShapeDtypeStruct((B, n_heads, head_dim), q.dtype)
        out_specs = pl.BlockSpec(
            (1, n_heads, head_dim), lambda b, p, pt, sl: (b, 0, 0)
        )
    else:
        out_shape = (
            jax.ShapeDtypeStruct((B, n_heads, head_dim), jnp.float32),
            jax.ShapeDtypeStruct((B, n_heads, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, n_heads, 128), jnp.float32),
        )
        out_specs = (
            pl.BlockSpec((1, n_heads, head_dim), lambda b, p, pt, sl: (b, 0, 0)),
            pl.BlockSpec((1, n_heads, 128), lambda b, p, pt, sl: (b, 0, 0)),
            pl.BlockSpec((1, n_heads, 128), lambda b, p, pt, sl: (b, 0, 0)),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec(
                (1, n_heads, head_dim), lambda b, p, pt, sl: (b, 0, 0)
            ),
            pl.BlockSpec(
                (1, page_size, n_kv, head_dim),
                lambda b, p, pt, sl: (pt[b, p], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, page_size, n_kv, head_dim),
                lambda b, p, pt, sl: (pt[b, p], 0, 0, 0),
            ),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, head_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, normalize=normalize),
        name=(
            "paged_decode_attention_pallas" if normalize
            else "paged_decode_attention_parts"
        ),
        out_shape=out_shape,
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32), q, k_cache, v_cache)
