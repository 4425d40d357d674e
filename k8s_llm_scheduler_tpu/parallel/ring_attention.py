"""Ring attention: causal self-attention with the sequence sharded over a
mesh axis.

Long-context prefill support (SURVEY §5 long-context axis; BASELINE config 5
stresses an ~8k-token 256-node prompt — this module is what lets the same
design scale far beyond that). Each device holds one sequence chunk of
Q/K/V; K/V chunks rotate around the ring via `ppermute` while attention
accumulates blockwise with the streaming-softmax (log-sum-exp) correction,
so no device ever materializes the full [S, S] score matrix and the
communication pattern rides ICI neighbor links.

Pure-JAX implementation (einsum + fori_loop under shard_map) — XLA overlaps
the ppermute with the block computation. GQA-aware like ops/attention.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from k8s_llm_scheduler_tpu.ops.attention import NEG_INF


def _block_attn(q, k, v, q_pos, k_pos, scale, k_valid=None):
    """One (q-chunk x k-chunk) block: masked logits, local max/sum stats.

    q: [B, Sq, n_kv, g, hd]; k/v: [B, Sk, n_kv, hd]; k_valid: [B, Sk] bool
    per-row key validity (padding mask), None = all valid.
    Returns (num [B,Sq,n_kv,g,hd], den [B,Sq,n_kv,g], mx [B,Sq,n_kv,g]).
    """
    logits = jnp.einsum(
        "bqkgh,bskh->bqkgs", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    mask = (q_pos[:, None] >= k_pos[None, :])[None, :, None, None, :]  # causal
    if k_valid is not None:
        mask = mask & k_valid[:, None, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    mx = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - mx[..., None])
    # exp(NEG_INF - NEG_INF) = 1 on fully-masked rows — zero them so a
    # padded-out row reports den 0 (weight 0) instead of garbage mass.
    p = jnp.where(mask, p, 0.0)
    den = jnp.sum(p, axis=-1)
    num = jnp.einsum("bqkgs,bskh->bqkgh", p, v.astype(jnp.float32))
    return num, den, mx


def ring_self_attention(
    q: jax.Array,  # [B, S_local, n_heads, head_dim] — local sequence chunk
    k: jax.Array,  # [B, S_local, n_kv, head_dim]
    v: jax.Array,
    axis_name: str,
    varying_axes: tuple[str, ...] | None = None,
    seq_lens: jax.Array | None = None,  # [B] GLOBAL valid length per row
) -> jax.Array:
    """Causal ring attention over `axis_name`. Call inside shard_map with the
    sequence dim sharded over that axis. Chunks are assumed layed out in
    order: device i holds positions [i*S_local, (i+1)*S_local).

    `seq_lens` gives each row's global valid length: keys at absolute
    positions >= seq_lens[b] are masked out of every block, so padded
    batches attend only real tokens — matching unsharded masked attention
    (padding-row queries attend the row's valid prefix, exactly like
    ops.attention.causal_prefill_attention; loss masking drops them)."""
    B, S, n_heads, hd = q.shape
    n_kv = k.shape[2]
    g = n_heads // n_kv
    scale = hd**-0.5

    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    local_pos = jnp.arange(S)
    q_pos = me * S + local_pos

    qg = q.reshape(B, S, n_kv, g, hd)

    # Initial accumulators must be marked device-varying over every manual
    # axis of the enclosing shard_map (ring axis + optional batch axis) or
    # the fori_loop carry types mismatch (shard_map VMA tracking).
    axes = varying_axes if varying_axes is not None else (axis_name,)

    def _varying(x):
        return jax.lax.pcast(x, axes, to="varying")

    num0 = _varying(jnp.zeros((B, S, n_kv, g, hd), jnp.float32))
    den0 = _varying(jnp.zeros((B, S, n_kv, g), jnp.float32))
    mx0 = _varying(jnp.full((B, S, n_kv, g), NEG_INF, jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(r, carry):
        k_cur, v_cur, num, den, mx = carry
        src = (me - r) % n  # whose chunk we hold after r rotations
        k_pos = src * S + local_pos
        k_valid = (
            None if seq_lens is None else k_pos[None, :] < seq_lens[:, None]
        )
        b_num, b_den, b_mx = _block_attn(
            qg, k_cur, v_cur, q_pos, k_pos, scale, k_valid
        )
        new_mx = jnp.maximum(mx, b_mx)
        corr_old = jnp.exp(mx - new_mx)
        corr_new = jnp.exp(b_mx - new_mx)
        num = num * corr_old[..., None] + b_num * corr_new[..., None]
        den = den * corr_old + b_den * corr_new
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, num, den, new_mx)

    k_f, v_f, num, den, mx = jax.lax.fori_loop(
        0, n, step, (k, v, num0, den0, mx0)
    )
    # den==0 only for a zero-length row (every key masked): the guard maps
    # it to 0 output instead of dividing by zero.
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.reshape(B, S, n_heads, hd).astype(q.dtype)


def make_ring_prefill_attention(
    mesh: Mesh, sp_axis: str = "sp", batch_axis: str | None = None
):
    """shard_map-wrapped ring attention: takes full [B, S, H, hd] arrays with
    S sharded over `sp_axis` (and optionally B over `batch_axis`), returns
    the attention output with the same sharding. Signature-compatible with
    ops.attention.causal_prefill_attention so it can be passed as
    `attn_impl` to the model forward. `seq_lens` (per-row global valid
    length) masks padded key positions out of every ring block, so padded
    batches match unsharded masked attention — the round-2 NaN-poison
    guard is gone."""

    spec = P(batch_axis, sp_axis, None, None)
    varying = tuple(a for a in (sp_axis, batch_axis) if a)

    def wrapped(q, k, v, lens):
        return ring_self_attention(
            q, k, v, sp_axis, varying_axes=varying, seq_lens=lens
        )

    # check_vma=True: unlike the collective-free pallas wrappers, the ring
    # loop carries real ppermute collectives and the pcast exists to
    # satisfy exactly this verifier — keep it on so a sharding bug fails
    # loudly instead of attending garbage.
    wrapped = jax.shard_map(
        wrapped,
        mesh=mesh,
        in_specs=(spec, spec, spec, P(batch_axis)),
        out_specs=spec,
        check_vma=True,
    )

    def attn(q, k, v, seq_lens=None):
        if seq_lens is None:
            seq_lens = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
        return wrapped(q, k, v, seq_lens.astype(jnp.int32))

    return attn
