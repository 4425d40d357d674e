"""The fused decode loop: one `lax.while_loop` XLA program per chunk.

Where the chunked path (engine/engine.py _decode_chunk_impl) scans a FIXED
`n_steps` — every step runs even after the whole batch finished — this
program loops with an early exit: the condition re-checks per-slot liveness
(`active & budget > 0`) each iteration, so a batch that stops at step 3 of
a 16-step chunk pays 3 model calls, and the over-dispatch the pipelined
harvest relies on (dispatch ceil(budget/chunk) chunks back-to-back, sync
one per chunk) is free past the finish line.

Everything the *Kernel Looping* shape demands happens inside the body:
- the loop-body forward (models/llama.forward_decode_fused_body — the same
  3-part cascade the chunked scan uses, which is what makes greedy output
  token-identical between the paths),
- on-device sampling with a THREADED PRNG key (split per iteration inside
  the loop — the key never round-trips to host),
- grammar via ONE dense-table gather (engine/fused/tables.py),
- per-slot stop detection (EOS / DFA done / budget exhaustion),
- KV append into the chunk buffer, flushed to the PAGED cache in one
  scatter after the loop (identical flush to the chunked path).

Emissions land in a fixed [M, n_steps] buffer (pad_id holes past each
slot's stop); `steps_run` reports the iterations actually executed so the
host's token accounting stays exact under early exit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from k8s_llm_scheduler_tpu.engine.fused.sampler import sample_fused
from k8s_llm_scheduler_tpu.models.llama import forward_decode_fused_body


def fused_decode_chunk_impl(
    params,
    cfg,               # static
    k_cache, v_cache,  # donated paged caches
    page_tables,       # [M, P] own-page tables (trash row included)
    prefix_k, prefix_v,  # [L, Sp, n_kv, hd] shared dense prefix KV
    prefix_len,        # scalar int32
    tok, pos, act, st, budget,  # donated per-slot state [M]
    dense_next,        # [S, V] int32 dense grammar table (-1 disallowed)
    done_state, eos_id, pad_id,
    rng, temperature,
    n_steps: int,      # static — harvest-chunk length
    constrained: bool,  # static
    top_k: int,        # static — 0 = full distribution
    paged_attn: str = "gather",  # static: "gather" | "pallas"
    shmap=None,        # static AttnImpl | None
    vocab_limit: int | None = None,  # static
    shardings=None,    # engine/sharded EngineShardings | None (tp constraints)
):
    """Up to `n_steps` fused decode iterations with early exit; one device
    program, zero host syncs. Returns (k_cache, v_cache, tok, pos, act,
    st, budget, emitted [M, n_steps], steps_run scalar int32).

    Paged-cache traffic is hoisted exactly like the chunked path: pages
    are frozen for the chunk ("gather" pre-gathers them dense, "pallas"
    streams them through the kernel), new K/V accumulates in a small
    chunk buffer, and ONE scatter flushes it back after the loop.
    """
    M, P = page_tables.shape
    ps = k_cache.shape[2]
    n_kv, hd = cfg.n_kv_heads, cfg.head_dim

    if shardings is not None:
        # tp serving (engine/sharded): every KV buffer the loop touches
        # is kv-head-sharded; pinning the layout here keeps the whole
        # while_loop partitioned — GSPMD must not replicate the pages
        # into the loop carry.
        k_cache, v_cache = shardings.kv5(k_cache), shardings.kv5(v_cache)
        prefix_k, prefix_v = shardings.kv4(prefix_k), shardings.kv4(prefix_v)
    own_start = pos - prefix_len  # [M] tokens already in own pages
    if paged_attn == "pallas":
        k_own, v_own = k_cache, v_cache  # [L, num_pages, ps, n_kv, hd]
    else:
        k_own = k_cache[:, page_tables].reshape(-1, M, P * ps, n_kv, hd)
        v_own = v_cache[:, page_tables].reshape(-1, M, P * ps, n_kv, hd)
        if shardings is not None:
            k_own, v_own = shardings.kv5(k_own), shardings.kv5(v_own)
    ck = jnp.zeros((cfg.n_layers, M, n_steps, n_kv, hd), k_cache.dtype)
    cv = jnp.zeros_like(ck)
    if shardings is not None:
        ck, cv = shardings.kv5(ck), shardings.kv5(cv)
    out0 = jnp.full((M, n_steps), pad_id, dtype=jnp.int32)

    def cond(state):
        i, _out, _ck, _cv, _tail, _tok, _pos, act, _st, budget, _key = state
        return (i < n_steps) & jnp.any(act & (budget > 0))

    def body(state):
        i, out, ck, cv, tail, tok, pos, act, st, budget, key = state
        act_eff = act & (budget > 0)
        logits, ck, cv = forward_decode_fused_body(
            params, cfg, tok, pos, k_own, v_own, own_start,
            ck, cv, tail, prefix_k, prefix_v, prefix_len,
            page_tables=page_tables,
            own_impl="pallas" if paged_attn == "pallas" else "dense",
            shmap=shmap,
        )
        if shardings is not None:
            # Vocab-sharded logits: the dense grammar gather and top-k
            # run on the sharded axis (sample_fused's reductions become
            # the only cross-shard traffic of the sampling step).
            logits = shardings.logits2(logits)
        key, sub = jax.random.split(key)
        nxt, new_st = sample_fused(
            logits, st, dense_next, sub, temperature, top_k,
            constrained, pad_id, vocab_limit,
        )
        emitted = jnp.where(act_eff, nxt, pad_id)
        new_st = jnp.where(act_eff, new_st, st)
        finished = (new_st == done_state) | (nxt == eos_id)
        new_act = act_eff & ~finished
        new_budget = jnp.where(act_eff, budget - 1, budget)
        new_pos = jnp.where(act_eff, pos + 1, pos)
        new_tail = jnp.where(act_eff, tail + 1, tail)
        out = jax.lax.dynamic_update_slice(out, emitted[:, None], (0, i))
        return (
            i + 1, out, ck, cv, new_tail, emitted, new_pos, new_act,
            new_st, new_budget, key,
        )

    tail0 = jnp.zeros(M, dtype=jnp.int32)
    steps_run, out, ck, cv, tail, tok, pos, act, st, budget, _ = (
        jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), out0, ck, cv, tail0, tok, pos, act, st, budget, rng),
        )
    )

    # Flush the chunk buffer into pages (identical to the chunked path):
    # entry j of slot m lands at own position own_start[m]+j; invalid
    # entries (j >= tail) go to the reserved scratch page 0.
    j = jnp.arange(n_steps)
    own_pos = own_start[:, None] + j[None, :]            # [M, n]
    valid = j[None, :] < tail[:, None]
    page_slot = jnp.clip(own_pos // ps, 0, P - 1)
    page_ids = jnp.take_along_axis(page_tables, page_slot, axis=1)
    page_ids = jnp.where(valid, page_ids, 0)
    offs = jnp.where(valid, own_pos % ps, 0)
    k_cache = k_cache.at[:, page_ids, offs].set(ck)
    v_cache = v_cache.at[:, page_ids, offs].set(cv)
    return k_cache, v_cache, tok, pos, act, st, budget, out, steps_run
