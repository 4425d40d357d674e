"""The TPU inference engine: cascade prefill, decision waves, fused decode.

This is the component that replaces the reference's entire
HuggingFaceClient network path (reference scheduler.py:418-433): where the
reference ships a prompt over HTTPS and waits for a remote 70B, this engine
runs the model in-process on the TPU mesh.

Design, driven by XLA semantics (everything hot is one traced program with
static shapes) and dispatch economics (host<->device round trips dominate
small-model latency; only syncs are expensive, enqueues pipeline):

- **Shared-prefix (cascade) prefill**: a scheduling burst shares its
  (system + cluster-state) prompt prefix (core/prompt.py; the reference's
  own cache key proves the equivalence class, scheduler.py:265-271). The
  prefix prefills ONCE per cluster snapshot into a dense KV buffer —
  blockwise for long prompts (_prefill_prefix_chunked: a 256-node cluster
  is ~41k byte-tokens and O(S^2) single-shot scores would not fit HBM) —
  and every request decodes against it.
- **Decision waves** (submit_wave/harvest_wave — the burst fast path): one
  fused device program runs the whole batch's suffix prefill, first-token
  sample, and GRAMMAR-ACCELERATED BLOCK DECODE to completion. Each block
  iteration samples one token from carried logits, expands the forced run
  that follows via DFA table gathers (free: no model call for the JSON
  skeleton), and runs one block-wide mini-prefill — a ~70-token decision
  costs ~9 model calls. Waves never touch the paged cache, pipeline
  back-to-back (round-trip latency overlaps), and start their D2H copy at
  submit so harvest finds results on host.
- **Sparse grammar tables** (engine/constrained.py SparseDFATables):
  per-state allowed-token lists, sampled in K-space — vocab-independent,
  so constrained decoding works unchanged at 128k-vocab BPE tokenizers.
  Changing the node-name set never recompiles.
- **Chunked continuous batching** (add_requests/step — the general path):
  a fixed decode batch of `max_slots` slots over the paged KV cache;
  `chunk_steps` fused decode steps per program, chained with one host sync;
  own-token attention either pre-gathers pages to a dense buffer or
  streams them through the Pallas kernel (paged_attn="pallas"). Requests
  join/leave between chunks; shapes never depend on how many are in
  flight.

  WHY TWO DECODE PATHS: decision serving uses waves EXCLUSIVELY —
  decisions are short, grammar-bounded, and arrive in prefix-sharing
  bursts, so one fused program with no paged-cache traffic beats chunked
  decode on every axis that matters there (dispatch count, HBM traffic,
  tail latency). The paged path is the GENERAL-COMPLETION engine: budgets
  beyond a wave's fused cap, no grammar, requests joining/leaving
  mid-flight, chunk-granular harvesting — the capability the reference
  exposes via its remote chat endpoint (reference scheduler.py:425-433).
  Its product surface is `generate()` / `cli complete`; it also serves as
  the fallback for workloads whose emission budget or batch dynamics
  don't fit a wave.
- **Device-resident decode state**: current token / position / active /
  DFA state / remaining-budget live on device between dispatches; the
  budget makes max_new_tokens a device-side guarantee.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from collections import OrderedDict
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from k8s_llm_scheduler_tpu.engine.constrained import (
    DecisionDFA,
    sparse_tables,
    wave_iterations,
)
from k8s_llm_scheduler_tpu.observability import spans
from k8s_llm_scheduler_tpu.engine.kv_cache import PagedKVCache
from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer, Tokenizer
from k8s_llm_scheduler_tpu.models import family
from k8s_llm_scheduler_tpu.models.configs import GdnMoeConfig, LlamaConfig, MlaMoeConfig, MlaScmoeConfig
from k8s_llm_scheduler_tpu.models.llama import (
    Params,
    forward_decode_buffered,
    forward_prefill,
    forward_prefill_suffix,
)
from k8s_llm_scheduler_tpu.ops.attention import NEG_INF, AttnImpl

logger = logging.getLogger(__name__)


def named_program(fn, *, program: str, **bound):
    """`functools.partial(fn, **bound)` for `jax.jit`, under a program name
    of its own: the compiled module is `jit_<program>`, which is what a
    device trace's "XLA Modules" line shows for every run of it and what
    a trace reader finds the program by (PERF.md §3 lists the names).
    `jax.jit` would name a bare `functools.partial` `jit__unknown`.
    (tools/graftlint sees through it as it does through a partial.)

    The name is also part of the persistent compile cache's key, and named
    scopes are not (the key is taken after debug info is stripped): a
    program whose scopes change must change its name, or a cache written
    before the change serves the old executable, without the scopes."""
    named = functools.partial(fn, **bound)
    named.__name__ = program
    return named


def _pick(masked, rng, temperature):
    """temperature>0 -> categorical, else argmax, over masked f32 logits."""
    greedy = jnp.argmax(masked, axis=-1)
    scaled = masked / jnp.maximum(temperature, 1e-6)
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def _sample_unconstrained(logits, pad_id, rng, temperature, vocab_limit=None):
    """Full-vocab sampling with only pad excluded (pad is the idle-slot
    emission sentinel — see set_grammar). `vocab_limit` (a static int, set
    when the tokenizer's vocab is smaller than the model's padded vocab)
    additionally masks ids the tokenizer cannot decode — a checkpoint-shaped
    128k-vocab model served with a small domain tokenizer must never emit
    an id past the tokenizer's table."""
    V = logits.shape[-1]
    ids = jnp.arange(V)[None, :]
    bad = ids == pad_id
    if vocab_limit is not None and vocab_limit < V:
        bad = bad | (ids >= vocab_limit)
    masked = jnp.where(bad, NEG_INF, logits)
    return _pick(masked, rng, temperature)


def _sample_sparse(logits, tok_rows, next_rows, rng, temperature):
    """Grammar sampling in K-space: gather the allowed tokens' logits, pick
    among them, map back to (token id, next DFA state). The full-vocab mask
    never materializes, so tables stay vocab-independent
    (engine/constrained.py SparseDFATables — this is what makes constrained
    decoding work at 128k-vocab BPE tokenizers).

    logits [R, V]; tok_rows/next_rows [R, K] (-1 padded)."""
    gathered = jnp.take_along_axis(logits, jnp.maximum(tok_rows, 0), axis=1)
    masked = jnp.where(tok_rows >= 0, gathered, NEG_INF)
    k = _pick(masked, rng, temperature)
    tok = jnp.take_along_axis(tok_rows, k[:, None], axis=1)[:, 0]
    nxt = jnp.take_along_axis(next_rows, k[:, None], axis=1)[:, 0]
    return tok.astype(jnp.int32), nxt.astype(jnp.int32)


def _admit_impl(
    params: Params,
    cfg: LlamaConfig,  # static
    tokens,        # [R, Ss] suffix tokens (R = admission-row bucket)
    suffix_lens,   # [R] int32 (0 on padding rows)
    prefix_k, prefix_v,  # [L, Sp, n_kv, hd] shared dense prefix KV
    prefix_len,    # scalar int32
    k_cache, v_cache,    # donated
    page_ids,      # [R, Ss/page_size] scatter destinations (0 = scratch)
    slot_ids,      # [R] int32 — target slot per row (trash slot M on padding)
    tok, pos, act, st, budget, first,  # donated per-slot state [M+1]
    new_budgets,   # [R] budget for admitted rows (max_new - 1; 0 on padding)
    sp_tokens, sp_next, done_state, eos_id, pad_id,
    dfa_start,     # scalar int32
    rng, temperature,
    constrained: bool,  # static
    prefix_impl: str | None = None,  # static
    vocab_limit: int | None = None,  # static — see _sample_unconstrained
    shardings=None,  # engine/sharded EngineShardings | None (tp constraints)
):
    """Batched admission: suffix prefill + KV scatter + first-token sample,
    one device program. Rows scatter into their slot's state; padding rows
    land in the reserved trash row (index M) and stay inactive."""
    if shardings is not None:
        # Pin the tp layout at the program boundary: pages and prefix KV
        # stay kv-head-sharded through the suffix prefill + scatter —
        # GSPMD must partition, never replicate-and-slice.
        k_cache, v_cache = shardings.kv5(k_cache), shardings.kv5(v_cache)
        prefix_k, prefix_v = shardings.kv4(prefix_k), shardings.kv4(prefix_v)
    last_logits, k_cache, v_cache = forward_prefill_suffix(
        params, cfg, tokens, suffix_lens, prefix_k, prefix_v, prefix_len,
        k_cache, v_cache, page_ids, prefix_impl=prefix_impl,
    )
    if shardings is not None:
        # Logits leave the (vocab-sharded) lm head already split on V;
        # the constraint keeps sampling's gathers on the sharded axis
        # instead of forcing an all-gather of [R, V] first.
        last_logits = shardings.logits2(last_logits)
    R = tokens.shape[0]
    start_vec = jnp.full((R,), dfa_start, dtype=jnp.int32)
    if constrained:
        first_new, st_new = _sample_sparse(
            last_logits, sp_tokens[start_vec], sp_next[start_vec], rng, temperature
        )
    else:
        first_new = _sample_unconstrained(
            last_logits, pad_id, rng, temperature, vocab_limit
        )
        st_new = start_vec
    finished = (first_new == eos_id) | (st_new == done_state)
    real = suffix_lens > 0  # padding rows must never activate the trash row

    tok = tok.at[slot_ids].set(first_new)
    pos = pos.at[slot_ids].set(prefix_len + suffix_lens)
    act = act.at[slot_ids].set(real & ~finished)
    st = st.at[slot_ids].set(st_new)
    budget = budget.at[slot_ids].set(new_budgets)
    first = first.at[slot_ids].set(first_new)
    return k_cache, v_cache, tok, pos, act, st, budget, first


def _decode_chunk_impl(
    params: Params,
    cfg: LlamaConfig,  # static
    k_cache, v_cache,  # donated
    page_tables,       # [M, max_pages] own-page tables
    prefix_k, prefix_v,  # [L, Sp, n_kv, hd]
    prefix_len,        # scalar int32
    tok, pos, act, st, budget,  # donated per-slot state [M]
    sp_tokens, sp_next, done_state, eos_id, pad_id,
    rng, temperature,
    n_steps: int,      # static
    constrained: bool,  # static
    paged_attn: str = "gather",  # static: "gather" | "pallas"
    shmap=None,  # static AttnImpl | None (tp-sharded paged kernel)
    vocab_limit: int | None = None,  # static — see _sample_unconstrained
    shardings=None,  # engine/sharded EngineShardings | None (tp constraints)
):
    """`n_steps` decode iterations fused into one program. Emits the sampled
    token per step; finished/exhausted/idle slots emit pad_id and idle.

    Paged-cache traffic is hoisted out of the step loop in one of two ways
    (the pages are frozen during a chunk — new K/V goes to a small chunk
    buffer and flushes back to pages in ONE scatter at the end):
    - "gather": own pages gather to a dense buffer once per chunk, then
      every step reads the dense buffer (measured ~2.5x over per-step
      paged scatter/gather on the bench size class);
    - "pallas": no gather at all — each step's own-token attention streams
      the pages HBM->VMEM through the hand-tiled kernel
      (ops/pallas_paged_attention.py), which wins when the gathered
      working set would be large (long sequences, many slots).
    """
    M, P = page_tables.shape
    ps = k_cache.shape[2]
    n_kv, hd = cfg.n_kv_heads, cfg.head_dim

    if shardings is not None:
        k_cache, v_cache = shardings.kv5(k_cache), shardings.kv5(v_cache)
        prefix_k, prefix_v = shardings.kv4(prefix_k), shardings.kv4(prefix_v)
    own_start = pos - prefix_len  # [M] tokens already in own pages
    if paged_attn == "pallas":
        k_own, v_own = k_cache, v_cache  # [L, num_pages, ps, n_kv, hd]
    else:
        # Frozen own-page KV for the whole chunk: [L, M, P*ps, n_kv, hd].
        k_own = k_cache[:, page_tables].reshape(-1, M, P * ps, n_kv, hd)
        v_own = v_cache[:, page_tables].reshape(-1, M, P * ps, n_kv, hd)
        if shardings is not None:
            # The page gather keeps the kv-head axis intact (axis 3 both
            # sides) — constrain so it stays a LOCAL gather per shard.
            k_own, v_own = shardings.kv5(k_own), shardings.kv5(v_own)
    ck = jnp.zeros((cfg.n_layers, M, n_steps, n_kv, hd), k_cache.dtype)
    cv = jnp.zeros_like(ck)
    if shardings is not None:
        ck, cv = shardings.kv5(ck), shardings.kv5(cv)

    def step(carry, _):
        ck, cv, tail, tok, pos, act, st, budget, key = carry
        act_eff = act & (budget > 0)
        logits, ck, cv = forward_decode_buffered(
            params, cfg, tok, pos, k_own, v_own, own_start,
            ck, cv, tail, prefix_k, prefix_v, prefix_len,
            page_tables=page_tables,
            own_impl="pallas" if paged_attn == "pallas" else "dense",
            shmap=shmap,
        )
        if shardings is not None:
            logits = shardings.logits2(logits)
        key, sub = jax.random.split(key)
        if constrained:
            nxt, new_st = _sample_sparse(
                logits, sp_tokens[st], sp_next[st], sub, temperature
            )
        else:
            nxt = _sample_unconstrained(
                logits, pad_id, sub, temperature, vocab_limit
            )
            new_st = st
        emitted = jnp.where(act_eff, nxt, pad_id)
        new_st = jnp.where(act_eff, new_st, st)
        finished = (new_st == done_state) | (nxt == eos_id)
        new_act = act_eff & ~finished
        new_budget = jnp.where(act_eff, budget - 1, budget)
        new_pos = jnp.where(act_eff, pos + 1, pos)
        new_tail = jnp.where(act_eff, tail + 1, tail)
        return (ck, cv, new_tail, emitted, new_pos, new_act, new_st, new_budget, key), emitted

    tail0 = jnp.zeros(M, dtype=jnp.int32)
    (ck, cv, tail, tok, pos, act, st, budget, _), toks = jax.lax.scan(
        step,
        (ck, cv, tail0, tok, pos, act, st, budget, rng),
        None,
        length=n_steps,
    )

    # Flush the chunk buffer into pages: entry j of slot m lands at own
    # position own_start[m]+j; invalid entries (j >= tail) go to scratch 0.
    j = jnp.arange(n_steps)
    own_pos = own_start[:, None] + j[None, :]            # [M, n]
    valid = j[None, :] < tail[:, None]
    page_slot = jnp.clip(own_pos // ps, 0, P - 1)
    page_ids = jnp.take_along_axis(page_tables, page_slot, axis=1)
    page_ids = jnp.where(valid, page_ids, 0)
    offs = jnp.where(valid, own_pos % ps, 0)
    # ck is [L, M, n, n_kv, hd]; index arrays [M, n] -> one scatter per cache.
    k_cache = k_cache.at[:, page_ids, offs].set(ck)
    v_cache = v_cache.at[:, page_ids, offs].set(cv)
    return k_cache, v_cache, tok, pos, act, st, budget, toks.T  # [M, n]


def _wave_impl(
    params: Params,
    cfg: LlamaConfig | MlaMoeConfig | MlaScmoeConfig | GdnMoeConfig,  # static
    tokens,        # [R, Ss] suffix tokens, left-aligned, padded
    suffix_lens,   # [R] int32 (0 on padding rows)
    prefix_cache,  # the model's cache tuple of the shared prefix, each
    # [L, Sp, *token shape]: (k, v) [.., n_kv, hd] dense, (c_kv, k_r) latent
    prefix_len,    # scalar int32
    max_new,       # [R] total emission budget per row (0 on padding rows)
    sp_tokens, sp_next, forced, forced_next, done_state, eos_id, pad_id,
    dfa_start,     # scalar int32
    rng, temperature,
    n_iters: int,  # static — worst-case block iterations (wave_iterations)
    F: int,        # static — block width (sampled token + forced run)
    cap: int,      # static — generated-KV capacity, >= max(max_new)
    constrained: bool,  # static
    prefix_impl: str | None = None,  # static
    vocab_limit: int | None = None,  # static — see _sample_unconstrained
    ragged_decode: bool = False,  # static — ragged-M decode matmuls
    shardings=None,  # engine/sharded EngineShardings | None (tp constraints)
    prefix_state=(),  # the model's per-sequence state after the prefix, each
    # [state layers, *shape]; () for a family that has none
):
    """One whole decision wave in ONE device program, with
    GRAMMAR-ACCELERATED BLOCK DECODING.

    Pipeline: batched suffix prefill against the shared dense prefix, then
    `n_iters` block iterations. Each iteration (a) samples ONE token from
    logits carried from the previous model call, (b) expands the forced run
    that follows it via DFA table gathers — no model call: every state with
    exactly one out-edge (JSON skeleton spans, engine/constrained.py
    forced_token_table) is consumed for free — and (c) runs one F-wide
    mini-prefill (models/llama.forward_block_decode) over the whole block
    to compute its K/V and the next choice point's logits. A ~70-token
    constrained decision completes in ~10-16 model calls instead of 70.

    Completion is guaranteed on device: `n_iters` comes from a DP over the
    DFA (wave_iterations) and the per-row budget gates every emission, so
    every request finishes inside the wave even for an unconstrained
    grammar (forced = all -1 degrades to one token per iteration with
    n_iters = max_new). No paged-cache traffic, one dispatch, one fetch.

    The block loop is a `lax.while_loop` bounded by `n_iters` that exits as
    soon as no row is alive: `n_iters` is a worst-case bound (and rounded up
    to bucket compile variants — engine.submit_wave), but typical decisions
    finish in fewer iterations, and a finished wave's remaining iterations
    would emit only pads. Early exit makes both the rounding padding and the
    post-completion tail free, so the bound can stay conservative.

    The cache (prefix, suffix, generated) is THE MODEL'S per-token cache:
    a tuple of arrays whose axes 0 and 1 are layer and token (row, then
    token, for the per-row buffers). The model module the config's type
    selects (models.family) says its shapes and brings the two forwards;
    nothing here knows what a cached token is made of.

    A family with a PER-SEQUENCE STATE (`state_shapes(cfg)` not empty:
    models/gdn_moe.py) also takes the state the prefix left: the suffix
    call seeds every row from it and returns each row's own, which rides
    the block loop's carry; every model call advances a row's state by the
    tokens it holds for the row, and a row that is not alive keeps it. The
    prefix's state is read here and never written.

    Returns (emitted [R, n_iters*F] with pad_id holes, active [R],
    iters_run scalar int32 — the number of model calls actually executed)
    and, for a model whose forwards count (family COUNTERS), those counts
    summed over the wave's model calls, int32 [len(COUNTERS)].
    """
    model = family(cfg)
    counted = len(model.COUNTERS) > 0  # static: a tuple of names
    stateful = len(model.state_shapes(cfg)) > 0  # static too
    if shardings is not None:
        prefix_cache = tuple(shardings.kv4(a) for a in prefix_cache)
    # Named scopes (suffix_prefill, block_decode, sample_expand, model;
    # attn / mlp / kv_writeback / lm_head inside models/llama.py) are
    # metadata on the compiled operations: a profiler trace reads device
    # time by step from them (observability/scopes.py), no fusion changes.
    with jax.named_scope("suffix_prefill"):
        last_logits, *sfx = model.forward_prefill_suffix_dense(
            params, cfg, tokens, suffix_lens, *prefix_cache, prefix_len,
            prefix_impl=prefix_impl,
            **({"state": prefix_state} if stateful else {}),
        )
    counts = sfx.pop() if counted else None
    state = sfx.pop() if stateful else None  # each row's, after its suffix
    R = tokens.shape[0]
    if shardings is not None:
        # Suffix KV [L, R, Ss, n_kv, hd] and (below) the generated-KV
        # buffers share the rank-5 kv-head layout with the paged cache.
        sfx = [shardings.kv5(a) for a in sfx]
        last_logits = shardings.logits2(last_logits)
    st = jnp.full((R,), dfa_start, dtype=jnp.int32)
    act = suffix_lens > 0
    # emitted doubles as the generated-KV write tail: waves start with an
    # empty buffer and every emitted token lands at its emission index.
    emitted = jnp.zeros(R, dtype=jnp.int32)
    pos_next = prefix_len + suffix_lens  # absolute position of next token

    # generated-token cache, one buffer per array of the cache tuple; F slots
    # beyond `cap` so that a block's whole window fits behind any tail
    # (ops/attention.write_block)
    gen = tuple(
        jnp.zeros((model.cache_layers(cfg), R, cap + F, *shape), prefix_cache[0].dtype)
        for shape in model.cache_token_shapes(cfg)
    )
    if shardings is not None:
        gen = tuple(shardings.kv5(a) for a in gen)
    jcol = jnp.arange(F)

    @jax.named_scope("sample_expand")
    def sample_expand(st, act, emitted, logits, key):
        key, sub = jax.random.split(key)
        # (a) sample the block's first token from the carried logits
        if constrained:
            t0, s_t0 = _sample_sparse(
                logits, sp_tokens[st], sp_next[st], sub, temperature
            )
        else:
            t0 = _sample_unconstrained(
                logits, pad_id, sub, temperature, vocab_limit
            )
            s_t0 = st
        emit0 = act & (emitted < max_new)
        s_cur = jnp.where(emit0, s_t0, st)
        fin0 = (t0 == eos_id) | (s_cur == done_state)
        blk = [jnp.where(emit0, t0, pad_id)]
        valid = [emit0]
        alive = emit0 & ~fin0 & (emitted + 1 < max_new)
        # (b) forced-run expansion: pure table gathers, no model calls
        for j in range(1, F):
            ft = forced[s_cur]
            emit_j = alive & (ft >= 0)
            t_j = jnp.where(emit_j, ft, pad_id)
            s_cur = jnp.where(emit_j, forced_next[s_cur], s_cur)
            fin_j = (t_j == eos_id) | (s_cur == done_state)
            blk.append(t_j)
            valid.append(emit_j)
            # paused-at-choice rows (ft < 0) stay alive for the next
            # iteration's sample; emitted rows continue unless finished or
            # out of budget
            alive = jnp.where(
                emit_j,
                ~fin_j & (emitted + j + 1 < max_new),
                alive & (ft < 0),
            )
        blk_tok = jnp.stack(blk, axis=1)      # [R, F]
        blk_valid = jnp.stack(valid, axis=1)  # [R, F]
        blk_len = blk_valid.sum(axis=1).astype(jnp.int32)
        return blk_tok, blk_valid, blk_len, s_cur, alive, key

    def iteration(carry):
        gen, st, act, emitted, pos_next, logits, key, *more = carry
        counts, state = more[:int(counted)], more[int(counted):]
        blk_tok, blk_valid, blk_len, s_cur, alive, key = sample_expand(
            st, act, emitted, logits, key
        )
        positions = pos_next[:, None] + jcol[None, :]
        # (c) one model call for the whole block
        with jax.named_scope("model"):
            new_logits, *gen = model.forward_block_decode(
                params, cfg, blk_tok, blk_valid, blk_len, positions,
                *sfx, suffix_lens, *gen, emitted,
                *prefix_cache, prefix_len, prefix_impl=prefix_impl,
                ragged=ragged_decode,
                **({"state": state[0]} if stateful else {}),
            )
        if counted:
            counts = [counts[0] + gen.pop()]
        if stateful:
            state = [gen.pop()]
        if shardings is not None:
            new_logits = shardings.logits2(new_logits)
            gen = [shardings.kv5(a) for a in gen]
        carry = (
            tuple(gen), s_cur, alive, emitted + blk_len,
            pos_next + blk_len, new_logits, key, *counts, *state,
        )
        return carry, blk_tok

    carry0 = (gen, st, act, emitted, pos_next, last_logits, rng)
    if counted:
        carry0 += (counts,)
    if stateful:
        carry0 += (state,)
    out0 = jnp.full((R, n_iters * F), pad_id, dtype=tokens.dtype)

    def cond(state):
        i, _, carry = state
        alive = carry[2]
        return (i < n_iters) & jnp.any(alive)

    def body(state):
        i, out, carry = state
        carry, blk_tok = iteration(carry)
        out = jax.lax.dynamic_update_slice(out, blk_tok, (0, i * F))
        return i + 1, out, carry

    with jax.named_scope("block_decode"):
        iters_run, out, carry = jax.lax.while_loop(
            cond, body, (jnp.int32(0), out0, carry0)
        )
    if counted:
        return out, carry[2], iters_run, carry[7]
    return out, carry[2], iters_run


def _lcp_seed_impl(bufs, seed_kv, reuse, shardings=None):
    """Each buffer of `bufs` ([L, cap, *token shape], zeros) with its first
    `reuse` tokens copied from the same member of `seed_kv` (a cached
    prefix's buffers, of their own length), under scope `lcp_seed`: a
    masked copy of fixed shape, `reuse` a traced scalar. On a tp mesh the
    buffers keep the prefix's head-sharded layout (`shardings.kv4`)."""
    if shardings is not None:
        bufs = tuple(shardings.kv4(a) for a in bufs)
    out = []
    with jax.named_scope("lcp_seed"):
        for buf, old in zip(bufs, seed_kv):
            n = min(buf.shape[1], old.shape[1])
            keep = (jnp.arange(n) < reuse).reshape(1, n, *(1,) * (buf.ndim - 2))
            head = jnp.where(keep, old[:, :n].astype(buf.dtype), buf[:, :n])
            out.append(jax.lax.dynamic_update_slice_in_dim(buf, head, 0, axis=1))
    return tuple(out)


@dataclasses.dataclass
class _PrefixKV:
    """The model's cache of a burst-shared prompt prefix, prefilled once:
    a tuple of arrays [L, Sp_bucket, *token shape] — (k, v) [.., n_kv, hd]
    for the dense family, the latent pair (c_kv [.., dc], k_r [.., dr]) for
    models/mla_moe.py. Axis 1 of every member is the token capacity.

    `state`: what a family with a per-sequence state (models/gdn_moe.py)
    carries AFTER the prefix's `length` tokens, each [state layers,
    *shape]; () for the others. It is pinned, evicted and epoch-stamped
    with its entry, and only ever read: every wave seeds its rows from
    it."""

    kv: tuple[jax.Array, ...]
    length: int
    token_ids: tuple[int, ...]
    state: tuple[jax.Array, ...] = ()

    @property
    def k(self) -> jax.Array:
        return self.kv[0]

    @property
    def v(self) -> jax.Array:
        return self.kv[1]

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in (*self.kv, *self.state))


@dataclasses.dataclass
class _Request:
    req_id: int
    slot: int
    prompt_len: int
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    first_pending: bool = True  # first token not yet harvested from device
    done: bool = False
    # Driven by an EXTERNAL decoder (spec/decoder.py): the slot is
    # deactivated in the engine's decode batch and every harvest path
    # skips it — fused chunks and open speculative rounds share one
    # dispatch pipeline without an engine-wide hold. The external owner
    # finishes the request through release_slot (or hands it back by
    # clearing this flag and re-arming the slot — the auto-disable path).
    external: bool = False
    # Parked piggyback emissions (engine._pending_emissions) with list
    # index < park_floor predate this request's admission: a slot reused
    # after an abort_all/rollback mid-pack must never book the aborted
    # occupant's parked tokens as its own (_finish_harvest skips those
    # columns). Reset to 0 once the parked list is consumed.
    park_floor: int = 0
    submitted_at: float = dataclasses.field(default_factory=time.perf_counter)


@dataclasses.dataclass
class Finished:
    req_id: int
    token_ids: list[int]
    text: str
    latency_ms: float


@dataclasses.dataclass
class WaveHandle:
    """An in-flight decision wave: dispatched, not yet harvested.

    Waves pipeline — submit_wave returns immediately after enqueueing the
    device program, so several waves can be in flight back-to-back and the
    per-dispatch round-trip latency overlaps instead of serializing
    (see _wave_impl)."""

    toks_d: jax.Array   # [R, n_iters*F] emitted tokens (pad_id holes)
    iters_d: jax.Array  # scalar int32 — model calls actually run (early exit)
    # int32 [len(COUNTERS)] of the model family's device counters over the
    # wave's model calls (models/mla_moe.py: expert load); None for a
    # family that counts nothing
    counts_d: jax.Array | None
    n: int              # real prompts in this wave (<= R)
    max_new_tokens: int
    req_ids: list[int]
    submitted_at: float = dataclasses.field(default_factory=time.perf_counter)
    # True when this wave's geometry compiled at dispatch: its wall time is
    # jit + execution, and service-time estimators must skip it.
    cold_compile: bool = False
    # Compiled-variant identity (engine._wave_key) — service-time
    # estimators key on it so a 50ms half-R decision wave and a 2s
    # full-R longctx wave don't share one estimate.
    geo_key: tuple | None = None
    # The wave's number: engine.stats["waves"] at submit, 1-based. The
    # device runs wave programs in submit order, so a trace's k-th
    # `jit_wave` run is the k-th `engine.submit_wave` annotation, and
    # the `wave` stat on both names it; every item's decision trace
    # carries the same number (engine/local.py _attach_item_spans).
    seq: int = 0
    bucket: int = 0       # suffix bucket (tokens per row) the wave compiled for
    model_calls: int = 0  # block iterations the device ran; set at harvest

    def is_ready(self) -> bool:
        """True once the device result landed (harvest won't block)."""
        try:
            return self.toks_d.is_ready()
        except AttributeError:  # pragma: no cover - older jax fallback
            return True


class InferenceEngine:
    """Single-owner (one thread/task) engine over one model + one KV cache."""

    DFA_STATE_CAPACITY = 4096
    # On-device prefix KV cache budget, in BYTES (not entries): a cached
    # prefix costs L x cap x n_kv x hd x 2 x dtype — ~6 MB at bench scale
    # but ~800 MB at 8B with a 4k-token prompt, so a count cap is the wrong
    # unit. At least one entry (the active prefix) is always kept.
    PREFIX_CACHE_BYTES = 1 << 30

    def __init__(
        self,
        params: Params,
        cfg: LlamaConfig,
        tokenizer: Tokenizer | None = None,
        *,
        num_pages: int = 512,
        page_size: int = 64,
        max_slots: int = 8,
        max_pages_per_seq: int = 64,
        prefill_buckets: tuple[int, ...] = (128, 256, 512, 1024, 2048, 4096, 8192),
        chunk_steps: int = 16,
        temperature: float = 0.3,
        rng_seed: int = 0,
        prefix_chunk: int = 2048,
        paged_attn: str = "gather",
        prefix_attn_impl: str | None = None,
        decode_matmul: str = "dense",  # "dense" | "ragged" (single device)
        mesh=None,  # jax.sharding.Mesh | None — set for multi-device serving
        admission_chunk_tokens: int = 256,
        fused_decode: bool = True,
        top_k: int = 0,
        fused_table_bytes: int | None = None,
    ) -> None:
        self.cfg = cfg
        self.params = params
        # The model module the config's TYPE selects (models.family): its
        # cache tuple's shapes and the three forwards of the decision path.
        # `paged` = the family also has the paged-pool forwards (admit,
        # decode chunks, the fused loop, spec/): the dense
        # family has, the latent ones (models/mla_moe.py, mla_scmoe.py) do
        # not — their entry points refuse by name (_require_paged) and no
        # pool is allocated for them.
        self._model = family(cfg)
        self._model_file = f"models/{self._model.__name__.rsplit('.', 1)[-1]}.py"
        self.paged = isinstance(cfg, LlamaConfig)
        # A family whose sequences carry a state besides their tokens
        # (`state_shapes`: models/gdn_moe.py): a prefix entry then holds
        # the state its prefill left, waves seed their rows from it, and
        # a new prefix is never seeded from a cached one's tokens (a
        # state exists only at the lengths it was saved at).
        self._stateful = len(self._model.state_shapes(cfg)) > 0
        self.tokenizer = tokenizer or ByteTokenizer()
        if self.tokenizer.vocab_size > cfg.vocab_size:
            raise ValueError(
                f"tokenizer vocab {self.tokenizer.vocab_size} > model vocab "
                f"{cfg.vocab_size} — the tokenizer would emit ids past the "
                f"embedding table"
            )
        # Tokenizer smaller than the model's (padded) vocab is fine —
        # checkpoint-shaped 128k-vocab configs served with a small domain
        # tokenizer (e.g. the committed 4k-BPE fixture). Grammar tables are
        # built from the tokenizer so constrained ids are always in range;
        # unconstrained sampling masks the undecodable tail via this limit.
        self._vocab_limit: int | None = (
            self.tokenizer.vocab_size
            if self.tokenizer.vocab_size < cfg.vocab_size
            else None
        )
        # Kept for components that must restore/replace params with the
        # SAME placement serving booted with (rollout/hotswap.py).
        self.mesh = mesh
        tp_size = mesh.shape.get("tp", 1) if mesh is not None else 1
        if not self.paged:
            if tp_size > 1:
                what = getattr(self._model, "UNSHARDED", (
                    "a per-sequence state has no sharding rule"
                    if self._stateful
                    else "a latent cache has no head axis to shard"
                ))
                raise ValueError(
                    f"{cfg.name}: llm.mesh tp={tp_size} is not served — "
                    f"{what} and the experts' "
                    f"exchange is not written (parallel/sharding.py, "
                    f"engine/sharded/)"
                )
            if decode_matmul != "dense":
                raise ValueError(
                    f"{cfg.name}: llm.decode_matmul {decode_matmul!r} is not "
                    f"served by {self._model_file}"
                )
        # The tp serving plane (engine/sharded/plane.py): the placement +
        # constraint authority for every device buffer this constructor
        # allocates and every jitted program it builds. None off-mesh —
        # all plane hooks below degrade to the single-device layout.
        from k8s_llm_scheduler_tpu.engine.sharded import build_plane

        self.plane = build_plane(mesh)
        shardings = (
            self.plane.engine_shardings() if self.plane is not None else None
        )
        self._shardings = shardings
        self.kv = PagedKVCache(
            cfg,
            # no paged forwards, no pool: the scratch page alone (the host
            # bookkeeping and page_size stay what callers read)
            num_pages=num_pages if self.paged else 1,
            page_size=page_size,
            max_slots=max_slots,
            max_pages_per_seq=max_pages_per_seq,
            sharding=self.plane.kv_pages if self.plane is not None else None,
        )
        bad = [bkt for bkt in prefill_buckets if bkt % page_size]
        if bad:
            raise ValueError(f"prefill buckets {bad} not multiples of page_size={page_size}")
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        # Block width for chunked long-prefix prefill: bounds the per-layer
        # cascade-attention intermediate at O(prefix_chunk x prefix) instead
        # of O(prefix^2) — a 16k x 48k f32 score block would not fit HBM.
        self.prefix_chunk = int(prefix_chunk)
        # Chunked-decode own-token attention: "gather" (dense pre-gather per
        # chunk) or "pallas" (stream pages through the hand-tiled kernel).
        if paged_attn not in ("gather", "pallas"):
            raise ValueError(f"paged_attn must be 'gather' or 'pallas', got {paged_attn!r}")
        self.paged_attn = paged_attn
        self.chunk_steps = int(chunk_steps)
        self.temperature = float(temperature)
        self.max_slots = max_slots

        # Per-instance shared-prefix attention impl (None = the module
        # default, "auto"): bound into the jitted programs as a closure
        # constant — per-engine, never a process-global mutation — as an
        # AttnImpl, whose `resolved` record says which implementation each
        # call site actually got (get_stats: attention_impls). On a
        # multi-device mesh with a tp axis it also carries the mesh: the
        # Pallas kernels run per-shard under shard_map over the tp-sharded
        # kv-head axis (GSPMD cannot partition a pallas_call), so the 70B
        # tp=8 serving path keeps flash attention instead of falling back
        # to XLA.
        if prefix_attn_impl not in (None, "auto", "xla", "pallas"):
            # A typo here would silently degrade to the einsum path —
            # exactly the flash-kernel regression this knob exists to avoid.
            raise ValueError(
                f"unknown prefix attention impl {prefix_attn_impl!r} "
                f"(expected 'auto', 'xla', or 'pallas')"
            )
        prefix_attn_impl = AttnImpl(
            kind=prefix_attn_impl or "auto",
            mesh=mesh if tp_size > 1 else None,
        )
        self.prefix_attn_impl = prefix_attn_impl
        if decode_matmul not in ("dense", "ragged"):
            raise ValueError(
                f"unknown decode_matmul {decode_matmul!r} "
                f"(expected 'dense' or 'ragged')"
            )
        if decode_matmul == "ragged" and tp_size > 1:
            # GSPMD cannot partition a pallas_call, so the ragged kernel
            # cannot run over a tp-sharded activation. This used to log
            # and silently serve the dense path — a config asking for the
            # ragged kernel got ~none of it and no signal. Refuse at
            # build time instead: the operator either drops the knob or
            # serves single-device, but never ships a mesh believing the
            # ragged path is live.
            raise ValueError(
                f"decode_matmul='ragged' is single-device-only (the "
                f"pallas kernel cannot be partitioned by GSPMD) but the "
                f"serving mesh has tp={tp_size}; use decode_matmul="
                f"'dense' for tensor-parallel serving"
            )
        self.decode_matmul = decode_matmul
        chunk_shmap = (
            prefix_attn_impl
            if tp_size > 1 and paged_attn == "pallas"
            else None
        )

        self._prefill = jax.jit(
            named_program(forward_prefill, program="prefill"),
            static_argnums=(1,),
        )
        # Prefix prefill needs KV only — skipping the LM head avoids a
        # [bucket, vocab] logits tensor on the admission critical path.
        self._prefill_kv = jax.jit(
            named_program(
                self._model.forward_prefill_kv, program="prefix_prefill_kv"
            ),
            static_argnums=(1,),
        )
        self._admit = jax.jit(
            named_program(
                _admit_impl, program="admit",
                prefix_impl=prefix_attn_impl,
                vocab_limit=self._vocab_limit,
                shardings=shardings,
            ),
            static_argnums=(1, 26),
            donate_argnums=(7, 8, 11, 12, 13, 14, 15, 16),
        )
        self._chunk = jax.jit(
            named_program(
                _decode_chunk_impl, program="decode_chunk",
                shmap=chunk_shmap,
                vocab_limit=self._vocab_limit,
                shardings=shardings,
            ),
            static_argnums=(1, 20, 21, 22),
            donate_argnums=(2, 3, 8, 9, 10, 11, 12),
        )
        # Fused on-device decode runtime (engine/fused/): the autoregressive
        # loop as ONE lax.while_loop program with early exit, on-device
        # sampling (greedy/temperature/top-k), dense-table grammar and
        # per-slot stop detection — the host syncs once per harvest chunk.
        # step_fused/decode_fused route here and FALL BACK to the sparse
        # chunked path whenever the grammar can't export a dense table
        # (size cap). Open speculative rounds do NOT gate it: a spec
        # stream deactivates only its own slot (_Request.external).
        self.fused_decode = bool(fused_decode)
        self.top_k = int(top_k)
        from k8s_llm_scheduler_tpu.engine.fused import (
            DENSE_TABLE_MAX_BYTES,
            fused_decode_chunk_impl,
        )

        self.fused_table_bytes = (
            int(fused_table_bytes)
            if fused_table_bytes is not None
            else DENSE_TABLE_MAX_BYTES
        )
        self._fused_chunk = jax.jit(
            named_program(
                fused_decode_chunk_impl, program="fused_decode_chunk",
                shmap=chunk_shmap,
                vocab_limit=self._vocab_limit,
                shardings=shardings,
            ),
            static_argnums=(1, 19, 20, 21, 22),
            donate_argnums=(2, 3, 8, 9, 10, 11, 12),
        )
        # Unconstrained fused chunks never read the table; a [1,1] dummy
        # keeps the traced shape stable. The real table is built lazily on
        # first constrained fused use (set_grammar resets it).
        self._fused_dummy = jnp.full((1, 1), -1, dtype=jnp.int32)
        self._fused_next_d: jax.Array | None = None
        self._fused_unsupported = False
        self._dfa: DecisionDFA | None = None
        self._wave = jax.jit(
            named_program(
                _wave_impl, program="wave",
                prefix_impl=prefix_attn_impl,
                vocab_limit=self._vocab_limit,
                ragged_decode=(decode_matmul == "ragged"),
                shardings=shardings,
            ),
            static_argnums=(1, 17, 18, 19, 20),
        )
        # The LCP seed of a chunked prefix prefill: the first `reuse` tokens'
        # cache copied from the seeding entry into the new buffers, `reuse`
        # traced (one program for each pair of buffer lengths, not one for
        # each reuse length; _warm_lcp_seed).
        self._lcp_seed = jax.jit(
            named_program(_lcp_seed_impl, program="lcp_seed", shardings=shardings),
            donate_argnums=(0,),
        )
        self._lcp_caps: set[int] = set()
        # Chunked long-prefix prefill reuses the dense cascade directly.
        self._suffix_dense = jax.jit(
            named_program(
                self._model.forward_prefill_suffix_dense, program="suffix_dense",
                prefix_impl=prefix_attn_impl,
            ),
            static_argnums=(1,),
        )
        # Block width for grammar-accelerated wave decoding: each iteration
        # consumes 1 sampled + up to wave_block-1 forced tokens. 24 packs
        # the longest JSON-skeleton span into one iteration (9 model calls
        # per decision vs 12 at width 16); the extra per-call width is
        # cheap next to a model call's fixed cost of reading the weights.
        self.wave_block = 24
        self._grammar_wave_iters: int | None = None
        # Wave-geometry bookkeeping for prewarming: every submit_wave
        # records its compiled variant key and the (bucket, max_new) shape
        # it served, so prewarm_wave_siblings can compile the row-bucket
        # variants a straggler-timing ragged wave would otherwise hit cold
        # mid-burst (a measured 5.1s jit stall class).
        self._wave_compiled: set[tuple] = set()
        self._wave_shapes_seen: set[tuple[int, int]] = set()
        # Geometries whose prewarm dispatch raised: excluded from the
        # backlog so a persistent failure can't wedge callers polling
        # wave_prewarm_backlog()==0 (a real wave still compiles the
        # variant on demand if it is ever actually needed).
        self._wave_prewarm_failed: set[tuple] = set()

        # Grammar tables (sparse, vocab-independent; content swaps without
        # recompiling for a same-K grammar — see SparseDFATables).
        self._constrained = False
        self._sp_tokens = jnp.full((1, 1), -1, dtype=jnp.int32)
        self._sp_next = jnp.zeros((1, 1), dtype=jnp.int32)
        self._forced = jnp.full((1,), -1, dtype=jnp.int32)
        self._forced_next = jnp.zeros((1,), dtype=jnp.int32)
        self._done_state = jnp.int32(-1)  # unconstrained: nothing reaches done
        self._dfa_start = 0
        self.set_grammar(None)

        # Shared-prefix store. The engine holds ONE active prefix at a time
        # (all in-flight slots decode against it); recent prefixes stay
        # cached on device keyed by their token ids.
        self._prefix: _PrefixKV | None = None
        self._prefix_cache: OrderedDict[tuple[int, ...], _PrefixKV] = OrderedDict()
        self._empty_prefix: _PrefixKV | None = None
        # Pinned prefix entries (admission/pinned.PinnedPrefixManager):
        # keys the byte-pressure evictor must skip — a pinned cluster
        # snapshot's KV is the base every delta-encoded prompt LCP-seeds
        # from, and evicting it between bursts re-pays the full cluster
        # prefill the pin exists to amortize. `prefix_epoch` stamps pin
        # handles: swap_params bumps it, so a pin taken under old weights
        # can NEVER serve a post-swap decision (the manager checks
        # pin_alive before trusting a handle).
        self._pinned_prefix_keys: set[tuple[int, ...]] = set()
        self.prefix_epoch = 0

        # Packed chunked admission (engine/admission/): chunk width for
        # the block-diagonal packed prefill; the jit is built lazily on
        # first admit_packed (the impl module imports this one's sampling
        # helpers). Piggybacked decode emissions dispatched between pack
        # chunks park here until the next step() harvest syncs them.
        self.admission_chunk_tokens = int(admission_chunk_tokens)
        self._packed_admit = None
        self._pending_emissions: list[jax.Array] = []

        # Speculative-decoding subsystem (spec/decoder.py), attached after
        # construction via attach_spec(): generate() routes through it when
        # present. None = plain decode only.
        self.spec = None

        # Continuous wave profiler (observability/profiler.py), attached
        # via attach_profiler(): submit_wave/harvest_wave fence their
        # dispatch and sync boundaries into it. None = one per-wave None
        # check, nothing else.
        self.profiler = None

        self._rng = jax.random.PRNGKey(rng_seed)
        self._req_counter = 0
        self._by_slot: dict[int, _Request] = {}
        # Device-resident per-slot decode state (+ post-sync host mirrors).
        # Row M (one past the real slots) is the TRASH row: admission-padding
        # rows scatter there and it never activates, so admission batches can
        # be narrower than max_slots without per-row masking games.
        M = max_slots + 1
        self._tok_d = jnp.zeros(M, dtype=jnp.int32)
        self._pos_d = jnp.zeros(M, dtype=jnp.int32)
        self._act_d = jnp.zeros(M, dtype=bool)
        self._st_d = jnp.zeros(M, dtype=jnp.int32)
        self._budget_d = jnp.zeros(M, dtype=jnp.int32)
        self._first_d = jnp.zeros(M, dtype=jnp.int32)
        self._act_np = np.zeros(M, dtype=bool)      # post-sync mirror
        self._budget_np = np.zeros(M, dtype=np.int32)
        # Page tables padded with the trash row (all-zeros -> scratch page).
        self._tables_src: jax.Array | None = None
        self._tables_padded: jax.Array | None = None
        self.stats = {
            "requests": 0,
            "completed": 0,
            "prefill_tokens": 0,
            # wave rows x the width the wave's suffix prefill was compiled
            # for: what the device computes where prefill_tokens counts
            # the real suffix tokens (padding rows and columns included)
            "suffix_tokens_computed": 0,
            "prefix_prefills": 0,
            "prefix_hits": 0,
            "decode_tokens": 0,
            "chunks": 0,
            "prefills": 0,
            "syncs": 0,
            # Pre-initialized (not lazily inserted on first use): the
            # telemetry sampler copies this dict from another thread, and
            # a first-time key insert resizing it mid-iteration would
            # raise "dictionary changed size during iteration" and drop
            # the sample covering exactly that event (e.g. the first hot
            # weight swap's HBM/occupancy transient).
            "waves": 0,
            "wave_model_calls": 0,
            # the model family's device counters, summed inside the wave
            # program and fetched with its tokens (models/mla_moe.py
            # COUNTERS: expert load; none for the dense family)
            **{name: 0 for name in self._model.COUNTERS},
            # wave rows seeded from a prefix's per-sequence state (a
            # family that has one: models/gdn_moe.py)
            **({"state_seeds": 0} if self._stateful else {}),
            "wave_prewarms": 0,
            "wave_prewarm_failures": 0,
            "prefix_reused_tokens": 0,
            "weight_swaps": 0,
            "packed_admissions": 0,
            "packed_prompts": 0,
            "pack_chunks": 0,
            "piggyback_chunks": 0,
            "pinned_prefixes": 0,
            "pin_evictions": 0,
            "fused_chunks": 0,
            "fused_steps": 0,
            "fused_fallbacks": 0,
            # Every XLA dispatch this engine issues on a serving path
            # (admission, decode chunks, waves, prefix prefills, packed
            # admission). The profiler exports its delta over a window
            # of completions as dispatches_per_decision.
            "dispatches": 0,
        }
        # Decision-flow books for the dispatches_per_decision gauge:
        # deltas since the last completed decision were booked.
        self._flow_dispatches_last = 0
        self._flow_completed_last = 0

    # ------------------------------------------------------------- grammar
    def set_grammar(self, dfa: DecisionDFA | None) -> None:
        """Install (or clear) the decision grammar as SPARSE device tables.

        States pad to DFA_STATE_CAPACITY and the K axis to a bucket
        (constrained.py sparse_tables), so same-structure grammars (every
        cluster snapshot's node-name set) reuse one compiled program.
        Unconstrained mode samples the full vocab minus pad — pad is the
        idle-slot emission sentinel and must never be sampleable, or
        emitted pads would be dropped from output and max_new_tokens
        accounting (generate() could spin forever on a pad-argmaxing
        model)."""
        # Fused-runtime table state resets with the grammar: the dense
        # table is built lazily on the first fused chunk (engine/fused/
        # tables.py caches per DFA, so reinstalls of a cached grammar
        # re-upload without re-deriving).
        self._dfa = dfa
        self._fused_next_d = None
        self._fused_unsupported = False
        if dfa is None:
            self._constrained = False
            self._sp_tokens = jnp.full((1, 1), -1, dtype=jnp.int32)
            self._sp_next = jnp.zeros((1, 1), dtype=jnp.int32)
            self._forced = jnp.full((1,), -1, dtype=jnp.int32)
            self._forced_next = jnp.zeros((1,), dtype=jnp.int32)
            self._done_state = jnp.int32(-1)
            self._dfa_start = 0
            self._grammar_wave_iters = None
            return
        # Capacity buckets by powers of two above the floor: a 256-node
        # cluster's grammar (~2.5k states) fits the floor; a 500+-node or
        # long-name grammar doubles the bucket (one extra compile per
        # bucket) instead of hard-failing.
        cap = self.DFA_STATE_CAPACITY
        while cap < dfa.n_states:
            cap *= 2
        t = sparse_tables(dfa)
        K = t.k_width
        sp_tokens = np.full((cap, K), -1, dtype=np.int32)
        sp_next = np.zeros((cap, K), dtype=np.int32)
        forced = np.full((cap,), -1, dtype=np.int32)
        forced_next = np.zeros((cap,), dtype=np.int32)
        sp_tokens[: t.n_states] = t.sp_tokens
        sp_next[: t.n_states] = t.sp_next
        forced[: t.n_states] = t.forced
        forced_next[: t.n_states] = t.forced_next
        self._constrained = True
        self._sp_tokens = jnp.asarray(sp_tokens)
        self._sp_next = jnp.asarray(sp_next)
        self._forced = jnp.asarray(forced)
        self._forced_next = jnp.asarray(forced_next)
        self._done_state = jnp.int32(dfa.done_state)
        self._dfa_start = dfa.start_state
        self._grammar_wave_iters = wave_iterations(dfa, self.wave_block)

    # -------------------------------------------------------------- prefix
    def _place_prefix(self, *arrays: jax.Array) -> tuple[jax.Array, ...]:
        """Pin a prefix cache tuple to the tp plane's head-sharded
        layout (no-op off-mesh). Every _PrefixKV the engine caches or
        pins goes through here, so pin/evict/truncate/rollback all
        operate on mesh-resident buffers and the jitted programs'
        prefix constraints are placement-true from the first dispatch."""
        if self.plane is None:
            return arrays
        return tuple(self.plane.place_prefix(a) for a in arrays)

    def _prefix_buffers(self, cap: int) -> tuple[jax.Array, ...]:
        """Zeroed cache tuple for `cap` prefix tokens: one array per member
        of the model's per-token cache, [L, cap, *token shape]."""
        return tuple(
            jnp.zeros((self._model.cache_layers(self.cfg), cap, *shape), dtype=self.cfg.dtype)
            for shape in self._model.cache_token_shapes(self.cfg)
        )

    def _zero_state(self) -> tuple[jax.Array, ...]:
        """The per-sequence state before any token, one array per member,
        [state layers, *shape]; () for a family that has none."""
        layers = self._model.state_layers(self.cfg)
        return tuple(
            jnp.zeros((layers, *shape), dtype)
            for shape, dtype in self._model.state_shapes(self.cfg)
        )

    def _prefix_state_kw(self, prefix: _PrefixKV) -> dict:
        """The wave program's `prefix_state` argument, for a family that has
        a state alone (the others' call stays as it was): the prefix's own
        arrays, read by every wave from this pin and never donated, each row
        seeded with a copy."""
        return {"prefix_state": prefix.state} if self._stateful else {}

    def _get_empty_prefix(self) -> _PrefixKV:
        if self._empty_prefix is None:
            self._empty_prefix = _PrefixKV(
                kv=self._place_prefix(*self._prefix_buffers(self.kv.page_size)),
                length=0,
                token_ids=(),
                state=self._zero_state(),
            )
        return self._empty_prefix

    def set_prefix(self, prompt_ids: list[int] | None) -> None:
        """Install the burst-shared prompt prefix (prefilling it once if not
        cached on device). Requires the engine to be drained — all in-flight
        slots decode against the same prefix buffer.

        Prefixes up to the largest prefill bucket run as ONE full-attention
        prefill; longer ones (the 256-node cluster-state prompt is ~40k
        byte-tokens, SURVEY §5 long-context) take the CHUNKED path — see
        _prefill_prefix_chunked."""
        if self._by_slot:
            raise RuntimeError("cannot switch prefix with requests in flight")
        if not prompt_ids:
            self._prefix = self._get_empty_prefix()
            return
        with spans.span("prefix_prefill", layer="engine", tokens=len(prompt_ids)) as _sp:
            self._set_prefix_inner(prompt_ids, _sp)

    def _set_prefix_inner(
        self, prompt_ids: list[int], _sp, activate: bool = True
    ) -> None:
        key = tuple(prompt_ids)
        cached = self._prefix_cache.get(key)
        if cached is not None:
            self._prefix_cache.move_to_end(key)
            if activate:
                self._prefix = cached
            self.stats["prefix_hits"] += 1
            if _sp is not None:
                _sp.attrs["cached"] = True
            if self.profiler is not None:
                self.profiler.note_prefix_prefill(0, cached.length)
            return
        n = len(prompt_ids)
        if n > self.cfg.max_seq_len:
            # Advisory, not fatal: RoPE extrapolates beyond the trained
            # window (quality degrades past it, correctness does not).
            logger.warning(
                "prefix of %d tokens exceeds model max_seq_len %d; "
                "quality may degrade", n, self.cfg.max_seq_len,
            )
        # Chunked path whenever the prompt exceeds one chunk — not just the
        # largest bucket: single-shot prefill materializes O(S^2 x heads)
        # attention scores (8.6 GB at 8B scale for an 8k prompt), while the
        # chunked cascade is bounded at O(prefix_chunk x S).
        prefilled = n
        n_cache = len(self._model.cache_token_shapes(self.cfg))
        if n > min(self.prefix_chunk, self.prefill_buckets[-1]):
            # a cached prefix's STATE exists at its own length only, not at
            # the common length: such a family prefills the whole prefix
            seed = None if self._stateful else self._best_lcp_seed(key)
            with spans.thread_span("dispatch", layer="engine"):
                kv, state = self._prefill_prefix_chunked(prompt_ids, seed=seed)
                kv = self._place_prefix(*kv)
            if seed is not None:
                prefilled = n - seed[1]  # reused tokens were not re-prefilled
            pfx = _PrefixKV(kv=kv, length=n, token_ids=key, state=state)
        else:
            bucket = self._bucket_for(n)
            pad = self.tokenizer.pad_id
            tokens = np.full((1, bucket), pad, dtype=np.int32)
            tokens[0, :n] = prompt_ids
            # blocks while the device's queue is full, as in submit_wave
            with spans.thread_span("dispatch", layer="engine"):
                _, *cache = self._prefill_kv(
                    self.params, self.cfg, jnp.asarray(tokens), jnp.asarray([n])
                )
                kv = self._place_prefix(*(a[:, 0] for a in cache[:n_cache]))
                # the state after the n real tokens of the padded bucket
                state = tuple(a[:, 0] for a in cache[n_cache]) if self._stateful else ()
            pfx = _PrefixKV(kv=kv, length=n, token_ids=key, state=state)
        self._prefix_cache[key] = pfx

        total = sum(p.nbytes for p in self._prefix_cache.values())
        if total > self.PREFIX_CACHE_BYTES and len(self._prefix_cache) > 1:
            # Oldest-first, but PINNED entries are skipped: a pinned
            # snapshot's KV is what every delta-encoded prompt LCP-seeds
            # from; evicting it between bursts re-pays the full cluster
            # prefill. If pins alone exceed the budget they are kept —
            # holding those bytes is exactly what pinning means
            # (PinnedPrefixManager bounds the pin count).
            for k in list(self._prefix_cache):
                if total <= self.PREFIX_CACHE_BYTES or len(self._prefix_cache) <= 1:
                    break
                if k == key or k in self._pinned_prefix_keys:
                    continue
                evicted = self._prefix_cache.pop(k)
                total -= evicted.nbytes
        if activate:
            self._prefix = pfx
        self.stats["prefix_prefills"] += 1
        self.stats["dispatches"] += 1
        self.stats["prefill_tokens"] += prefilled
        if self.profiler is not None:
            self.profiler.note_prefix_prefill(prefilled, n)

    def pin_prefix(self, prompt_ids: list[int]) -> tuple[tuple[int, ...], int]:
        """Prefill (or cache-hit) `prompt_ids` as a PINNED prefix-cache
        entry WITHOUT making it the engine's active prefix.

        The pin is the delta-encoding anchor: a pinned cluster-snapshot
        prefix stays resident on device across bursts, exempt from
        byte-pressure eviction, so every later delta-extended prompt
        LCP-seeds from it and prefills only its delta tail
        (_best_lcp_seed / _prefill_prefix_chunked). Engine-owner thread
        only, like every dispatch path — but safe with requests in
        flight (the active prefix pointer is untouched).

        Returns (cache key, prefix_epoch). The epoch stamps the pin's
        weight generation: swap_params bumps it and clears the pin set,
        so callers must re-check pin_alive() before trusting a handle.
        """
        if not prompt_ids:
            raise ValueError("cannot pin an empty prefix")
        key = tuple(prompt_ids)
        with spans.span(
            "prefix_prefill", layer="engine", tokens=len(prompt_ids), pin=True
        ) as _sp:
            self._set_prefix_inner(prompt_ids, _sp, activate=False)
        if key not in self._pinned_prefix_keys:
            self._pinned_prefix_keys.add(key)
            self.stats["pinned_prefixes"] = (
                self.stats.get("pinned_prefixes", 0) + 1
            )
        return key, self.prefix_epoch

    def unpin_prefix(self, key: tuple[int, ...]) -> None:
        """Release a pin (the entry becomes ordinary-evictable; its KV
        stays cached until byte pressure claims it)."""
        if key in self._pinned_prefix_keys:
            self._pinned_prefix_keys.discard(key)
            self.stats["pin_evictions"] = (
                self.stats.get("pin_evictions", 0) + 1
            )

    def pin_alive(self, key: tuple[int, ...], epoch: int) -> bool:
        """True iff the pin still serves: taken under the CURRENT weights
        (epoch matches — a hot swap bumps prefix_epoch) and its KV entry
        is still resident and pinned."""
        return (
            epoch == self.prefix_epoch
            and key in self._pinned_prefix_keys
            and key in self._prefix_cache
        )

    def export_prefix_kv(
        self, key: tuple[int, ...]
    ) -> tuple[jax.Array, ...] | None:
        """Hand out the cached KV stack for `key` (the shared prefix-KV
        plane exports pinned snapshots through here, fleet/kvplane/).

        Ships the FULL capacity buffer — bucket padding included — so an
        adopting peer installs bytes identical to this engine's own
        entry and no novel pad-shape reaches its jitted programs.
        Returns None when the entry is not resident."""
        self._require_stateless("export_prefix_kv() (the shared prefix-KV plane)")
        pfx = self._prefix_cache.get(tuple(key))
        if pfx is None:
            return None
        return pfx.kv

    def adopt_prefix_pages(
        self,
        prompt_ids: list[int],
        k: jax.Array,
        v: jax.Array,
    ) -> tuple[tuple[int, ...], int]:
        """Install a peer replica's exported prefix KV as a PINNED cache
        entry — pin_prefix's outcome without paying its prefill (the
        adopt-remote-pages seam of the shared prefix-KV plane).

        The buffers must carry this engine's exact cache geometry
        ([the model's cache layers, cap >= len(prompt_ids), *its per-token shape]:
        n_kv_heads, head_dim for the dense family);
        anything else is refused here rather than at decode time. Host
        arrays are placed through _place_prefix, so on a tp mesh the
        adopted pages land head-sharded exactly like a local prefill's.

        Returns (cache key, prefix_epoch) — pin_prefix's contract, and
        the same staleness rules apply (pin_alive / swap_params)."""
        self._require_stateless("adopt_prefix_pages() (the shared prefix-KV plane)")
        if not prompt_ids:
            raise ValueError("cannot adopt an empty prefix")
        key = tuple(prompt_ids)
        n = len(key)
        kshape, vshape = tuple(k.shape), tuple(v.shape)
        token_shapes = self._model.cache_token_shapes(self.cfg)
        layers = self._model.cache_layers(self.cfg)
        if (
            kshape[1:2] != vshape[1:2]
            or kshape[1] < n
            or any(
                (shape[0], *shape[2:]) != (layers, *want)
                for shape, want in zip((kshape, vshape), token_shapes)
            )
        ):
            raise ValueError(
                f"adopted prefix pages have shape k={kshape} v={vshape}; "
                f"this engine needs [L={layers}, cap>={n}, "
                f"*{token_shapes[0]}] and [.., *{token_shapes[1]}]"
            )
        kv = self._place_prefix(
            jnp.asarray(k, dtype=self.cfg.dtype),
            jnp.asarray(v, dtype=self.cfg.dtype),
        )
        self._prefix_cache[key] = _PrefixKV(kv=kv, length=n, token_ids=key)
        self._prefix_cache.move_to_end(key)
        if key not in self._pinned_prefix_keys:
            self._pinned_prefix_keys.add(key)
            self.stats["pinned_prefixes"] = (
                self.stats.get("pinned_prefixes", 0) + 1
            )
        self.stats["adopted_prefixes"] = (
            self.stats.get("adopted_prefixes", 0) + 1
        )
        return key, self.prefix_epoch

    def _best_lcp_seed(
        self, key: tuple[int, ...]
    ) -> tuple[tuple[jax.Array, ...], int] | None:
        """Find the cached prefix sharing the longest common token prefix
        with `key`: (its cache tuple, the reuse length).

        Cluster snapshots drift incrementally (a pod count here, a usage
        figure there), and causal attention makes the KV of every token
        BEFORE the first changed token bit-identical — so a new snapshot's
        prefix re-prefills only its changed tail. The prompt renders nodes
        in a stable order (core/prompt.py) precisely so this prefix stays
        long under drift. The reuse length is the exact LCP (the resume
        loop prefills from any offset); seeding is skipped below a small
        threshold where a fresh prefill is just as cheap."""
        chunk = min(self.prefix_chunk, self.prefill_buckets[-1])
        threshold = max(chunk // 2, 64)
        key_arr = np.asarray(key, dtype=np.int64)
        best: _PrefixKV | None = None
        best_reuse = 0
        for old_key, pfx in self._prefix_cache.items():
            m = min(len(old_key), len(key))
            if m < threshold:
                continue
            old_arr = np.asarray(old_key[:m], dtype=np.int64)  # graftlint: ok[device-sync-in-loop] — old_key is a host-side tuple of token ids (cache key), not a device value; no transfer happens
            mismatch = np.nonzero(old_arr != key_arr[:m])[0]
            lcp = int(mismatch[0]) if mismatch.size else m
            if lcp > best_reuse:
                best_reuse, best = lcp, pfx
        if best is None or best_reuse < threshold:
            return None
        return best.kv, best_reuse

    def _prefill_prefix_chunked(
        self,
        prompt_ids: list[int],
        seed: tuple[tuple[jax.Array, ...], int] | None = None,
    ) -> tuple[tuple[jax.Array, ...], tuple[jax.Array, ...]]:
        """Blockwise prefill for prefixes beyond the largest bucket.

        Processes the prompt in largest-bucket chunks; each chunk attends to
        the dense KV accumulated so far plus causally within itself (the
        same cascade attention the per-pod suffixes use), then appends its
        KV into the growing buffer. Memory stays O(chunk x prefix) per
        layer instead of O(prefix^2), which is what makes the 256-node /
        40k-token cluster prompt feasible on one chip.

        `seed` = (cache tuple, reuse_len) from _best_lcp_seed: the first
        reuse_len tokens' cache copies from the cached buffers and prefill
        starts there — incremental prefix caching for drifting cluster
        snapshots.

        A family with a per-sequence state is never seeded; its state is
        carried from chunk to chunk (a chunk is a one-row suffix call
        seeded from the state the chunks before it left).

        Returns (the cache tuple, each [L, cap, *token shape], cap a chunk
        multiple; the state after the n tokens, () for a family without).
        """
        chunk = min(self.prefix_chunk, self.prefill_buckets[-1])
        n = len(prompt_ids)
        # Always reserve one chunk of headroom beyond the rounded length:
        # an UNALIGNED LCP resume writes chunk-wide blocks from a non-chunk
        # start, so its last write spans past n — without headroom,
        # dynamic_update_slice CLAMPS the out-of-bounds start and silently
        # overwrites good copied KV with padding garbage. Reserving it
        # unconditionally (not just for unaligned resumes) keeps seeded and
        # fresh prefills of the same prompt length on ONE buffer shape, so
        # _suffix_dense/_wave/_admit/_chunk compile once per length bucket
        # instead of twice (a mid-burst jit-stall class).
        cap = -(-n // chunk) * chunk + chunk
        done = 0 if seed is None else seed[1]
        pad = self.tokenizer.pad_id
        bufs = self._prefix_buffers(cap)
        self._warm_lcp_seed(cap)
        if seed is not None:
            seed_kv, reuse = seed
            bufs = self._lcp_seed(bufs, seed_kv, jnp.int32(reuse))
            self.stats["prefix_reused_tokens"] = (
                self.stats.get("prefix_reused_tokens", 0) + reuse
            )
        n_cache = len(bufs)
        state = self._zero_state()
        for start in range(done, n, chunk):
            piece = prompt_ids[start : start + chunk]
            m = len(piece)
            tokens = np.full((1, chunk), pad, dtype=np.int32)
            tokens[0, :m] = piece
            out = self._suffix_dense(
                self.params, self.cfg,
                jnp.asarray(tokens), jnp.asarray([m], dtype=np.int32),
                *bufs, jnp.int32(done),
                **({"state": state} if self._stateful else {}),
            )
            if self._stateful:
                state = tuple(a[:, 0] for a in out[1 + n_cache])
            # each [L, 1, chunk, *token shape] -> append at `start`
            bufs = tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    buf, new[:, 0].astype(buf.dtype), start, axis=1
                )
                for buf, new in zip(bufs, out[1 : 1 + n_cache])
            )
            done += m
        return bufs, state

    def _warm_lcp_seed(self, cap: int) -> None:
        """The seed copy is one program for each pair of buffer lengths
        (new, seeding entry's): the first time a chunked prefix buffer
        `cap` long is made, run it once on zeros for `cap` against every
        length seen before, both ways, so that no pair first meets a
        seed mid-serving."""
        if self._stateful or cap in self._lcp_caps:
            return
        self._lcp_caps.add(cap)
        for other in sorted(self._lcp_caps):
            for a, b in {(cap, other), (other, cap)}:
                self._lcp_seed(self._prefix_buffers(a), self._prefix_buffers(b), jnp.int32(0))

    @property
    def prefix_len(self) -> int:
        return self._prefix.length if self._prefix else 0

    def _require_paged(self, path: str) -> None:
        """Refuse, before anything is traced, an entry point that runs the
        paged pool for a model family that has no paged forwards."""
        if not self.paged:
            what = getattr(self._model, "PAGED_MISSING",
                           "a per-sequence state" if self._stateful else "a latent cache")
            raise ValueError(
                f"{self.cfg.name}: {path} is not served — it runs the paged "
                f"KV pool, and {self._model_file} brings the decision wave's "
                f"forwards only ({what} in PagedKVCache / KVGeometry "
                f"is not written)"
            )

    def _require_stateless(self, path: str) -> None:
        """Refuse an entry point that hands a prefix out or takes one in as
        its cache tuple alone, for a family whose prefix is a cache AND a
        state."""
        if self._stateful:
            raise ValueError(
                f"{self.cfg.name}: {path} is not served — a pinned prefix of "
                f"{self._model_file} is its cache and the per-sequence state "
                f"its prefill left, and the plane ships (k, v) only "
                f"(fleet/kvplane/pages.py)"
            )

    # ------------------------------------------------------------ requests
    def _bucket_for(self, n: int) -> int:
        for bkt in self.prefill_buckets:
            if n <= bkt:
                return bkt
        raise ValueError(
            f"prompt of {n} tokens exceeds largest prefill bucket "
            f"{self.prefill_buckets[-1]}"
        )

    @property
    def free_slots(self) -> int:
        return self.max_slots - len(self._by_slot)

    def max_suffix_tokens(self, max_new_tokens: int) -> int:
        """Longest admissible prompt/suffix for the PAGED (add_requests/
        step) path — bounded by the page-table width and the largest
        prefill bucket. The wave path never touches pages, so it is
        bounded only by prefill_buckets[-1] (what engine/local.py
        pre-checks); callers of the paged path should pre-check against
        this so one oversized request fails alone instead of poisoning
        its admission batch."""
        by_pages = (
            self.kv.max_pages_per_seq * self.kv.page_size - (max_new_tokens + 1)
        )
        return min(by_pages, self.prefill_buckets[-1])

    def _padded_tables(self) -> jax.Array:
        """kv page tables + the all-zeros trash row, cached per table build."""
        src = self.kv.page_tables()
        if src is not self._tables_src:
            self._tables_src = src
            self._tables_padded = jnp.vstack(
                [src, jnp.zeros((1, src.shape[1]), dtype=src.dtype)]
            )
        return self._tables_padded

    @property
    def has_active(self) -> bool:
        return bool(self._by_slot)

    def add_request(self, prompt_ids: list[int], max_new_tokens: int = 200) -> int:
        """Single-request admission (tests, simple callers); see add_requests.

        max_new_tokens defaults to the reference's sampling cap
        (config.yaml:14)."""
        return self.add_requests([prompt_ids], max_new_tokens)[0]

    def add_requests(
        self,
        prompts: list[list[int]],
        max_new_tokens: int = 200,
    ) -> list[int]:
        """Admit a batch of requests in ONE device dispatch (no host sync).

        Each prompt is the per-request SUFFIX if a prefix is installed
        (set_prefix), else the whole prompt. All prompts pad to one shared
        bucket. Decoding starts at the next `step()` call.
        """
        self._require_paged("add_requests() (chunked continuous batching)")
        if not prompts:
            return []
        if any(not p for p in prompts):
            raise ValueError("empty prompt")
        if len(prompts) > self.free_slots:
            raise RuntimeError(
                f"no free slots for {len(prompts)} request(s) "
                f"({self.free_slots} free) — backpressure the caller"
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        prefix = self._prefix or self._get_empty_prefix()
        self._prefix = prefix

        ps = self.kv.page_size
        bucket = self._bucket_for(max(len(p) for p in prompts))
        n_blocks = bucket // ps
        pad = self.tokenizer.pad_id
        # Admission-row bucket: exactly 1 for single requests (generate,
        # trickle traffic — avoids max_slots x the prefill memory/compute),
        # else the full width. Two compiled programs per token bucket, and
        # the padding rows scatter into the trash row.
        R = 1 if len(prompts) == 1 else self.max_slots
        trash = self.max_slots

        tokens = np.full((R, bucket), pad, dtype=np.int32)
        suffix_lens = np.zeros(R, dtype=np.int32)
        page_ids = np.zeros((R, n_blocks), dtype=np.int32)
        slot_ids = np.full(R, trash, dtype=np.int32)
        new_budgets = np.zeros(R, dtype=np.int32)

        reqs: list[_Request] = []
        slots: list[int] = []
        try:
            for row, ids in enumerate(prompts):
                n = len(ids)
                slot = self.kv.allocate_slot(n, reserve_decode=max_new_tokens + 1)
                slots.append(slot)
                info_pages = self.kv.slot_pages(slot)
                used = self.kv.pages_needed(n)
                tokens[row, :n] = ids
                suffix_lens[row] = n
                slot_ids[row] = slot
                new_budgets[row] = max_new_tokens - 1
                for j in range(min(used, n_blocks)):
                    page_ids[row, j] = info_pages[j]
                req = _Request(
                    req_id=self._req_counter,
                    slot=slot,
                    prompt_len=n,
                    max_new_tokens=max_new_tokens,
                )
                self._req_counter += 1
                reqs.append(req)

            self._rng, sub = jax.random.split(self._rng)
            with spans.span(
                "prefill_dispatch", layer="engine",
                tokens=int(suffix_lens.sum()), requests=len(prompts),
            ):
                (
                    self.kv.k, self.kv.v,
                    self._tok_d, self._pos_d, self._act_d, self._st_d,
                    self._budget_d, self._first_d,
                ) = self._admit(
                self.params, self.cfg,
                jnp.asarray(tokens), jnp.asarray(suffix_lens),
                prefix.k, prefix.v, jnp.int32(prefix.length),
                self.kv.k, self.kv.v,
                jnp.asarray(page_ids), jnp.asarray(slot_ids),
                self._tok_d, self._pos_d, self._act_d, self._st_d,
                self._budget_d, self._first_d,
                jnp.asarray(new_budgets),
                self._sp_tokens, self._sp_next, self._done_state,
                jnp.int32(self.tokenizer.eos_id),
                jnp.int32(self.tokenizer.pad_id), jnp.int32(self._dfa_start),
                sub, jnp.float32(self.temperature), self._constrained,
            )
        except Exception:
            # Roll back BOTH the allocation loop and the device dispatch:
            # these slots are not in _by_slot yet, so no later recovery path
            # (abort_all) could ever free them.
            for s in slots:
                self.kv.free_slot(s)
            raise
        for req in reqs:
            req.park_floor = len(self._pending_emissions)
            self._by_slot[req.slot] = req
            # Optimistic mirrors until the next sync tells the truth.
            self._act_np[req.slot] = True
            self._budget_np[req.slot] = max_new_tokens - 1
        self.stats["requests"] += len(reqs)
        self.stats["prefills"] += 1
        self.stats["dispatches"] += 1
        self.stats["prefill_tokens"] += int(suffix_lens.sum())
        return [r.req_id for r in reqs]

    # -------------------------------------------------- packed admission
    def admit_packed(
        self,
        prompts: list[list[int]],
        max_new_tokens: int = 200,
        piggyback_decode: bool = True,
    ) -> list[int]:
        """Admit a batch via the ADMISSION PLANE: packed chunked prefill.

        Where add_requests pads every prompt to one shared bucket (R x
        bucket prefill compute for maybe a fifth that many real tokens),
        this packs the prompts into ONE token stream cut into fixed
        `admission_chunk_tokens` chunks with block-diagonal attention
        (engine/admission/packer.py + models/llama.forward_prefill_packed)
        — prefill compute scales with the REAL token count. Between
        chunks, in-flight decode slots advance by one fused decode chunk
        (SARATHI piggybacking): a long admission burst never stalls
        decode for its whole prefill, and prompts that complete mid-pack
        start decoding on the very next piggybacked chunk. Everything
        dispatches without a host sync; the next step() harvests.

        Decoding is token-identical to admitting the same prompts via
        add_requests or serially via generate() under greedy decoding —
        the block-diagonal mask computes exactly the serial attention
        (test-pinned, tests/test_admission.py).
        """
        self._require_paged("admit_packed() (packed admission)")
        if not prompts:
            return []
        if any(not p for p in prompts):
            raise ValueError("empty prompt")
        if len(prompts) > self.free_slots:
            raise RuntimeError(
                f"no free slots for {len(prompts)} request(s) "
                f"({self.free_slots} free) — backpressure the caller"
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        limit = self.max_suffix_tokens(max_new_tokens)
        for ids in prompts:
            if len(ids) > limit:
                raise ValueError(
                    f"prompt of {len(ids)} tokens exceeds the paged "
                    f"admission limit {limit}"
                )
        prof = self.profiler
        t0 = time.perf_counter() if prof is not None else 0.0
        chunk_prefill_s = 0.0
        piggyback_s = 0.0
        prefix = self._prefix or self._get_empty_prefix()
        self._prefix = prefix
        # Parked arrays that predate this admission belong to previous
        # slot occupants — this pack's requests must not book them
        # (park_floor; the pack's OWN piggyback parks stay bookable).
        park_floor0 = len(self._pending_emissions)

        from k8s_llm_scheduler_tpu.engine.admission.packer import pack_prompts

        if self._packed_admit is None:
            # Lazy: admission/chunked.py imports this module's sampling
            # helpers, so the jit is built on first use instead of at
            # import time (no cycle, no cost for engines that never pack).
            from k8s_llm_scheduler_tpu.engine.admission.chunked import (
                packed_admit_step,
            )

            self._packed_admit = jax.jit(
                named_program(
                    packed_admit_step, program="packed_admit",
                    prefix_impl=self.prefix_attn_impl,
                    vocab_limit=self._vocab_limit,
                    shardings=self._shardings,
                ),
                static_argnums=(1, 35),
                donate_argnums=(8, 9, 10, 12, 13, 21, 22, 23, 24, 25, 26),
            )

        C = self.admission_chunk_tokens
        plan = pack_prompts(prompts, C, self.tokenizer.pad_id)
        # Carry capacity buckets by powers of two over the chunk count so
        # pack sizes share compiled variants (log2 many, not one per size).
        cap_chunks = 1
        while cap_chunks < plan.n_chunks:
            cap_chunks *= 2
        CAP = cap_chunks * C
        E = self.max_slots  # ends-per-chunk bucket (a pack <= max_slots)
        ps = self.kv.page_size
        trash = self.max_slots

        carry_k = jnp.zeros(
            (self.cfg.n_layers, CAP, self.cfg.n_kv_heads, self.cfg.head_dim),
            dtype=self.cfg.dtype,
        )
        carry_v = jnp.zeros_like(carry_k)
        carry_seg = jnp.full((CAP,), -1, dtype=jnp.int32)

        slots: list[int] = []
        ended = 0
        try:
            slot_pages: list[list[int]] = []
            for ids in prompts:
                slot = self.kv.allocate_slot(
                    len(ids), reserve_decode=max_new_tokens + 1
                )
                slots.append(slot)
                slot_pages.append(self.kv.slot_pages(slot))
            for ci, chunk in enumerate(plan.chunks):
                page_ids = np.zeros(C, dtype=np.int32)
                offs = np.zeros(C, dtype=np.int32)
                for i in range(chunk.n_tokens):
                    s = int(chunk.seg[i])
                    p = int(chunk.positions[i])
                    page_ids[i] = slot_pages[s][p // ps]
                    offs[i] = p % ps
                end_idx = np.zeros(E, dtype=np.int32)
                end_slots = np.full(E, trash, dtype=np.int32)
                end_valid = np.zeros(E, dtype=bool)
                end_pos = np.zeros(E, dtype=np.int32)
                end_budgets = np.zeros(E, dtype=np.int32)
                for row, end in enumerate(chunk.ends):
                    end_idx[row] = end.index
                    end_slots[row] = slots[end.prompt]
                    end_valid[row] = True
                    end_pos[row] = prefix.length + plan.prompt_lens[end.prompt]
                    end_budgets[row] = max_new_tokens - 1
                positions = chunk.positions + np.int32(prefix.length)
                self._rng, sub = jax.random.split(self._rng)
                t_d = time.perf_counter() if prof is not None else 0.0
                (
                    carry_k, carry_v, carry_seg,
                    self.kv.k, self.kv.v,
                    self._tok_d, self._pos_d, self._act_d, self._st_d,
                    self._budget_d, self._first_d,
                ) = self._packed_admit(
                    self.params, self.cfg,
                    jnp.asarray(chunk.tokens), jnp.asarray(chunk.seg),
                    jnp.asarray(positions),
                    prefix.k, prefix.v, jnp.int32(prefix.length),
                    carry_k, carry_v, carry_seg, jnp.int32(ci * C),  # graftlint: ok[jit-donated-reuse] — read and rebound by the SAME multi-line call statement (the tuple-unpack above); each iteration passes the previous dispatch's returned buffers
                    self.kv.k, self.kv.v,
                    jnp.asarray(page_ids), jnp.asarray(offs),
                    jnp.asarray(end_idx), jnp.asarray(end_slots),
                    jnp.asarray(end_valid), jnp.asarray(end_pos),
                    jnp.asarray(end_budgets),
                    self._tok_d, self._pos_d, self._act_d, self._st_d,
                    self._budget_d, self._first_d,
                    self._sp_tokens, self._sp_next, self._done_state,
                    jnp.int32(self.tokenizer.eos_id),
                    jnp.int32(self.tokenizer.pad_id),
                    jnp.int32(self._dfa_start),
                    sub, jnp.float32(self.temperature), self._constrained,
                )
                if prof is not None:
                    chunk_prefill_s += time.perf_counter() - t_d
                self.stats["pack_chunks"] += 1
                self.stats["dispatches"] += 1
                ended += len(chunk.ends)
                # SARATHI piggyback: between prefill chunks, every
                # in-flight decode slot (earlier requests AND pack
                # prompts that already completed) advances one fused
                # decode chunk — dispatch only, still no host sync.
                if piggyback_decode and ci + 1 < plan.n_chunks and (
                    self._by_slot or ended
                ):
                    t_d = time.perf_counter() if prof is not None else 0.0
                    self._pending_emissions.append(
                        self._chunk_dispatch(prefix)
                    )
                    self.stats["piggyback_chunks"] += 1
                    if prof is not None:
                        piggyback_s += time.perf_counter() - t_d
        except Exception:
            # Roll back the allocation loop: these slots are not in
            # _by_slot yet, so no later recovery path could free them.
            # Device-side decode state must roll back WITH the pages: a
            # prompt that ended in an already-dispatched chunk scattered
            # act=True into its slot, and a ghost-active freed slot would
            # decode garbage into whichever request reuses it next.
            for s in slots:
                self.kv.free_slot(s)
            if slots:
                idx = jnp.asarray(slots)
                self._act_d = self._act_d.at[idx].set(False)
                self._budget_d = self._budget_d.at[idx].set(0)
                self._act_np[slots] = False
                self._budget_np[slots] = 0
            if not self._by_slot:
                # No pre-existing requests: any piggybacked emissions
                # belong to the failed pack's freed slots — a future
                # request reusing a slot must never inherit them. (With
                # requests in flight they stay: their decode genuinely
                # advanced and the next step() harvests it.)
                self._pending_emissions = []
            raise
        reqs: list[_Request] = []
        for ids, slot in zip(prompts, slots):
            req = _Request(
                req_id=self._req_counter,
                slot=slot,
                prompt_len=len(ids),
                max_new_tokens=max_new_tokens,
            )
            self._req_counter += 1
            reqs.append(req)
            req.park_floor = park_floor0
            self._by_slot[slot] = req
            # Optimistic mirrors until the next sync tells the truth.
            self._act_np[slot] = True
            self._budget_np[slot] = max_new_tokens - 1
        self.stats["requests"] += len(reqs)
        self.stats["prefills"] += 1
        self.stats["dispatches"] += 1
        self.stats["prefill_tokens"] += plan.total_tokens
        self.stats["packed_admissions"] += 1
        self.stats["packed_prompts"] += len(prompts)
        if prof is not None:
            prof.on_pack(
                wall_s=time.perf_counter() - t0,
                chunk_prefill_s=chunk_prefill_s,
                piggyback_s=piggyback_s,
                n_prompts=len(prompts),
                tokens=plan.total_tokens,
                chunks=plan.n_chunks,
            )
        return [r.req_id for r in reqs]

    # ---------------------------------------------------------------- wave
    def _wave_geometry(
        self, n_prompts: int, max_new_tokens: int
    ) -> tuple[int, int, int]:
        """(R, n_iters, F) for a wave of `n_prompts`.

        TWO row buckets: half width and full width. Wave compute scales
        with R (every padding row still runs masked through the model), so
        a burst whose leaders fit the half bucket — the common case — pays
        half the prefill/decode; exactly two buckets bounds the
        compiled-variant count. With a grammar, block decoding needs only
        wave_iterations(dfa) model calls (forced runs are free); without
        one, every token is a choice (F=1, one per iteration). n_iters is
        bucketed to multiples of 4 to bound compile variants further."""
        half = self.max_slots // 2
        R = half if 0 < n_prompts <= half else self.max_slots
        if self._constrained and self._grammar_wave_iters is not None:
            F = self.wave_block
            n_iters = min(self._grammar_wave_iters, max_new_tokens)
        else:
            F = 1
            n_iters = max_new_tokens
        n_iters = max(4, -(-n_iters // 4) * 4)
        return R, n_iters, F

    def _wave_key(
        self, R: int, bucket: int, n_iters: int, F: int, max_new: int
    ) -> tuple:
        """Identity of one compiled _wave variant: everything that changes
        the traced program's shapes/statics. Prefix buffer length and
        grammar table shapes are included — a same-R wave against a longer
        prefix or a wider DFA bucket is a different executable."""
        prefix = self._prefix or self._get_empty_prefix()
        return (
            R, bucket, n_iters, F, max_new,
            prefix.k.shape[1], self._sp_tokens.shape, self._constrained,
        )

    def wave_prewarm_backlog(self) -> int:
        """Number of sibling wave geometries not yet compiled (read-only;
        safe to poll from other threads)."""
        return len(self._missing_wave_siblings())

    def _missing_wave_siblings(self) -> list[tuple[int, int, int]]:
        """(n_prompts, bucket, max_new) probes for wave variants adjacent
        to ones already used: BOTH row buckets at every seen (suffix
        bucket, budget). A burst normally runs full-R waves, then one
        straggler forms a half-R ragged tail — that variant must not
        compile mid-burst."""
        out = []
        # list(): submit_wave (engine-owner thread) mutates the set while
        # bench/monitors poll the backlog from other threads — iterating
        # the live set would intermittently raise RuntimeError
        for bucket, max_new in list(self._wave_shapes_seen):
            for n_prompts in (1, self.max_slots):
                R, n_iters, F = self._wave_geometry(n_prompts, max_new)
                key = self._wave_key(R, bucket, n_iters, F, max_new)
                if (
                    key not in self._wave_compiled
                    and key not in self._wave_prewarm_failed
                ):
                    out.append((n_prompts, bucket, max_new))
        return out

    def prewarm_wave_siblings(self, limit: int | None = None) -> int:
        """Compile up to `limit` missing sibling wave geometries by
        dispatching one dummy wave each (row 0 holds a single real token;
        the rest are padding — with a grammar the while-loop early-exits
        after one short decision, so the device cost is a fraction of a
        real wave; the jit compile is the point). Engine-owner thread
        only, like every dispatch path. Results are discarded; the dummy
        wave shares nothing with slot state."""
        done = 0
        for n_prompts, bucket, max_new in self._missing_wave_siblings():
            if limit is not None and done >= limit:
                break
            R, n_iters, F = self._wave_geometry(n_prompts, max_new)
            prefix = self._prefix or self._get_empty_prefix()
            self._prefix = prefix
            pad = self.tokenizer.pad_id
            tokens = np.full((R, bucket), pad, dtype=np.int32)
            tokens[0, 0] = self.tokenizer.eos_id
            suffix_lens = np.zeros(R, dtype=np.int32)
            suffix_lens[0] = 1
            max_new_vec = np.zeros(R, dtype=np.int32)
            max_new_vec[0] = max_new
            self._rng, sub = jax.random.split(self._rng)
            key = self._wave_key(R, bucket, n_iters, F, max_new)
            try:
                self._wave(
                    self.params, self.cfg,
                    jnp.asarray(tokens), jnp.asarray(suffix_lens),
                    prefix.kv, jnp.int32(prefix.length),
                    jnp.asarray(max_new_vec),
                    self._sp_tokens, self._sp_next, self._forced,
                    self._forced_next, self._done_state,
                    jnp.int32(self.tokenizer.eos_id), jnp.int32(pad),
                    jnp.int32(self._dfa_start),
                    sub, jnp.float32(self.temperature),
                    n_iters, F, max_new, self._constrained,
                    **self._prefix_state_kw(prefix),
                )
            except Exception:
                # Record and move on: the backlog must drain even when a
                # dispatch fails (a wedged backlog would stall callers
                # waiting on wave_prewarm_backlog()==0 forever), and the
                # variant still compiles on demand if ever truly needed.
                self._wave_prewarm_failed.add(key)
                self.stats["wave_prewarm_failures"] = (
                    self.stats.get("wave_prewarm_failures", 0) + 1
                )
                logger.exception(
                    "wave prewarm dispatch failed for geometry %s", key
                )
                continue
            self._wave_compiled.add(key)
            self.stats["wave_prewarms"] = (
                self.stats.get("wave_prewarms", 0) + 1
            )
            done += 1
        return done

    def submit_wave(
        self, prompts: list[list[int]], max_new_tokens: int = 200
    ) -> WaveHandle:
        """Dispatch a whole batch's decode-to-completion as ONE device
        program and return WITHOUT syncing.

        The burst fast path (_wave_impl): suffix prefill + first token +
        full constrained decode fused into a single program that never
        touches the paged KV cache. Independent of slot state — it can run
        regardless of in-flight chunked requests (they share nothing but
        the prefix buffer and grammar tables, which the wave only reads).
        Every request finishes inside the wave: the device-side budget
        guarantees it even for an unconstrained grammar.

        Waves pipeline: submit several back-to-back, then harvest_wave in
        submission order — round-trip latency overlaps across waves.
        """
        if not prompts:
            raise ValueError("empty wave")
        if any(not p for p in prompts):
            raise ValueError("empty prompt")
        if len(prompts) > self.max_slots:
            raise RuntimeError(
                f"wave of {len(prompts)} exceeds max_slots={self.max_slots}"
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        bucket = self._bucket_for(max(len(p) for p in prompts))
        seq = self.stats.get("waves", 0) + 1
        with spans.thread_span(
            "submit_wave", layer="engine",
            wave=seq, rows=len(prompts), bucket=bucket,
        ):
            return self._submit_wave(prompts, max_new_tokens, bucket, seq)

    def _submit_wave(
        self, prompts: list[list[int]], max_new_tokens: int, bucket: int,
        seq: int,
    ) -> WaveHandle:
        prof = self.profiler
        # Dispatch fence OPENS before prompt packing: padding/copy work is
        # part of what the host pays per dispatch boundary.
        t_dispatch0 = time.perf_counter() if prof is not None else 0.0
        prefix = self._prefix or self._get_empty_prefix()
        self._prefix = prefix

        R, n_iters, F = self._wave_geometry(len(prompts), max_new_tokens)
        self._wave_shapes_seen.add((bucket, max_new_tokens))
        geo_key = self._wave_key(R, bucket, n_iters, F, max_new_tokens)
        cold_compile = geo_key not in self._wave_compiled
        pad = self.tokenizer.pad_id
        tokens = np.full((R, bucket), pad, dtype=np.int32)
        suffix_lens = np.zeros(R, dtype=np.int32)
        max_new = np.zeros(R, dtype=np.int32)
        for row, ids in enumerate(prompts):
            tokens[row, : len(ids)] = ids
            suffix_lens[row] = len(ids)
            max_new[row] = max_new_tokens

        # engine.dispatch: every call in here enqueues device work, and
        # each BLOCKS while the device's queue is full (measured on the
        # v5e, PERF.md §6 PR 25: with the device the bottleneck the worker
        # spends most of a wave's time right here). It is named apart so
        # that the rest of submit_wave reads as the host's own work.
        with spans.thread_span("dispatch", layer="engine", wave=seq):
            self._rng, sub = jax.random.split(self._rng)
            toks_d, _, iters_d, *counts_d = self._wave(
                self.params, self.cfg,
                jnp.asarray(tokens), jnp.asarray(suffix_lens),
                prefix.kv, jnp.int32(prefix.length),
                jnp.asarray(max_new),
                self._sp_tokens, self._sp_next, self._forced, self._forced_next,
                self._done_state,
                jnp.int32(self.tokenizer.eos_id), jnp.int32(pad),
                jnp.int32(self._dfa_start),
                sub, jnp.float32(self.temperature),
                n_iters, F, max_new_tokens, self._constrained,
                **self._prefix_state_kw(prefix),
            )
        # Recorded only AFTER a successful dispatch: a failed first
        # dispatch must leave the geometry cold (or the retry's compile
        # would be mislabeled warm and poison the service-time EMA, and
        # the prewarm path would skip a geometry that never compiled).
        self._wave_compiled.add(geo_key)
        # Start the D2H transfer right behind the program so harvest finds
        # the results already on host instead of starting the copy then.
        try:
            for arr in (toks_d, iters_d, *counts_d):
                arr.copy_to_host_async()
        except AttributeError:  # pragma: no cover - backend without D2H async
            pass
        req_ids = list(range(self._req_counter, self._req_counter + len(prompts)))
        self._req_counter += len(prompts)
        self.stats["waves"] = seq
        self.stats["prefills"] += 1
        self.stats["dispatches"] += 1
        self.stats["prefill_tokens"] += int(suffix_lens.sum())
        self.stats["suffix_tokens_computed"] += R * bucket
        if self._stateful:
            self.stats["state_seeds"] += R
        self.stats["requests"] += len(prompts)
        handle = WaveHandle(
            toks_d=toks_d,
            iters_d=iters_d,
            counts_d=counts_d[0] if counts_d else None,
            n=len(prompts),
            max_new_tokens=max_new_tokens,
            req_ids=req_ids,
            cold_compile=cold_compile,
            geo_key=geo_key,
            seq=seq,
            bucket=bucket,
        )
        if prof is not None:
            # dispatch fence CLOSES here: packing + jit enqueue + D2H kick
            prof.on_submit(
                handle, t_dispatch0, time.perf_counter(),
                suffix_tokens=int(suffix_lens.sum()),
                n_requests=len(prompts),
                prefix_len=prefix.length,
                cold_compile=cold_compile,
            )
        return handle

    def harvest_wave(self, handle: WaveHandle) -> list[Finished]:
        """Sync one wave's results (blocks until the device program ran)."""
        with spans.thread_span(
            "harvest_wave", layer="engine",
            wave=handle.seq, rows=handle.n, bucket=handle.bucket,
        ) as ann:
            return self._harvest_wave(handle, ann)

    def _harvest_wave(self, handle: WaveHandle, ann) -> list[Finished]:
        prof = self.profiler
        if prof is not None:
            t_harvest0 = time.perf_counter()
            ready_at_entry = handle.is_ready()
        # ONE device_get for both results: each fetch is its own blocking
        # round trip, and the wave sync is the per-decision critical path.
        with spans.thread_span("harvest_wait", layer="engine", wave=handle.seq):
            toks_np, iters_np, counts_np = jax.device_get(
                (handle.toks_d, handle.iters_d, handle.counts_d)
            )
        handle.model_calls = int(iters_np)
        # the family's device counters ride the same fetch: no extra sync
        counts = (
            {} if counts_np is None
            else dict(zip(self._model.COUNTERS, map(int, counts_np)))
        )
        for name, value in counts.items():
            self.stats[name] += value
        if ann is not None:
            ann.set_metadata(model_calls=handle.model_calls, **counts)
        if prof is not None:
            # the block_until_ready boundary just closed
            t_sync = time.perf_counter()
        # Actual model calls this wave ran: the while-loop's early exit means
        # this is <= the compiled n_iters bound (no phantom iterations are
        # ever counted — or executed).
        self.stats["wave_model_calls"] = (
            self.stats.get("wave_model_calls", 0) + int(iters_np)
        )
        self.stats["syncs"] += 1
        pad = self.tokenizer.pad_id
        latency_ms = (time.perf_counter() - handle.submitted_at) * 1000.0
        out: list[Finished] = []
        wave_decode_tokens = 0
        for row in range(handle.n):
            ids = [int(t) for t in toks_np[row] if t != pad]
            ids = ids[: handle.max_new_tokens]
            self.stats["completed"] += 1
            self.stats["decode_tokens"] += len(ids)
            wave_decode_tokens += len(ids)
            out.append(
                Finished(
                    req_id=handle.req_ids[row],
                    token_ids=ids,
                    text=self.tokenizer.decode(ids),
                    latency_ms=latency_ms,
                )
            )
        if prof is not None:
            prof.on_harvest(
                handle, t_harvest0, t_sync, time.perf_counter(),
                decode_tokens=wave_decode_tokens,
                model_calls=int(iters_np),
                ready_at_entry=ready_at_entry,
            )
        return out

    def decide_wave(
        self, prompts: list[list[int]], max_new_tokens: int = 200
    ) -> list[Finished]:
        """Synchronous wave: submit + harvest (tests, simple callers)."""
        return self.harvest_wave(self.submit_wave(prompts, max_new_tokens))

    # ---------------------------------------------------------------- step
    def step(self, chunks: int = 1) -> list[Finished]:
        """Run `chunks` fused decode chunks back-to-back (no intermediate
        sync), then ONE host sync; returns requests that finished."""
        self._require_paged("step() (chunked decode)")
        if not self._by_slot:
            return []
        with spans.span("decode_chunk", layer="engine", chunks=chunks) as sp:
            before = self.stats["decode_tokens"]
            finished = self._step_inner(chunks)
            if sp is not None:
                sp.attrs["finished"] = len(finished)
                sp.attrs["tokens"] = self.stats["decode_tokens"] - before
        return finished

    def _chunk_dispatch(self, prefix: _PrefixKV) -> jax.Array:
        """Dispatch ONE fused decode chunk (no host sync); returns the
        device array of emitted tokens [M+1, chunk_steps]. Shared by
        step() and the admission plane's piggybacked decode
        (admit_packed), so both paths run the identical program."""
        self._rng, sub = jax.random.split(self._rng)
        (
            self.kv.k, self.kv.v,
            self._tok_d, self._pos_d, self._act_d, self._st_d,
            self._budget_d, toks_d,
        ) = self._chunk(
            self.params, self.cfg, self.kv.k, self.kv.v,
            self._padded_tables(),
            prefix.k, prefix.v, jnp.int32(prefix.length),
            self._tok_d, self._pos_d, self._act_d, self._st_d,
            self._budget_d,
            self._sp_tokens, self._sp_next, self._done_state,
            jnp.int32(self.tokenizer.eos_id),
            jnp.int32(self.tokenizer.pad_id),
            sub, jnp.float32(self.temperature), self.chunk_steps,
            self._constrained, self.paged_attn,
        )
        self.stats["chunks"] += 1
        self.stats["dispatches"] += 1
        return toks_d

    def _step_inner(self, chunks: int) -> list[Finished]:
        prefix = self._prefix or self._get_empty_prefix()
        # Emissions from decode chunks piggybacked during a packed
        # admission (admit_packed) were dispatched without a sync; they
        # harvest here, FIRST (chronological order per slot).
        emissions: list[jax.Array] = list(self._pending_emissions)
        self._pending_emissions = []
        any_active = bool(
            (self._act_np & (self._budget_np > 0))[list(self._by_slot)].any()
        )
        if any_active:
            for _ in range(max(1, chunks)):
                emissions.append(self._chunk_dispatch(prefix))

        # ONE host sync for everything: emitted tokens + post-chunk state +
        # first tokens of freshly admitted requests.
        fetched = jax.device_get(
            (emissions, self._act_d, self._budget_d, self._first_d)
        )
        emitted_np, act_np, budget_np, first_np = fetched
        self.stats["syncs"] += 1
        return self._finish_harvest(emitted_np, act_np, budget_np, first_np)

    def _finish_harvest(
        self, emitted_np, act_np, budget_np, first_np
    ) -> list[Finished]:
        """Resolve harvested emissions into per-request token streams and
        Finished records — the shared back half of step() and the fused
        harvest (step_fused/decode_fused). Token accounting is EXACT:
        emitted counts pad-filtered tokens actually sampled, never
        chunk-capacity estimates (pad is unsampleable for active slots —
        set_grammar), so early-exiting fused chunks book only what ran."""
        # np.array copies: device_get may hand back read-only views and the
        # mirrors are mutated host-side (optimistic admission flags).
        self._act_np = np.array(act_np)
        self._budget_np = np.array(budget_np)
        toks = (
            np.concatenate(emitted_np, axis=1)
            if len(emitted_np)
            else np.zeros((self.max_slots + 1, 0), dtype=np.int32)
        )
        # Column offset of each harvested emission array: a request whose
        # slot was freed and reused mid-pack (abort_all / spec rollback
        # during an in-flight pack chunk) must not book the PREVIOUS
        # occupant's parked piggyback columns — park_floor marks where
        # this request's emissions can start.
        col_at = np.cumsum([0] + [a.shape[1] for a in emitted_np])

        finished: list[Finished] = []
        pad = self.tokenizer.pad_id
        for slot, req in list(self._by_slot.items()):
            if req.external:
                # Driven by an external decoder (an open speculative
                # stream): its slot is inactive in the decode batch and
                # its completion/teardown belongs to that owner.
                continue
            if req.first_pending:
                req.generated.append(int(first_np[slot]))
                req.first_pending = False
            start = col_at[min(req.park_floor, len(emitted_np))]
            req.park_floor = 0  # the parked list is consumed by this harvest
            emitted = [int(t) for t in toks[slot, start:] if t != pad]
            # Tokens after the finishing token are pad, so emitted is exact
            # (pad is never sampleable for active slots — see set_grammar).
            req.generated.extend(emitted)
            self.stats["decode_tokens"] += len(emitted)
            if not self._act_np[slot] or self._budget_np[slot] <= 0:
                req.done = True
                self.kv.free_slot(slot)
                del self._by_slot[slot]
                ids = req.generated[: req.max_new_tokens]
                finished.append(
                    Finished(
                        req_id=req.req_id,
                        token_ids=ids,
                        text=self.tokenizer.decode(ids),
                        latency_ms=(time.perf_counter() - req.submitted_at) * 1000.0,
                    )
                )
                self.stats["completed"] += 1
        self._book_decision_flow()
        return finished

    def _book_decision_flow(self) -> None:
        """Feed the profiler's dispatches_per_decision gauge: the delta of
        engine dispatches over the delta of completed decisions since the
        last completion was booked. Dispatches accumulate across harvests
        that complete nothing, so the telescoped ratio is exact."""
        if self.profiler is None:
            return
        d_done = self.stats["completed"] - self._flow_completed_last
        if d_done <= 0:
            return
        d_disp = self.stats["dispatches"] - self._flow_dispatches_last
        self._flow_completed_last = self.stats["completed"]
        self._flow_dispatches_last = self.stats["dispatches"]
        self.profiler.on_decision_flow(d_disp, d_done)

    # ---------------------------------------------------------- fused decode
    def dense_grammar(self) -> jax.Array | None:
        """The active grammar's dense [states, vocab] transition table on
        device, or None (no grammar / past the byte cap). Built lazily on
        first use and shared by every dense-table consumer — the fused
        while_loop AND the speculative verifier's greedy grammar path
        (spec/verify.py) gather from this one array."""
        if not self._constrained or self._fused_unsupported:
            return None
        if self._fused_next_d is None:
            from k8s_llm_scheduler_tpu.engine.fused import dense_tables

            tables = (
                dense_tables(
                    self._dfa, self.cfg.vocab_size, self.fused_table_bytes
                )
                if self._dfa is not None
                else None
            )
            if tables is None:
                self._fused_unsupported = True
                logger.info(
                    "grammar cannot export a dense fused table (cap %d "
                    "bytes); decode stays on the sparse chunked path",
                    self.fused_table_bytes,
                )
                return None
            self._fused_next_d = jnp.asarray(tables.next_state)
        return self._fused_next_d

    def _fused_ready(self) -> bool:
        """Whether the fused runtime can serve the CURRENT grammar state.
        False routes callers to the sparse chunked path: grammar too
        large for a dense table (size cap — a 128k-vocab production
        grammar) or fused decode disabled. Open speculative rounds no
        longer gate this: a spec stream deactivates only its own slot
        (_Request.external), so fused chunks and spec rounds pipeline
        together."""
        if not self.fused_decode:
            return False
        if not self._constrained:
            return True
        return self.dense_grammar() is not None

    def _fused_chunk_dispatch(self, prefix: _PrefixKV):
        """Dispatch ONE fused decode chunk (no host sync); returns the
        device pair (emitted tokens [M+1, chunk_steps], steps_run scalar).
        The fused twin of _chunk_dispatch."""
        self._rng, sub = jax.random.split(self._rng)
        table = (
            self._fused_next_d if self._constrained else self._fused_dummy
        )
        (
            self.kv.k, self.kv.v,
            self._tok_d, self._pos_d, self._act_d, self._st_d,
            self._budget_d, toks_d, steps_d,
        ) = self._fused_chunk(
            self.params, self.cfg, self.kv.k, self.kv.v,
            self._padded_tables(),
            prefix.k, prefix.v, jnp.int32(prefix.length),
            self._tok_d, self._pos_d, self._act_d, self._st_d,
            self._budget_d,
            table, self._done_state,
            jnp.int32(self.tokenizer.eos_id),
            jnp.int32(self.tokenizer.pad_id),
            sub, jnp.float32(self.temperature),
            self.chunk_steps, self._constrained, self.top_k,
            self.paged_attn,
        )
        self.stats["chunks"] += 1
        self.stats["dispatches"] += 1
        self.stats["fused_chunks"] += 1
        return toks_d, steps_d

    def _mean_decode_ctx(self) -> float:
        """Host-side mean attention context of in-flight decode slots
        (prefix + prompt + generated so far) — feeds the profiler's fused
        FLOP books without a device fetch."""
        if not self._by_slot:
            return float(self.prefix_len)
        own = [
            req.prompt_len + len(req.generated)
            for req in self._by_slot.values()
        ]
        return self.prefix_len + sum(own) / len(own)

    def step_fused(self, chunks: int = 1) -> list[Finished]:
        """step()'s fused twin: `chunks` while_loop decode chunks dispatched
        back-to-back, then ONE host sync. Early exit makes over-dispatch
        free (a finished batch's remaining chunks run zero iterations), so
        token accounting stays exact — the span and stats book tokens
        actually emitted, never chunk capacity. Falls back to step() when
        the fused runtime can't serve (_fused_ready)."""
        self._require_paged("step_fused() (the fused decode loop)")
        if not self._by_slot:
            return []
        if not self._fused_ready():
            self.stats["fused_fallbacks"] += 1
            return self.step(chunks)
        prof = self.profiler
        t0 = time.perf_counter() if prof is not None else 0.0
        with spans.span(
            "decode_chunk", layer="engine", chunks=chunks, fused=True
        ) as sp:
            tok_before = self.stats["decode_tokens"]
            step_before = self.stats["fused_steps"]
            finished = self._step_fused_inner(chunks, prof, t0)
            if sp is not None:
                sp.attrs["finished"] = len(finished)
                sp.attrs["tokens"] = self.stats["decode_tokens"] - tok_before
                sp.attrs["steps"] = self.stats["fused_steps"] - step_before
        return finished

    def _step_fused_inner(self, chunks: int, prof, t0: float) -> list[Finished]:
        prefix = self._prefix or self._get_empty_prefix()
        emissions: list[jax.Array] = list(self._pending_emissions)
        self._pending_emissions = []
        steps_ds: list[jax.Array] = []
        any_active = bool(
            (self._act_np & (self._budget_np > 0))[list(self._by_slot)].any()
        )
        ctx = self._mean_decode_ctx() if prof is not None else 0.0
        if any_active:
            for _ in range(max(1, chunks)):
                toks_d, steps_d = self._fused_chunk_dispatch(prefix)
                emissions.append(toks_d)
                steps_ds.append(steps_d)
        t_disp = time.perf_counter() if prof is not None else 0.0
        fetched = jax.device_get(
            (emissions, steps_ds, self._act_d, self._budget_d, self._first_d)
        )
        emitted_np, steps_np, act_np, budget_np, first_np = fetched
        t_sync = time.perf_counter() if prof is not None else 0.0
        self.stats["syncs"] += 1
        self.stats["fused_steps"] += int(sum(int(s) for s in steps_np))
        tok_before = self.stats["decode_tokens"]
        finished = self._finish_harvest(emitted_np, act_np, budget_np, first_np)
        if prof is not None:
            now = time.perf_counter()
            prof.on_fused(
                wall_s=now - t0,
                dispatch_s=t_disp - t0,
                sync_s=t_sync - t_disp,
                harvest_s=now - t_sync,
                steps=int(sum(int(s) for s in steps_np)),
                tokens=self.stats["decode_tokens"] - tok_before,
                chunks=len(steps_ds),
                ctx=ctx,
            )
        return finished

    def decode_fused(self) -> list[Finished]:
        """Drive every in-flight slot to COMPLETION through the fused
        runtime: dispatch ceil(max remaining budget / chunk_steps) fused
        chunks back-to-back with no intervening host sync (they pipeline
        on device; early exit makes post-completion chunks free), then
        harvest with ONE host sync per chunk in dispatch order — the
        per-token round trip is gone and the per-chunk sync overlaps the
        later chunks' device execution. The device-side budget guarantees
        completion within the dispatched chunks. Falls back to a step()
        drain when the fused runtime can't serve."""
        self._require_paged("decode_fused() (the fused decode loop)")
        if not self._by_slot:
            return []
        if not self._fused_ready():
            self.stats["fused_fallbacks"] += 1
            out: list[Finished] = []
            # external (spec-driven) requests never finish through step()
            # — draining on them would spin forever
            while any(not r.external for r in self._by_slot.values()):
                out.extend(self.step())
            return out
        with spans.span(
            "decode_chunk", layer="engine", fused=True, drain=True
        ) as sp:
            before = self.stats["decode_tokens"]
            finished = self._decode_fused_inner()
            if sp is not None:
                sp.attrs["finished"] = len(finished)
                sp.attrs["tokens"] = self.stats["decode_tokens"] - before
        return finished

    def _decode_fused_inner(self) -> list[Finished]:
        prof = self.profiler
        t0 = time.perf_counter() if prof is not None else 0.0
        ctx = self._mean_decode_ctx() if prof is not None else 0.0
        prefix = self._prefix or self._get_empty_prefix()
        emissions: list[jax.Array] = list(self._pending_emissions)
        self._pending_emissions = []
        live = list(self._by_slot)
        budget_max = int(self._budget_np[live].max()) if live else 0
        n_chunks = max(1, -(-budget_max // self.chunk_steps))
        handles = []
        for _ in range(n_chunks):
            handles.append(self._fused_chunk_dispatch(prefix))
        t_disp = time.perf_counter() if prof is not None else 0.0
        # Pending (piggybacked) emissions are chronologically FIRST per
        # slot; fetching them is its own host sync and is counted as one
        # (by the time it runs, every chunk is already enqueued, so it
        # gates nothing extra — but the books must not undercount).
        emitted_np: list[np.ndarray] = []
        if emissions:
            emitted_np = list(jax.device_get(emissions))
            self.stats["syncs"] += 1
        steps_total = 0
        for toks_d, steps_d in handles:
            toks_np, steps_np = jax.device_get((toks_d, steps_d))  # graftlint: ok[device-sync-in-loop] — THE fused harvest cadence: one sync per CHUNK (chunk_steps tokens), never per token, while later chunks keep executing on device
            emitted_np.append(toks_np)
            steps_total += int(steps_np)
            self.stats["syncs"] += 1
        t_sync = time.perf_counter() if prof is not None else 0.0
        self.stats["fused_steps"] += steps_total
        act_np, budget_np, first_np = jax.device_get(
            (self._act_d, self._budget_d, self._first_d)
        )
        tok_before = self.stats["decode_tokens"]
        finished = self._finish_harvest(emitted_np, act_np, budget_np, first_np)
        if prof is not None:
            now = time.perf_counter()
            prof.on_fused(
                wall_s=now - t0,
                dispatch_s=t_disp - t0,
                sync_s=t_sync - t_disp,
                harvest_s=now - t_sync,
                steps=steps_total,
                tokens=self.stats["decode_tokens"] - tok_before,
                chunks=n_chunks,
                ctx=ctx,
            )
        return finished

    def release_slot(self, slot: int) -> None:
        """Tear down one admitted slot out-of-band: drop its request, free
        its pages, and clear the host + device decode state. THE teardown
        for completions that bypass step() (spec/decoder.py finishes and
        rollbacks) — every per-slot engine field is cleared in exactly one
        place so new state can't silently leak through an external path."""
        del self._by_slot[slot]
        self.kv.free_slot(slot)
        self._act_np[slot] = False
        self._budget_np[slot] = 0
        self._act_d = self._act_d.at[slot].set(False)
        self._budget_d = self._budget_d.at[slot].set(0)

    def abort_all(self) -> None:
        """Free every in-flight slot and its KV pages — recovery path after a
        failed dispatch so the engine never leaks capacity."""
        for slot in list(self._by_slot):
            self.kv.free_slot(slot)
            del self._by_slot[slot]
        self._act_np[:] = False
        self._budget_np[:] = 0
        self._act_d = jnp.zeros(self.max_slots + 1, dtype=bool)
        self._budget_d = jnp.zeros(self.max_slots + 1, dtype=jnp.int32)
        # Un-harvested piggybacked emissions belong to the aborted work;
        # a later request reusing a slot must never inherit their tokens.
        self._pending_emissions = []

    # ---------------------------------------------------------------- swap
    def swap_params(self, params: Params) -> Params:
        """Replace the served weights IN PLACE; returns the old params tree
        (rollout/hotswap.py holds it for double-buffered rollback, or drops
        it pre-restore for in-place donation at 70B scale).

        Engine-owner thread only, like every dispatch path, and only at a
        wave barrier (no un-harvested WaveHandles): waves capture `params`
        by reference at submit, so swapping under an in-flight wave is
        device-safe but would leave its result attributed to the wrong
        version. LocalLLMBackend.run_quiesced provides exactly that
        barrier.

        Everything derived from the old weights is invalidated here:
        - the on-device prefix-KV cache (every cached cluster-state prefix,
          including LCP-reuse seeds, was prefilled under the old weights);
        - the active prefix pointer — unless paged slots are mid-flight
          (identical-params swaps may run mid-stream; cross-version
          callers must drain first, which run_quiesced guarantees for the
          wave path);
        - grammar tables, decode state, and the paged KV survive: none of
          them depend on weight values;
        - any OPEN SPECULATIVE stream rolls back first (spec/decoder.py
          on_swap): its un-verified block's pages truncate via
          PagedKVCache.truncate and device-resident proposal blocks drop,
          so nothing computed under the old weights can seed a post-swap
          round.
        The decision cache above the engine needs its own epoch bump —
        rollout/hotswap.py owns that (core/cache.bump_generation)."""
        if self.spec is not None:
            self.spec.on_swap()
        old = self.params
        self.params = params
        self._prefix_cache.clear()
        # Pinned snapshot-prefix entries are invalidated WITH the cache:
        # the pin set empties and the epoch bump makes every outstanding
        # PinHandle stale (pin_alive -> False), so a pin taken under the
        # old weights can never serve a post-swap decision — the
        # admission-plane twin of the decision cache's generation bump.
        self._pinned_prefix_keys.clear()
        self.prefix_epoch += 1
        if self._by_slot:
            # keep the active prefix for in-flight paged decodes; it is
            # evicted from the cache so no FUTURE request reuses it
            logger.warning(
                "weight swap with %d paged request(s) in flight — they "
                "continue against the pre-swap prefix KV (token-identical "
                "only for identical params)", len(self._by_slot),
            )
        else:
            self._prefix = None
        self.stats["weight_swaps"] = self.stats.get("weight_swaps", 0) + 1
        return old

    # ------------------------------------------------------------ convenience
    def attach_spec(self, decoder) -> None:
        """Attach a speculative decoder (spec/decoder.py SpeculativeDecoder).

        generate() then routes single-request completions through the
        async propose/verify pipeline; the fused decode path remains the
        fallback (unsupported prompts, auto-disable) and the multi-slot
        add_requests/step surface is unchanged. An open speculative
        stream occupies only its own slot (_Request.external) — fused
        chunks for other slots keep dispatching — and swap_params calls
        decoder.on_swap() so open blocks roll back before new weights
        install."""
        self._require_paged("attach_spec() (speculative decoding, spec/)")
        self.spec = decoder

    def attach_profiler(self, profiler) -> None:
        """Attach a continuous wave profiler (observability/profiler.py
        EngineProfiler). submit_wave/harvest_wave then fence their
        dispatch/sync boundaries into it; engine/local.py contributes the
        queue-stall and ready-edge fences. None detaches."""
        self.profiler = profiler

    def generate(
        self,
        prompt_ids: list[int],
        max_new_tokens: int = 200,
        use_spec: bool | None = None,
    ) -> Finished:
        """Synchronous single-request generation (tests, simple callers).

        `use_spec`: None = speculative when a decoder is attached
        (attach_spec) and the request fits it; True/False force the path
        (bench A/Bs pass False for the plain arm on a spec-enabled
        engine)."""
        self._require_paged("generate() over the paged pool")
        if use_spec is None:
            use_spec = self.spec is not None
        if (
            use_spec
            and self.spec is not None
            and self.spec.supports(prompt_ids, max_new_tokens)
        ):
            return self.spec.generate(prompt_ids, max_new_tokens)
        req_id = self.add_request(prompt_ids, max_new_tokens)
        # Plain decode rides the FUSED runtime (decode_fused: all chunks
        # enqueued back-to-back, one gating sync) — this is the baseline
        # the spec A/B is judged against; falls back internally when the
        # grammar can't fuse.
        while True:
            for fin in self.decode_fused():
                if fin.req_id == req_id:
                    return fin

    def get_stats(self) -> dict[str, Any]:
        out = {**self.stats, "pages_free": self.kv.pages_free,
               "slots_free": self.free_slots}
        # sched/client nests this under "engine" ->
        # llm_scheduler_engine_dispatches_per_decision: windowed from the
        # profiler's flow books when attached, lifetime ratio otherwise.
        dpd = None
        if self.profiler is not None:
            dpd = self.profiler.dispatches_per_decision()
        if dpd is None and self.stats["completed"]:
            dpd = round(
                self.stats["dispatches"] / self.stats["completed"], 4
            )
        if dpd is not None:
            out["dispatches_per_decision"] = dpd
        if self.spec is not None:
            out["spec"] = self.spec.stats.snapshot()
        if self.prefix_attn_impl.resolved:
            # which attention implementation each traced call site got
            # (ops/attention.AttnImpl) — "auto" dropping to the einsum
            # path is a fine selection and must not be an invisible one
            out["attention_impls"] = self.prefix_attn_impl.resolved_counts()
        return out
