"""Latent attention (MLA) with a sigmoid-routed sparse-expert feed-forward
behind leading dense layers, in functional JAX: the `glm4_moe_lite` /
DeepSeek-V3 layer (GLM-4.7-Flash), on the decision path.

THE LAYER EQUATIONS. `x` is the residual stream, KEPT IN FLOAT32: every
sublayer's output is accumulated into it in float32, and the router reads
its scores from it unrounded (where the fourth and fifth score lie closer
than the arithmetic resolves, a token meets another expert; what is left
of that with a float32 stream comes from the bf16 matmul operands, PERF.md
§6 PR 30). bf16 weights and matmul operands, float32 accumulation, RMSNorm
with `cfg.rms_eps`; H heads, ranks `q_lora_rank` (dq)
and `kv_lora_rank` (dc), head widths `qk_nope_head_dim` (dn),
`qk_rope_head_dim` (dr), `v_head_dim` (dv).

- Attention, every layer. h = RMSNorm(x). `c_q = RMSNorm(h W_dq)` [dq];
  `[q_nope | q_rope] = c_q W_uq` as H x (dn + dr); `[c_kv | k_r] = h W_dkv`
  [dc + dr]; `c_kv = RMSNorm(c_kv)`; `q_rope` and `k_r` rotated at the
  token's position (theta `cfg.rope_theta` over the dr rope dims, the
  repo's half-split layout, no scaling; `k_r` is ONE vector shared by all
  heads). THE CACHE IS `(c_kv, k_r)` PER TOKEN PER LAYER, dc + dr numbers,
  and nothing else. `[k_nope | v] = c_kv W_ukv` as H x (dn + dv);
  `score = (q_nope . k_nope + q_rope . k_r) / sqrt(dn + dr)`, causal softmax
  in float32, `o = sum p v` (H x dv), `x += o W_o`.
  Every forward runs the ABSORBED form of this (the same mathematics; the
  written-out form is the reference's, and tests hold the two equal):
  `q_nope W_uk^T` (H x dc) is scored against c_kv directly and W_uv is
  applied to `sum p c_kv`, so nothing per head is ever made of a cached
  token and no K/V is written out of a cache. On the chip neither form won
  on any segment by more than 7% (PERF.md §6 PR 30), so there is one.
- Dense feed-forward (the leading `n_dense_layers`): SwiGLU, width d_ff.
- Expert feed-forward (the rest). h = RMSNorm(x); router logits
  `h_f32 W_g` in FLOAT32 [n_routed_experts]; `s = sigmoid(logits)`; the
  `n_experts_per_tok` experts with the largest `s + b` (b: the selection
  bias `e_score_correction_bias`; no group limit); weights
  `s_e / sum_selected s` (the bias does not enter the weights), times
  `routed_scaling_factor`; output `sum w_e SwiGLU_e(h)` (width
  d_ff_expert) `+ SwiGLU_shared(h)` (width n_shared_experts x d_ff_expert).
  No capacity, no dropped token. The layer holds experts `expert_first ..
  + experts_held`, routes over all of them, and computes its own experts'
  part: where it holds a share, from the assignments it holds alone while
  they fit `held_bound`, from every token x pick row when they do not or
  when it holds all (`routed_experts`). PADDING IS NOT ROUTED: a token that
  is not valid adds no load and touches no expert's weights.
- Head: final RMSNorm, untied output head.
- Left out: the multi-token-prediction module (a draft head for
  self-speculation; the main model's logits do not depend on it).

Params are two stacks, `params["dense_layers"]` and `params["moe_layers"]`
(leading axis = layer of its stack), each its own `lax.scan`; ONE block
function (`_layer`) serves prefix prefill, suffix prefill and block decode,
which differ in what the queries may see and in where the cache is sunk.

The three entry points keep the contracts of models/llama.py's
`forward_prefill_kv`, `forward_prefill_suffix_dense` and
`forward_block_decode`, except that the cache they take and return is the
latent pair (c_kv [L, ..., dc], k_r [L, ..., dr]) and that the two wave
forwards return one more value, the expert-load counters COUNTERS names.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from k8s_llm_scheduler_tpu.models.configs import MlaMoeConfig
from k8s_llm_scheduler_tpu.models.llama import (
    _embed,
    _last_valid_logits,
    apply_rope,
    rms_norm,
)
from k8s_llm_scheduler_tpu.ops.attention import NEG_INF, merge_attention_parts, write_block
from k8s_llm_scheduler_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul
from k8s_llm_scheduler_tpu.ops.router_top_k import router_top_k

Params = dict[str, Any]

# What a wave counts of its expert layers, summed over layers and model
# calls on the device (engine.stats carries them under these names).
COUNTERS = ("moe_assignments", "moe_experts_hit", "moe_layer_calls", "moe_max_load")

# std of the drawn selection bias. The published model LEARNS the bias to
# level the experts' load; a draw has to be small beside the distance between
# neighbouring scores at the selection's edge (~0.015 at 64 experts, top 4),
# or the expert that drew the largest bias takes a standing lead: at 0.1 one
# expert met a quarter of all tokens (PERF.md §6 PR 30). 0.02 reorders
# neighbours, so selecting with it and weighting without it still differ.
BIAS_SCALE = 0.02


def cache_token_shapes(cfg) -> tuple[tuple[int, ...], ...]:
    """Per-token trailing shapes of the cache tuple: (c_kv, k_r)."""
    return ((cfg.kv_lora_rank,), (cfg.qk_rope_head_dim,))


def cache_layers(cfg: MlaMoeConfig) -> int:
    """Leading axis of the cache tuple: one attention sublayer a layer."""
    return cfg.n_layers


def state_shapes(cfg) -> tuple:
    """What a sequence carries besides its per-token cache: nothing."""
    return ()


def state_layers(cfg) -> int:
    return 0


def _inv_freq(cfg) -> jax.Array:
    dr = cfg.qk_rope_head_dim
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))


# --------------------------------------------------------------------- init
def init_params(rng: jax.Array, cfg: MlaMoeConfig, quantize: str | None = None) -> Params:
    """Random-init: normal x 1/sqrt(fan_in), the embedding at unit scale
    (every sublayer adds a term of unit scale to the stream; an embedding of
    0.02 drowns in the first, and the tokens' streams, so their routes,
    come out nearly alike), norms at one, the selection bias normal x
    BIAS_SCALE in float32 (non-zero, so that selecting with it and
    weighting without it can be told apart). Expert
    weights are drawn a layer at a time (`lax.map`): the float32 draw of a
    whole stack of experts would not fit beside the weights."""
    if quantize is not None:
        raise ValueError(
            f"{cfg.name}: llm.quantization {quantize!r} is not served by "
            f"models/mla_moe.py (int8 expert weights: models/quant.py)"
        )
    D, H = cfg.d_model, cfg.n_heads
    dq, dc, dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    E, Fe = cfg.experts_held, cfg.d_ff_expert
    Fs = cfg.n_shared_experts * Fe
    k_embed, k_head, k_dense, k_moe = jax.random.split(rng, 4)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5).astype(cfg.dtype)

    def ones(shape):
        return jnp.ones(shape, dtype=cfg.dtype)

    def attention(keys, L):
        return {
            "attn_norm": ones((L, D)),
            "w_dq": dense(keys[0], (L, D, dq), D),
            "q_norm": ones((L, dq)),
            "w_uq": dense(keys[1], (L, dq, H * (dn + dr)), dq),
            "w_dkv": dense(keys[2], (L, D, dc + dr), D),
            "kv_norm": ones((L, dc)),
            "w_ukv": dense(keys[3], (L, dc, H * (dn + dv)), dc),
            "wo": dense(keys[4], (L, H * dv, D), H * dv),
            "mlp_norm": ones((L, D)),
        }

    def experts(key, shape, fan_in, L):
        return jax.lax.map(lambda k: dense(k, shape, fan_in), jax.random.split(key, L))

    Ld, Lm = cfg.n_dense_layers, cfg.n_moe_layers
    kd, km = jax.random.split(k_dense, 8), jax.random.split(k_moe, 13)
    return {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, D), dtype=jnp.float32)).astype(cfg.dtype),
        "final_norm": ones((D,)),
        "lm_head": dense(k_head, (D, cfg.vocab_size), D),
        "dense_layers": {
            **attention(kd, Ld),
            "w_gate": dense(kd[5], (Ld, D, cfg.d_ff), D),
            "w_up": dense(kd[6], (Ld, D, cfg.d_ff), D),
            "w_down": dense(kd[7], (Ld, cfg.d_ff, D), cfg.d_ff),
        },
        "moe_layers": {
            **attention(km, Lm),
            "router": dense(km[5], (Lm, D, cfg.n_routed_experts), D),
            "router_bias": jax.random.normal(km[6], (Lm, cfg.n_routed_experts), dtype=jnp.float32) * BIAS_SCALE,
            "we_gate": experts(km[7], (E, D, Fe), D, Lm),
            "we_up": experts(km[8], (E, D, Fe), D, Lm),
            "we_down": experts(km[9], (E, Fe, D), Fe, Lm),
            "ws_gate": dense(km[10], (Lm, D, Fs), D),
            "ws_up": dense(km[11], (Lm, D, Fs), D),
            "ws_down": dense(km[12], (Lm, Fs, D), Fs),
        },
    }


# ------------------------------------------------------------- feed-forward
def _swiglu(h: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """SwiGLU of h (the weights' dtype), its output in float32 for the
    residual stream."""
    gate = jnp.einsum("...d,df->...f", h, w_gate)
    up = jnp.einsum("...d,df->...f", h, w_up)
    fused = jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up
    return jnp.einsum("...f,fd->...d", fused, w_down, preferred_element_type=jnp.float32)


# A router's scores of its logits [T, outputs], by `cfg.router_score`.
SCORES = {"sigmoid": jax.nn.sigmoid, "softmax": lambda logits: jax.nn.softmax(logits, axis=-1)}
# What a layer with identity experts counts besides COUNTERS.
ZERO_COUNTERS = ("moe_zero_assignments", "moe_ffn_assignments")
# And behind those: the layer calls whose held assignments all lay within
# `held_bound` (every call, where the bound is every row).
BOUND_COUNTERS = ("moe_bounded_calls",)

# How many times the rows a level router would send a share its short path
# holds (`held_bound`). Rows in lockstep and repeated prompt tokens pick equal
# experts, so a call's held count spreads well over a level draw's; at 2 every
# call of the share cell still fitted, prefix prefills included, and at 4 the
# layer alone took 4% (suffix call) and 13% (prefix prefill) longer for rows
# nothing used (PERF.md §6 PR 36). A call that does not fit is exact all the
# same: it takes every row.
HELD_SLACK = 2

# Rows a block of `group_positions`' rank product: within a block, the rows of
# each group before a row are one strictly-lower-triangular product on the
# MXU (the systolic array's width), exact in float32 for 0/1 operands.
RANK_BLOCK = 128
# The longest head `order_head` finds by comparing. Up to it, each place of
# the head compares every row's position with its own and sums the one row
# that matches; past it, the rows are scattered to their places. On a TPU v5e
# the compare cost ~1.1-1.7 ns a thousand (place, row) pairs and the scatter
# ~4.6 ns a row (a decode call's head of 1,024 of 1,920 rows: ~3 us against
# ~14; 512 of 12,288: ~7 against ~60), so the compare is the faster up to a
# head of ~3,000 whatever the rows (PERF.md §6).
COMPARE_HEAD_ROWS = 2048


# group_positions and order_head are jitted, as router_top_k is: a process
# traces and lowers each once a shape, not once a layer of each program.
@functools.partial(jax.jit, static_argnums=1)
def group_positions(group: jax.Array, n_held: int) -> tuple[jax.Array, jax.Array]:
    """(sizes [n_held] int32, position [n] int32) of the group ids [n] in
    0 .. n_held, where `n_held` is no group: how many rows each group holds
    (the scatter-add's count), and where each row lands in
    `jnp.argsort(group, stable=True)` (row i at position[i], so position is
    that order's inverse). A stable counting sort: a group's rows follow
    the groups before it in their own order, rows of no group follow every
    group. The rows of each group before a row are a prefix count, one
    triangular product a block of RANK_BLOCK rows plus the blocks before."""
    n = group.shape[0]
    pad = -n % RANK_BLOCK
    g = jnp.pad(group, (0, pad), constant_values=n_held)
    blocks = (g[:, None] == jnp.arange(n_held, dtype=g.dtype)[None, :]).reshape(-1, RANK_BLOCK, n_held)
    rows = jnp.arange(RANK_BLOCK)
    earlier = (rows[:, None] > rows[None, :]).astype(jnp.bfloat16)
    within = jnp.einsum("ij,bjg->big", earlier, blocks.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32).astype(jnp.int32)
    per_block = jnp.sum(blocks, axis=1, dtype=jnp.int32)  # [blocks, n_held]
    before = (within + (jnp.cumsum(per_block, axis=0) - per_block)[:, None, :]).reshape(-1, n_held)[:n]
    sizes = jnp.sum(per_block, axis=0)
    starts = jnp.cumsum(sizes) - sizes
    held = group < n_held
    mine = jnp.sum(jnp.where(blocks.reshape(-1, n_held)[:n], before + starts, 0), axis=1)
    # a row of no group: every held row, then the rows of no group before it
    none = jnp.sum(sizes) + jnp.arange(n, dtype=jnp.int32) - jnp.sum(before, axis=1)
    return sizes, jnp.where(held, mine, none)


@functools.partial(jax.jit, static_argnums=1)
def order_head(position: jax.Array, m: int) -> jax.Array:
    """The first `m` entries [m] of the order whose inverse is the
    permutation `position` [n] (row i at position[i]): the row at each
    place under m, found by comparing every row's position with it, or,
    for a head longer than COMPARE_HEAD_ROWS, each row with a position
    under m written to it and the rest dropped."""
    n = position.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    if m <= COMPARE_HEAD_ROWS:
        here = position[None, :] == jnp.arange(m, dtype=jnp.int32)[:, None]
        return jnp.sum(jnp.where(here, rows[None, :], 0), axis=1, dtype=jnp.int32)
    return jnp.zeros((m,), jnp.int32).at[position].set(rows, mode="drop", unique_indices=True)


def route(lp: Params, cfg, h: jax.Array, sel=None) -> tuple[jax.Array, jax.Array]:
    """(selected router outputs [T, k] int32, their weights [T, k] f32) of
    the normed tokens h [T, D]: scores in float32 by `cfg.router_score`
    (sigmoid of each logit, or a softmax over all the router's outputs),
    selection by score + bias, weights from the scores alone, renormalised
    where `cfg.norm_topk_prob`, scaled. With `sel` the selection is GIVEN
    and only weighted (benchmark/tests/read_flips.py hands the program the
    reference's selection, to show what a gap is made of)."""
    logits = jnp.einsum(
        "td,de->te", h.astype(jnp.float32), lp["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = SCORES[cfg.router_score](logits)
    if sel is None:
        # a router without a selection bias (models/gdn_moe.py) holds no such leaf
        sel, w = router_top_k(scores, lp.get("router_bias"), cfg.n_experts_per_tok)
    else:
        w = jnp.take_along_axis(scores, sel, axis=1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * cfg.routed_scaling_factor


def zero_experts(cfg, h: jax.Array, sel: jax.Array, w: jax.Array, valid: jax.Array):
    """The identity experts' part for normed tokens h [T, D]: router outputs
    `n_routed_experts ..` return their input, so a token's picks among them
    add h times the sum of their weights (f32 [T, D]); and ZERO_COUNTERS:
    the valid tokens' picks that fell on identity experts, and on any
    feed-forward expert, held here or not."""
    with jax.named_scope("moe_zero"):
        zero = valid[:, None] & (sel >= cfg.n_routed_experts)
        y = h.astype(jnp.float32) * jnp.sum(jnp.where(zero, w, 0.0), axis=1)[:, None]
        ffn = valid[:, None] & (sel < cfg.n_routed_experts)
    return y, jnp.stack([jnp.sum(zero), jnp.sum(ffn)]).astype(jnp.int32)


def held_bound(n_rows: int, held_n: int, n_outputs: int) -> int:
    """The most assignment rows a layer that holds `held_n` of its router's
    `n_outputs` outputs handles on its short path, of the `n_rows` (tokens x
    picks) a call routes: HELD_SLACK times what a level router sends it, in
    whole row tiles of the grouped kernels, and never more than all of them.
    A layer that holds every output gets `n_rows`: it has no short path."""
    level_rows = -(-HELD_SLACK * n_rows * held_n // n_outputs)
    return min(n_rows, -(-level_rows // ROW_TILE) * ROW_TILE)


def routed_experts(lp: Params, cfg, h: jax.Array, valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed output for normed tokens h
    [T, D] (f32 [T, D]) and the layer's COUNTERS. Tokens that are not
    `valid` [T] are not routed. `lp["we_*"]` hold experts `expert_first ..
    + experts_held` of the `n_routed_experts` the router scores: this
    layer's [E, ..], or the whole stack's [L, E, ..] with `lp["layer"]`
    saying which layer this is (`_run_stacks`: the kernel then reads the
    stack in place). Where the config has identity experts
    (`cfg.n_zero_experts` not None: router outputs behind the feed-forward experts),
    their part is added HERE, on every share (they hold no weights and are
    applied where the token is), and ZERO_COUNTERS follow COUNTERS. Where
    the layer holds a share of its router's outputs (it then has the short
    path below), BOUND_COUNTERS come last.

    Held assignments sort before the rows of no group, so where a call's
    held assignments number at most `held_bound` they are the head of the
    order, and only the head is gathered, multiplied and added back to its
    tokens. A call with more takes every row, as a layer that holds every
    output always does (its bound is every row): nothing is ever dropped."""
    T, D = h.shape
    k, held_n = cfg.n_experts_per_tok, cfg.experts_held
    bound = held_bound(T * k, held_n, lp["router"].shape[-1])
    with jax.named_scope("moe_router"):
        sel, w = route(lp, cfg, h)
    with jax.named_scope("moe_dispatch"):
        local = sel - cfg.expert_first
        held = valid[:, None] & (local >= 0) & (local < held_n)
        # not held -> group `held_n`, which sorts behind every expert
        group = jnp.where(held, local, held_n).reshape(T * k)
        sizes, position = group_positions(group, held_n)

    def experts(head):
        """f32 [M, D]: the grouped experts' output for the assignments
        `head` [M], a head of the order; rows of no group are not written."""
        with jax.named_scope("moe_dispatch"):
            rows = h.astype(lp["we_gate"].dtype)[head // k]
        with jax.named_scope("moe_experts"):
            gate, up, down = (
                w if w.ndim == 4 else w[None] for w in (lp["we_gate"], lp["we_up"], lp["we_down"])
            )
            layer = lp.get("layer", 0)
            mid = grouped_matmul(rows, (gate, up), sizes, layer, swiglu=True)
            return grouped_matmul(mid, (down,), sizes, layer, out_dtype=jnp.float32)

    def every_row():
        with jax.named_scope("moe_dispatch"):
            order = order_head(position, T * k)
        out = experts(order)
        with jax.named_scope("moe_combine"):
            # rows of no group were never written: select, never scale
            back = out[position].reshape(T, k, D)
            return jnp.sum(jnp.where(held[..., None], back * w[..., None], 0.0), axis=1)

    def held_rows():
        with jax.named_scope("moe_dispatch"):
            head = order_head(position, bound)
        out = experts(head)
        with jax.named_scope("moe_combine"):
            live = jnp.arange(bound)[:, None] < jnp.sum(sizes)
            weighted = jnp.where(live, out * w.reshape(T * k)[head][:, None], 0.0)
            # each row to its token, summed on the MXU: a one-hot [T, bound]
            to_token = (head // k)[None, :] == jnp.arange(T)[:, None]
            return jnp.dot(to_token.astype(jnp.float32), weighted, precision=jax.lax.Precision.HIGHEST)

    if bound == T * k:
        y, fits = every_row(), True
    else:
        fits = jnp.sum(sizes) <= bound
        y = jax.lax.cond(fits, held_rows, every_row)
    counters = jnp.stack([
        jnp.sum(held), jnp.sum(sizes > 0), jnp.int32(1), jnp.max(sizes),
    ]).astype(jnp.int32)
    more = []
    if cfg.n_zero_experts is not None:
        y_zero, zero_counters = zero_experts(cfg, h, sel, w, valid)
        y = y + y_zero
        more.append(zero_counters)
    if held_n < lp["router"].shape[-1]:
        more.append(jnp.asarray(fits, jnp.int32)[None])
    return y, jnp.concatenate([counters, *more]) if more else counters


def shared_experts(lp: Params, h: jax.Array, scale: float = 1.0) -> jax.Array:
    """The shared experts as one SwiGLU of their widths side by side, times
    `scale` in float32 (models/cohere2_moe.py averages its four: 1/4; the
    other families add theirs whole, and their programs hold no product)."""
    with jax.named_scope("moe_shared"):
        y = _swiglu(h.astype(lp["ws_gate"].dtype), lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return y if scale == 1.0 else y * scale


@jax.named_scope("mlp")
def _feed_forward(lp: Params, cfg: MlaMoeConfig, x: jax.Array, valid: jax.Array, moe: bool):
    """(feed-forward output in float32, COUNTERS) of the float32 stream x;
    `valid` like x less its last axis. The router reads the normed stream
    as it is; the matmuls take it in the weights' dtype."""
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if not moe:
        return (_swiglu(h.astype(cfg.dtype), lp["w_gate"], lp["w_up"], lp["w_down"]),
                jnp.zeros((len(COUNTERS),), jnp.int32))
    flat = h.reshape(-1, h.shape[-1])
    y, counters = routed_experts(lp, cfg, flat, valid.reshape(-1))
    # a share's BOUND_COUNTERS are not among this family's COUNTERS
    return (y + shared_experts(lp, flat)).reshape(x.shape), counters[:len(COUNTERS)]


# ---------------------------------------------------------------- attention
def _w_ukv(lp: Params, cfg: MlaMoeConfig) -> jax.Array:
    return lp["w_ukv"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
    )


def _softmax_part(logits, mask, values, eq_out):
    """(o, m, l) of ops/attention.merge_attention_parts from masked logits
    [B, H, S, T]; o = p @ values by `eq_out`."""
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    o = jnp.einsum(eq_out, p.astype(values.dtype), values, preferred_element_type=jnp.float32)
    return o, m, jnp.sum(p, axis=-1)


def attend_absorbed(lp, cfg, q_nope, q_rope, segments):
    """Attention output [B, S, H, dv] of queries against latent segments
    [(c [B?, T, dc], r [B?, T, dr], mask broadcastable to [B, H, S, T])]:
    the queries are taken into the latent space (q_nope W_uk^T) and the
    summed latent out of it (W_uv); no per-head K or V of a cached token
    is made. A segment without a batch axis (the shared prefix) is read
    once for all rows."""
    w = _w_ukv(lp, cfg)
    dn = cfg.qk_nope_head_dim
    scale = cfg.qk_head_dim**-0.5
    with jax.named_scope("mla_up"):
        q_abs = jnp.einsum("bshd,chd->bshc", q_nope, w[:, :, :dn])
    parts = []
    with jax.named_scope("latent_attention"):
        for c, r, mask in segments:
            t = "btc" if c.ndim == 3 else "tc"
            logits = (
                jnp.einsum(f"bshc,{t}->bhst", q_abs, c, preferred_element_type=jnp.float32)
                + jnp.einsum(f"bshc,{t}->bhst", q_rope, r, preferred_element_type=jnp.float32)
            ) * scale
            parts.append(_softmax_part(logits, mask, c, f"bhst,{t}->bhsc"))
        o_lat = merge_attention_parts(parts)  # [B, H, S, dc]
    with jax.named_scope("mla_up"):
        return jnp.einsum("bhsc,chd->bshd", o_lat.astype(q_nope.dtype), w[:, :, dn:])


def _scaled_norm(x, weight, eps: float, scale: float):
    """RMSNorm times a config's fixed latent scale (`q_lora_scale`,
    `kv_lora_scale`); the product is rounded once."""
    if scale == 1.0:
        return rms_norm(x, weight, eps)
    y = rms_norm(x.astype(jnp.float32), weight.astype(jnp.float32), eps)
    return (y * scale).astype(x.dtype)


@jax.named_scope("attn")
def attention_sublayer(lp, cfg, x, positions, inv_freq, attend):
    """ONE latent-attention sublayer for every forward of both families,
    over the float32 stream x: project (mla_down, mla_up), attend as the
    caller says (`attend(lp, q_nope, q_rope, c_kv, k_r) -> [B, S, H, dv]`:
    what the queries may see is the forward's), output projection. Returns
    (x + attention, (c_kv, k_r) of these tokens): where the cache is sunk
    is the forward's too. The cached latent carries `cfg.kv_lora_scale`."""
    B, S = x.shape[:2]
    H, dc, dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    with jax.named_scope("mla_down"):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps).astype(cfg.dtype)
        c_q = _scaled_norm(jnp.einsum("bsd,dq->bsq", h, lp["w_dq"]), lp["q_norm"],
                           cfg.rms_eps, cfg.q_lora_scale)
        kv = jnp.einsum("bsd,dc->bsc", h, lp["w_dkv"])
        c_kv = _scaled_norm(kv[..., :dc], lp["kv_norm"], cfg.rms_eps, cfg.kv_lora_scale)
        k_r = apply_rope(kv[..., None, dc:], positions, inv_freq)[..., 0, :]
    with jax.named_scope("mla_up"):
        q = jnp.einsum("bsq,qh->bsh", c_q, lp["w_uq"]).reshape(B, S, H, cfg.qk_head_dim)
        q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], positions, inv_freq)
    o = attend(lp, q_nope, q_rope, c_kv, k_r)
    with jax.named_scope("wo"):
        x = x + jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * cfg.v_head_dim), lp["wo"],
                           preferred_element_type=jnp.float32)
    return x, (c_kv, k_r)


def _layer(lp, cfg, x, positions, valid, inv_freq, moe, attend):
    """ONE layer for every forward: the attention sublayer, then the
    feed-forward. Returns (x, (c_kv, k_r) of these tokens, COUNTERS)."""
    x, cache = attention_sublayer(lp, cfg, x, positions, inv_freq, attend)
    y, counters = _feed_forward(lp, cfg, x, valid, moe)
    return x + y, cache, counters


EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


def _stream(params: Params, tokens: jax.Array) -> jax.Array:
    """The residual stream at its start: the embedding, in float32."""
    return _embed(params, tokens).astype(jnp.float32)


def _run_stacks(params, cfg, x, carry, xs, step):
    """The dense stack's scan, then the expert stack's, over one block
    function. `step(lp, moe, x, carry, xs_l, idx) -> (x, carry, ys,
    counters)`; `xs`: arrays with a leading layer axis over BOTH stacks
    (cut per stack here); `ys` come back stacked the same way. The routed
    experts' weights are NOT scanned over: a scan hands its body a slice,
    and a slice that feeds a kernel is a copy (ops/grouped_matmul.py); the
    body gets the whole stack and its own index in it (`lp["layer"]`)."""
    counters = jnp.zeros((len(COUNTERS),), jnp.int32)
    outs = []
    lo = 0
    for name, moe, n in (("dense_layers", False, cfg.n_dense_layers),
                         ("moe_layers", True, cfg.n_moe_layers)):
        if n == 0:
            continue
        whole = {k: v for k, v in params[name].items() if k in EXPERT_LEAVES}
        scanned = {k: v for k, v in params[name].items() if k not in EXPERT_LEAVES}

        def body(state, inp, moe=moe, whole=whole, lo=lo):
            x, carry, counters = state
            lp, xs_l, idx = inp
            x, carry, ys, c = step({**lp, **whole, "layer": idx - lo}, moe, x, carry, xs_l, idx)
            return (x, carry, counters + c), ys

        cut = jax.tree_util.tree_map(lambda a: a[lo:lo + n], xs)
        (x, carry, counters), ys = jax.lax.scan(
            body, (x, carry, counters), (scanned, cut, jnp.arange(lo, lo + n)))
        outs.append(ys)
        lo += n
    ys = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a, axis=0), *outs)
    return x, carry, ys, counters


# ------------------------------------------------------------------ prefill
def forward_prefill_kv(params: Params, cfg: MlaMoeConfig, tokens, seq_lens):
    """Full-prompt prefill for the cache alone, under scope
    `prefix_prefill`: (None, c_kv [L, B, S, dc], k_r [L, B, S, dr])."""
    B, S = tokens.shape
    inv_freq = _inv_freq(cfg)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    valid = positions < seq_lens[:, None]
    mask = (positions[0][:, None] >= positions[0][None, :])[None, None] & valid[:, None, None, :]

    def attend(lp, q_nope, q_rope, c_kv, k_r):
        return attend_absorbed(lp, cfg, q_nope, q_rope, [(c_kv, k_r, mask)])

    def step(lp, moe, x, carry, _xs, _idx):
        x, cache, counters = _layer(lp, cfg, x, positions, valid, inv_freq, moe, attend)
        return x, carry, cache, counters

    with jax.named_scope("prefix_prefill"):
        _, _, (c_all, r_all), _ = _run_stacks(
            params, cfg, _stream(params, tokens), (), (), step)
    return None, c_all, r_all


def forward_prefill_suffix_dense(
    params: Params,
    cfg: MlaMoeConfig,
    tokens: jax.Array,       # [B, Ss] int32, per-request suffix, left-aligned
    suffix_lens: jax.Array,  # [B] valid suffix tokens (0 = row unused)
    prefix_c: jax.Array,     # [L, Sp, dc] shared latent prefix
    prefix_r: jax.Array,     # [L, Sp, dr]
    prefix_len: jax.Array,   # scalar int32
    prefix_impl: Any = None,  # the dense family's kernel choice; no kernel here
):
    """Batched suffix prefill against the shared latent prefix, the suffix
    cache kept dense: (last_logits [B, V] f32, c_sfx [L, B, Ss, dc], r_sfx
    [L, B, Ss, dr], COUNTERS). Padding tokens of a suffix are not routed."""
    B, S = tokens.shape
    inv_freq = _inv_freq(cfg)
    j = jnp.arange(S)
    positions = prefix_len + jnp.broadcast_to(j, (B, S))
    valid = j[None, :] < suffix_lens[:, None]
    pre_mask = (jnp.arange(prefix_c.shape[1]) < prefix_len)[None, None, None, :]
    own_mask = (j[:, None] >= j[None, :])[None, None] & valid[:, None, None, :]

    def step(lp, moe, x, carry, xs_l, _idx):
        pc, pr = xs_l

        def attend(lp, q_nope, q_rope, c_kv, k_r):
            return attend_absorbed(lp, cfg, q_nope, q_rope,
                                   [(pc, pr, pre_mask), (c_kv, k_r, own_mask)])

        x, cache, counters = _layer(lp, cfg, x, positions, valid, inv_freq, moe, attend)
        return x, carry, cache, counters

    x, _, (c_sfx, r_sfx), counters = _run_stacks(
        params, cfg, _stream(params, tokens), (), (prefix_c, prefix_r), step)
    return _last_valid_logits(params, cfg, x.astype(cfg.dtype), suffix_lens), c_sfx, r_sfx, counters


# ------------------------------------------------------------- block decode
def forward_block_decode(
    params: Params,
    cfg: MlaMoeConfig,
    blk_tok: jax.Array,    # [R, F] int32, this iteration's token block
    blk_valid: jax.Array,  # [R, F] bool, left-aligned valid tokens
    blk_len: jax.Array,    # [R] int32
    positions: jax.Array,  # [R, F] absolute positions
    c_sfx: jax.Array,      # [L, R, Ss, dc] latent suffix cache
    r_sfx: jax.Array,      # [L, R, Ss, dr]
    suffix_lens: jax.Array,  # [R]
    gen_c: jax.Array,      # [L, R, cap+F, dc] generated-token latents
    gen_r: jax.Array,      # [L, R, cap+F, dr]
    tail: jax.Array,       # [R] tokens already in gen_c / gen_r
    prefix_c: jax.Array,   # [L, Sp, dc] shared latent prefix
    prefix_r: jax.Array,
    prefix_len: jax.Array,
    prefix_impl: Any = None,
    ragged: bool = False,
):
    """One grammar-accelerated decode iteration (models/llama.py
    `forward_block_decode` says what that is) through the latent caches:
    (logits [R, V] f32 at each row's last valid position, gen_c, gen_r,
    COUNTERS). Padding positions of the block are not routed; the block's
    latents are written once every layer has run (ops/attention.write_block)."""
    if ragged:
        raise ValueError(f"{cfg.name}: llm.decode_matmul 'ragged' is not served by models/mla_moe.py")
    inv_freq = _inv_freq(cfg)
    j = jnp.arange(blk_tok.shape[1])
    pre_mask = (jnp.arange(prefix_c.shape[1]) < prefix_len)[None, None, None, :]
    sfx_mask = (jnp.arange(c_sfx.shape[2])[None, :] < suffix_lens[:, None])[:, None, None, :]
    gen_mask = (jnp.arange(gen_c.shape[2])[None, :] < tail[:, None])[:, None, None, :]
    blk_mask = ((j[:, None] >= j[None, :])[None] & blk_valid[:, None, :])[:, None]

    def step(lp, moe, x, carry, xs_l, idx):
        pc, pr, sc, sr = xs_l

        def attend(lp, q_nope, q_rope, c_kv, k_r):
            # gen_mask exposes entries < tail only: never this block's own
            return attend_absorbed(lp, cfg, q_nope, q_rope, [
                (pc, pr, pre_mask), (sc, sr, sfx_mask),
                (gen_c[idx], gen_r[idx], gen_mask), (c_kv, k_r, blk_mask),
            ])

        x, cache, counters = _layer(lp, cfg, x, positions, blk_valid, inv_freq, moe, attend)
        return x, carry, cache, counters

    x, _, (c_blk, r_blk), counters = _run_stacks(
        params, cfg, _stream(params, blk_tok), (),
        (prefix_c, prefix_r, c_sfx, r_sfx), step)
    with jax.named_scope("kv_writeback"):
        gen_c = write_block(gen_c, tail, c_blk)
        gen_r = write_block(gen_r, tail, r_blk)
    return _last_valid_logits(params, cfg, x.astype(cfg.dtype), blk_len), gen_c, gen_r, counters
