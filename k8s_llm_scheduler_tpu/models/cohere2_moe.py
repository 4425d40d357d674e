"""Window attention and global attention without position encoding, three
layers to one, each layer a PARALLEL block of attention and a sparse-expert
feed-forward on one LayerNorm of the stream, in functional JAX: the
`cohere2_moe` layer (Command A+ 218B-A25B), on the decision path.

THE LAYER EQUATIONS. `x` is the residual stream, KEPT IN FLOAT32 as in
models/gdn_moe.py. D = d_model; H query heads and Hkv key/value heads of
width hd (GQA groups of H / Hkv); bf16 weights, no biases.

- Embedding: `x0 = E[token]`.
- Every layer: `h = LN(x)`, Cohere's LayerNorm: `(x - mean x) rsqrt(var x +
  eps) w`, a weight and no bias, in float32. Then `x <- x + Attn(h) +
  FFN(h)`: one norm a layer, the two sublayers side by side (the parallel
  block, `use_parallel_block`).
- Attention: `q = h W_q`, `k = h W_k`, `v = h W_v`, no q or k norm;
  softmax of `q k / sqrt(hd)` in float32; `o W_o`.
  - Window layers (three of every four, the period's first three:
    `local_attn_first`): q and k rotated at the token's position (theta
    `rope_theta` over all hd dims; GPT-J pairs as published, run here in the
    repo's half-split layout, which is a fixed permutation of each head's
    columns of W_q and W_k); query i sees key j iff `i - window < j <= i`.
  - Global layers (the period's last): no position encoding, causal.
- FFN: `s = sigmoid(h W_r)` in float32 over all `n_routed_experts`
  outputs; the `n_experts_per_tok` largest s, no selection bias; weights
  `w_i = s_i / sum_top s` (`norm_topk_prob`), no routed scaling. Routed
  part `sum w_i SwiGLU_i(h)` (width d_ff_expert each); shared part the MEAN
  of `n_shared_experts` SwiGLUs of the same width
  (`shared_expert_combination_strategy` "average"), run as one SwiGLU of
  their widths side by side whose output is times 1 / n_shared_experts.
  The layer holds experts `expert_first .. + experts_held`, routes over all
  of them and computes its own experts' part (models/mla_moe.py
  `routed_experts`, which this family calls as it is, with
  `shared_experts`); a pick on an expert held elsewhere adds nothing here.
  PADDING IS NOT ROUTED.
- Head: `LN_f(x) E^T logit_scale`, the embedding table tied.

WHAT A SEQUENCE CARRIES: (k, v) [n_layers, ..., tokens, Hkv, hd], every
layer's, as the dense family's; a window layer's cache holds the whole
sequence too (a pin that keeps only a window's tail is not written). THE
WINDOW IS APPLIED WHERE A QUERY MEETS A CACHE: the prefix through
ops/attention.py `prefix_attend_parts(window=...)` (on the chip a Pallas
kernel that never visits a key block wholly below the window), the suffix,
the generated tokens and the block through masks. A chunk or a suffix no
longer than the window sees itself causally, as without one.

Params: `params["layers"]` holds every leaf with the layer as its leading
axis. ONE `lax.scan` over the periods, the period's layers written out in
its body; every weight is read out of its whole stack at a traced index
(models/mla_scmoe.py `_layer` says why), the routed experts' stacks handed
to the grouped kernels whole.

The three entry points keep models/llama.py's contracts; the two wave
forwards return COUNTERS behind the cache: the routed layer's (EXPERT and
BOUND, summed over layers), WINDOW_COUNTERS and BLOCK_PREFIX_COUNTERS (once
a call: the prefix query positions block decode computed in every layer,
narrowed and at full width; models/llama.py).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from k8s_llm_scheduler_tpu.models.configs import Cohere2MoeConfig
from k8s_llm_scheduler_tpu.models.llama import apply_rope
from k8s_llm_scheduler_tpu.models.mla_moe import BOUND_COUNTERS, EXPERT_LEAVES, routed_experts, shared_experts
from k8s_llm_scheduler_tpu.models.mla_moe import COUNTERS as EXPERT_COUNTERS
from k8s_llm_scheduler_tpu.ops.attention import (
    BLOCK_PREFIX_COUNTERS,
    attend_part,
    block_prefix_attend_parts,
    block_prefix_counts,
    block_prefix_rung,
    causal_chunk_attend_parts,
    merge_attention_parts,
    prefix_attend_parts,
    window_prefix_keys_read,
    write_block,
)

Params = dict[str, Any]

# Of the prefix keys a causal layer would read, what a window layer's
# attention reads: the valid queries of a call (suffix or served tokens),
# each counted as the prefix keys the windowed attention visits for it (the
# kernel's key blocks; ops/attention.py window_prefix_keys_read) and as
# prefix_len, once a call, not a layer. The names
# benchmark/metrics/window_kv_read_share.py reads.
WINDOW_COUNTERS = ("window_keys_read", "window_keys_causal")
COUNTERS = EXPERT_COUNTERS + BOUND_COUNTERS + WINDOW_COUNTERS + BLOCK_PREFIX_COUNTERS

# What the paged pool and a tp mesh would need of this family, for the
# refusals that name it (engine/engine.py, engine/local.py).
PAGED_MISSING = "a window"
UNSHARDED = "its attention is data-parallel, each chip its own requests"

ATTN_LEAVES = ("wq", "wk", "wv", "wo")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")
# The tied table's init (`init_params` says why; benchmark/configs/command-a-plus-05-2026.json
# `assumed` holds the readings).
EMBED_STD = 0.02
# W_q's draw is times Q_GAIN: the attention's logits have std Q_GAIN
# (`init_params` says why).
Q_GAIN = 2.5


# ------------------------------------------------------- what a sequence carries
def cache_token_shapes(cfg: Cohere2MoeConfig) -> tuple[tuple[int, ...], ...]:
    """Per-token trailing shapes of the cache tuple: (k, v)."""
    return ((cfg.n_kv_heads, cfg.head_dim),) * 2


def cache_layers(cfg: Cohere2MoeConfig) -> int:
    """Leading axis of the cache tuple: every layer attends."""
    return cfg.n_layers


def state_shapes(cfg) -> tuple:
    """What a sequence carries besides its per-token cache: nothing."""
    return ()


def state_layers(cfg) -> int:
    return 0


# --------------------------------------------------------------------- init
def init_params(rng: jax.Array, cfg: Cohere2MoeConfig, quantize: str | None = None) -> Params:
    """Random init: normal x 1/sqrt(fan_in) for every matrix, drawn a layer
    at a time (`lax.map`: the float32 draw of a whole stack of experts would
    not fit beside the weights), norms at one. THE TIED TABLE is drawn at
    EMBED_STD: it is the head too, so the part of the final stream that is
    its own token's embedding enters every logit as E[token] . E[v], a term
    no layer computes and no precision moves; at 0.02 the layers carry the
    stream (models/mamba2_hybrid.py `init_params` says what a larger draw
    did to a comparison). W_Q IS DRAWN TIMES Q_GAIN, so that the attention's
    logits have std Q_GAIN: at unit std a query spreads its weight over ~n/e
    of a 10k-token prefix's keys, every row of a wave reads the same average
    of the prefix, greedy decoding serves every row the same tokens, and on
    the chip neither the program nor the int8 control moved one choice of
    1,728 on three seeds; at 2.5 a query weighs ~n e^-6.25 keys (~20 of
    10k, ~8 of a 4,096 window) and the control separates
    (benchmark/configs/command-a-plus-05-2026.json `assumed` holds the
    readings). W_Q AND W_K ARE KEPT BY HEAD, [L, heads, hd, D] (drawn [D,
    heads hd] as the other matrices, then transposed): compiled for a v5e,
    block decode copied every layer's W_q out of a [D, H hd] or [H hd, D]
    stack on each model call (512 MB, a tenth of the device's time on the
    command-a-plus cell), and W_k's out of [D, Hkv hd]; by head, the
    projections read them where they lie."""
    if quantize is not None:
        raise ValueError(
            f"{cfg.name}: llm.quantization {quantize!r} is not served by models/cohere2_moe.py"
        )
    D, L, H, Hkv, hd = cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, Fe, Fs = cfg.experts_held, cfg.d_ff_expert, cfg.d_ff_shared
    k_embed, k_layers = jax.random.split(rng)
    k = jax.random.split(k_layers, 11)

    def dense(key, shape, fan_in, gain=1.0):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * (gain * fan_in**-0.5)).astype(cfg.dtype)

    def stacked(key, shape, fan_in, gain=1.0):
        return jax.lax.map(lambda kl: dense(kl, shape, fan_in, gain), jax.random.split(key, L))

    return {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, D), dtype=jnp.float32) * EMBED_STD
                  ).astype(cfg.dtype),
        "final_norm": jnp.ones((D,), cfg.dtype),
        "layers": {
            "attn_norm": jnp.ones((L, D), cfg.dtype),
            "wq": jnp.swapaxes(stacked(k[0], (D, H * hd), D, Q_GAIN), 1, 2).reshape(L, H, hd, D),
            "wk": jnp.swapaxes(stacked(k[1], (D, Hkv * hd), D), 1, 2).reshape(L, Hkv, hd, D),
            "wv": stacked(k[2], (D, Hkv * hd), D),
            "wo": stacked(k[3], (H * hd, D), H * hd),
            "router": stacked(k[4], (D, cfg.n_routed_experts), D),
            "we_gate": stacked(k[5], (E, D, Fe), D),
            "we_up": stacked(k[6], (E, D, Fe), D),
            "we_down": stacked(k[7], (E, Fe, D), Fe),
            "ws_gate": stacked(k[8], (D, Fs), D),
            "ws_up": stacked(k[9], (D, Fs), D),
            "ws_down": stacked(k[10], (Fs, D), Fs),
        },
    }


# -------------------------------------------------------------------- norms
def layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """(x - mean) rsqrt(var + eps) w, in float32: Cohere's LayerNorm, no bias."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


@jax.named_scope("embed")
def _stream(params: Params, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens].astype(jnp.float32)


@jax.named_scope("lm_head")
def _last_valid_logits(params: Params, cfg: Cohere2MoeConfig, x: jax.Array, lens: jax.Array) -> jax.Array:
    """Logits [B, V] f32 at each row's last valid token, through the tied
    table, times logit_scale."""
    last = jnp.maximum(lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    h = layer_norm(x_last, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
    logits = jnp.einsum("bd,vd->bv", h, params["embed"], preferred_element_type=jnp.float32)
    return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


def _inv_freq(cfg: Cohere2MoeConfig) -> jax.Array:
    hd = cfg.head_dim
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


# ---------------------------------------------------------------- attention
def attention(lp: Params, cfg: Cohere2MoeConfig, u: jax.Array, positions, inv_freq, rotate: bool, attend):
    """The attention's output [B, S, D] f32 for normed tokens u (the weights'
    dtype) and the (k, v) [B, S, Hkv, hd] of these tokens, rotated where
    `rotate`. `attend(q, qg, k, v)`: the flash parts of what the queries may
    see, merged, [B, Hkv, G, S, hd]; q [B, S, H, hd] in the kernels' layout,
    qg [B, S, Hkv, G, hd] float32 times hd^-1/2."""
    B, S, _ = u.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,hkd->bshk", u, lp["wq"])
    k = jnp.einsum("bsd,hkd->bshk", u, lp["wk"])
    v = jnp.einsum("bsd,dn->bsn", u, lp["wv"]).reshape(B, S, Hkv, hd)
    if rotate:
        q, k = apply_rope(q, positions, inv_freq), apply_rope(k, positions, inv_freq)
    qg = (q.astype(jnp.float32) * hd**-0.5).reshape(B, S, Hkv, H // Hkv, hd)
    o = jnp.moveaxis(attend(q, qg, k, v), 3, 1).reshape(B, S, H * hd)
    out = jnp.einsum("bsn,nd->bsd", o.astype(cfg.dtype), lp["wo"], preferred_element_type=jnp.float32)
    return out, (k, v)


def own_parts(q, qg, k, v, lens, window: int | None, impl):
    """Flash parts of a call's queries against its own keys, causal, row b's
    first lens[b] valid. A window no shorter than the call cannot bind: the
    plain causal parts (the kernel on the chip); else the einsum with the
    window in its mask."""
    S = q.shape[1]
    if window is None or S <= window:
        return causal_chunk_attend_parts(q, qg, k, v, lens, impl=impl)
    i = jnp.arange(S)
    seen = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < window)
    mask = seen[None] & (i[None, None, :] < lens[:, None, None])
    return attend_part(qg, k, v, mask[:, None, None], "bqkgh,bskh->bkgqs")


# ------------------------------------------------------------- feed-forward
@jax.named_scope("mlp")
def _sparse_block(layers: Params, idx, cfg: Cohere2MoeConfig, h: jax.Array, valid: jax.Array):
    """(the feed-forward's output in float32, EXPERT + BOUND counters) for
    the normed stream h (float32: the router reads it unrounded) of layer
    `idx` of the whole stack `layers`."""
    flat = h.reshape(-1, h.shape[-1])
    routed = {"router": layers["router"][idx], **{k: layers[k] for k in EXPERT_LEAVES}, "layer": idx}
    y, counters = routed_experts(routed, cfg, flat, valid.reshape(-1))
    if counters.shape[0] == len(EXPERT_COUNTERS):
        # a layer that holds every expert has no short path: every call is within its bound
        counters = jnp.concatenate([counters, jnp.ones((1,), jnp.int32)])
    y = y + shared_experts({k: layers[k][idx] for k in SHARED_LEAVES}, flat, cfg.shared_scale)
    return y.reshape(h.shape), counters


# ----------------------------------------------------------- the layer scan
def _run_periods(params, cfg: Cohere2MoeConfig, x, valid, positions, cache_xs, attend):
    """Every layer over the float32 stream x [B, S, D]: a scan over the
    periods, the period's window layers and its global layer written out in
    its body. `cache_xs`: cache arrays [L, ..], handed to the scan a period
    at a time; `attend(cache_l, j, idx, window, q, qg, k, v)` says what the
    queries of layer `idx` (place j of its period, `cache_l` its period's
    [period, ..] slice) may see, `window` the window or None. Returns (x,
    (k, v) of these tokens [L, B, S, Hkv, hd], EXPERT + BOUND counters)."""
    per, at_global = cfg.period, cfg.global_position
    layers = params["layers"]
    inv_freq = _inv_freq(cfg)
    periods = tuple(a.reshape(cfg.n_periods, per, *a.shape[1:]) for a in cache_xs)

    def body(carry, inp):
        x, counters = carry
        cache_l, p = inp
        kvs = []
        for j in range(per):
            idx = p * per + j
            window = None if j == at_global else cfg.window
            h = layer_norm(x, layers["attn_norm"][idx], cfg.norm_eps)
            with jax.named_scope("attn"), jax.named_scope("full_attn" if window is None else "swa_attn"):
                y, kv = attention({k_: layers[k_][idx] for k_ in ATTN_LEAVES}, cfg, h.astype(cfg.dtype),
                                  positions, inv_freq, window is not None,
                                  lambda *qkv: attend(cache_l, j, idx, window, *qkv))
            kvs.append(kv)
            y_ffn, c = _sparse_block(layers, idx, cfg, h, valid)
            x = x + y + y_ffn
            counters = counters + c
        return (x, counters), tuple(jnp.stack(a) for a in zip(*kvs))

    zero = jnp.zeros((len(EXPERT_COUNTERS) + len(BOUND_COUNTERS),), jnp.int32)
    (x, counters), (k, v) = jax.lax.scan(body, (x, zero), (periods, jnp.arange(cfg.n_periods)))
    flat = lambda a: a.reshape(cfg.n_layers, *a.shape[2:])  # noqa: E731
    return x, (flat(k), flat(v)), counters


def _with_window_counters(cfg: Cohere2MoeConfig, counters, valid, q_shape, prefix_k, prefix_len, impl,
                          block_prefix):
    """COUNTERS: the routed layer's, then, over the call's valid queries,
    the prefix keys a window layer's attention reads (a static count a
    query, what its kernel visits) and those a causal layer would, then the
    call's BLOCK_PREFIX_COUNTERS."""
    rows = jnp.sum(valid.astype(jnp.int32))
    read = window_prefix_keys_read(q_shape, prefix_k.shape[1:], cfg.window, impl)
    return jnp.concatenate([counters, jnp.stack([rows * read, rows * prefix_len]).astype(jnp.int32),
                            block_prefix])


def _lowest_key(cfg: Cohere2MoeConfig, positions):
    """The lowest position a window query at `positions` sees."""
    return positions - (cfg.window - 1)


# ------------------------------------------------------------------ prefill
def forward_prefill_kv(params: Params, cfg: Cohere2MoeConfig, tokens, seq_lens):
    """Full-prompt prefill for the cache alone, under scope
    `prefix_prefill`: (None, k [L, B, S, Hkv, hd], v)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    valid = positions < seq_lens[:, None]

    def attend(_cache, _j, _idx, window, q, qg, k, v):
        return merge_attention_parts([own_parts(q, qg, k, v, seq_lens, window, None)])

    with jax.named_scope("prefix_prefill"):
        _, (k_all, v_all), _ = _run_periods(params, cfg, _stream(params, tokens), valid, positions, (), attend)
    return None, k_all, v_all


def forward_prefill_suffix_dense(
    params: Params,
    cfg: Cohere2MoeConfig,
    tokens: jax.Array,       # [B, Ss] int32, per-request suffix, left-aligned
    suffix_lens: jax.Array,  # [B] valid suffix tokens (0 = row unused)
    prefix_k: jax.Array,     # [L, Sp, Hkv, hd] shared prefix cache
    prefix_v: jax.Array,
    prefix_len: jax.Array,   # scalar int32
    prefix_impl: Any = None,  # static: ops/attention.prefix_attend_parts
):
    """Batched suffix prefill against the shared prefix (also every chunk of
    the engine's chunked prefix prefill, a one-row call): a window layer's
    query at position prefix_len + s sees the prefix from prefix_len + s -
    window + 1 on. Returns (last_logits [B, V] f32, k_sfx [L, B, Ss, Hkv,
    hd], v_sfx, COUNTERS)."""
    B, S = tokens.shape
    j = jnp.arange(S)
    positions = prefix_len + jnp.broadcast_to(j, (B, S))
    valid = j[None, :] < suffix_lens[:, None]
    lowest = _lowest_key(cfg, positions)

    def attend(cache_l, jj, _idx, window, q, qg, k, v):
        pk, pv = cache_l[0][jj], cache_l[1][jj]
        seen = None if window is None else (window, lowest)
        return merge_attention_parts([
            prefix_attend_parts(q, qg, pk, pv, prefix_len, impl=prefix_impl, window=seen),
            own_parts(q, qg, k, v, suffix_lens, window, prefix_impl)])

    x, (k_sfx, v_sfx), counters = _run_periods(
        params, cfg, _stream(params, tokens), valid, positions, (prefix_k, prefix_v), attend)
    return (_last_valid_logits(params, cfg, x, suffix_lens), k_sfx, v_sfx,
            _with_window_counters(cfg, counters, valid, (B, S, cfg.n_heads, cfg.head_dim), prefix_k,
                                  prefix_len, prefix_impl, jnp.zeros((len(BLOCK_PREFIX_COUNTERS),), jnp.int32)))


# ------------------------------------------------------------- block decode
def forward_block_decode(
    params: Params,
    cfg: Cohere2MoeConfig,
    blk_tok: jax.Array,    # [R, F] int32, this iteration's token block
    blk_valid: jax.Array,  # [R, F] bool, left-aligned valid tokens
    blk_len: jax.Array,    # [R] int32
    positions: jax.Array,  # [R, F] absolute positions
    k_sfx: jax.Array,      # [L, R, Ss, Hkv, hd] suffix cache
    v_sfx: jax.Array,
    suffix_lens: jax.Array,  # [R]
    gen_k: jax.Array,      # [L, R, cap+F, Hkv, hd] generated-token cache
    gen_v: jax.Array,
    tail: jax.Array,       # [R] tokens already in gen_k / gen_v
    prefix_k: jax.Array,   # [L, Sp, Hkv, hd] shared prefix cache
    prefix_v: jax.Array,
    prefix_len: jax.Array,
    prefix_impl: Any = None,
    ragged: bool = False,
):
    """One grammar-accelerated decode iteration (models/llama.py
    `forward_block_decode` says what that is). A window layer's query at
    position p sees every cached token at positions p - window < t <= p:
    prefix token t at t, suffix token t at prefix_len + t, generated token t
    at prefix_len + suffix_len + t, block token at its position. The
    prefix part of every layer runs at the block positions the call
    carries (ops/attention.py block_prefix_attend_parts). Returns (logits
    [R, V] f32 at each row's last valid position, gen_k, gen_v,
    COUNTERS)."""
    if ragged:
        raise ValueError(f"{cfg.name}: llm.decode_matmul 'ragged' is not served by models/cohere2_moe.py")
    R, F = blk_tok.shape
    jf = jnp.arange(F)
    lowest = _lowest_key(cfg, positions)                       # [R, F]
    t_sfx = prefix_len + jnp.arange(k_sfx.shape[2])            # [Ss]
    t_gen = (prefix_len + suffix_lens)[:, None] + jnp.arange(gen_k.shape[2])[None, :]  # [R, cap+F]
    sfx_ok = (jnp.arange(k_sfx.shape[2])[None, :] < suffix_lens[:, None])[:, None, :]
    gen_ok = (jnp.arange(gen_k.shape[2])[None, :] < tail[:, None])[:, None, :]
    blk_ok = (jf[:, None] >= jf[None, :])[None] & blk_valid[:, None, :]
    masks = {
        None: (sfx_ok, gen_ok, blk_ok),
        cfg.window: (sfx_ok & (t_sfx[None, None, :] >= lowest[:, :, None]),
                     gen_ok & (t_gen[:, None, :] >= lowest[:, :, None]),
                     blk_ok & (positions[:, None, :] >= lowest[:, :, None])),
    }
    eq = "bqkgh,bskh->bkgqs"
    rung = block_prefix_rung(blk_len, F)

    def attend(cache_l, jj, idx, window, q, qg, k, v):
        pk, pv, sk, sv = (a[jj] for a in cache_l)
        sfx_mask, gen_mask, blk_mask = (m[:, None, None] for m in masks[window])
        seen = None if window is None else (window, lowest)
        # gen_mask exposes entries < tail only: never this block's own
        return merge_attention_parts([
            block_prefix_attend_parts(rung, q, qg, pk, pv, prefix_len, impl=prefix_impl, window=seen),
            attend_part(qg, sk, sv, sfx_mask, eq), attend_part(qg, gen_k[idx], gen_v[idx], gen_mask, eq),
            attend_part(qg, k, v, blk_mask, eq)])

    x, (k_blk, v_blk), counters = _run_periods(
        params, cfg, _stream(params, blk_tok), blk_valid, positions,
        (prefix_k, prefix_v, k_sfx, v_sfx), attend)
    with jax.named_scope("kv_writeback"):
        gen_k = write_block(gen_k, tail, k_blk)
        gen_v = write_block(gen_v, tail, v_blk)
    return (_last_valid_logits(params, cfg, x, blk_len), gen_k, gen_v,
            _with_window_counters(cfg, counters, blk_valid, (R, F, cfg.n_heads, cfg.head_dim), prefix_k,
                                  prefix_len, prefix_impl, block_prefix_counts(rung, R, F)))
