"""Mamba-2 state-space mixers and softmax attention without position
encoding, in a fixed period, over dense SwiGLUs, in functional JAX: the
`granitemoehybrid` layer with no experts (Granite 4.0-H Micro), on the
decision path. The whole model on one chip.

THE LAYER EQUATIONS. `x` is the residual stream, KEPT IN FLOAT32 as in
models/gdn_moe.py. Every norm is `rms(u) = u rsqrt(mean u^2 + eps) w`, with
a plain weight (not 1 + w). D = d_model; m = `residual_multiplier`.

- Embedding: `x0 = embedding_multiplier E[token]`.
- Layer i: `h = x + m mixer_i(rms(x))`, `out = h + m W_down(silu(u W_gate)
  * u W_up)` with `u = rms(h)` (W_in holds [gate | up]); `mixer_i` is the
  attention where i is in `attn_layers`, else the Mamba-2 mixer.
- Mamba-2 mixer (H heads of width P, state width N, one group of B and C,
  inner width H P): `[z | xBC | dt] = u W_in`, no bias; `xBC <-
  silu(conv(xBC) + b_conv)`, causal, depthwise over the H P + 2 N channels,
  `conv_kernel` taps, the last meeting the token itself; split xBC into x
  [H, P], B [N], C [N]; `dt <- softplus(dt + dt_bias)` (no clamp), `A =
  -exp(A_log)`, both a head. For each head, token by token: `S <- e^{dt_t
  A} S + dt_t x_t B_t^T` with S [P, N]; `y_t = S C_t + D x_t`. Then `y <-
  rms(y silu(z))` over the WHOLE inner width (the norm after the gate), then
  W_out. THE PROGRAM RUNS THE CHUNKED FORM of that recurrence as ONE KERNEL
  (ops/ssd_scan.py writes the form out): a (row, block of heads)'s state
  read from HBM once, held in VMEM across the call's chunks and written
  once, WHERE IT LIES. A position that is not valid has dt = 0, which
  leaves the state as it was BY CONSTRUCTION, and the convolution's window
  is cut at the row's valid length.
- Attention (H query heads, Hkv key/value heads, width hd = D / H): `W_q`,
  `W_k`, `W_v`, `W_o`, no bias, no rotary, no q or k norm; causal softmax of
  q k `attention_multiplier`. The flash kernels of ops/pallas_prefix_attention.py
  scale q by hd^-1/2 themselves, so q is handed to them times
  `attention_multiplier` sqrt(hd).
- Head: `rms(x_L) E^T / logits_scaling`, the embedding table tied.

WHAT A SEQUENCE CARRIES. The attention layers have a per-token cache, (k, v)
[n_attn_layers, ..., tokens, Hkv, hd], as the dense family's. The Mamba-2
layers have a STATE a sequence: S [H, P, N] float32 and the last
`conv_kernel - 1` inputs of the convolution [conv_kernel - 1, channels],
after a given number of tokens. `state_shapes` lists them ONE MEMBER A
MAMBA-2 POSITION OF THE PERIOD (S of the period's first, second, .. Mamba-2
layer, then their windows), each with the periods as its leading axis. The
matrices ride the layer scan's CARRY whole and each layer's kernel advances
its own entry in place (models/gdn_moe.py says why a state kept as one
array cost two copies of it a period); the windows are small and are
scanned over. The three forwards take and return the state beside the cache
(`state=`), as models/gdn_moe.py's do.

Params: `params["layers"]` holds what every layer has (two norms, the
SwiGLU; leading axis = layer), `params["ssm"]` the Mamba-2 mixers (leading
axis = their count, in layer order) and `params["attn"]` the attentions. ONE
`lax.scan` over the periods, the period's layers written out in its body;
every weight is read out of its whole stack at a traced index
(models/mla_scmoe.py `_layer` says why).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from k8s_llm_scheduler_tpu.models.configs import Mamba2HybridConfig
from k8s_llm_scheduler_tpu.models._state import STATE_COUNTERS, window_at
from k8s_llm_scheduler_tpu.ops.attention import (
    BLOCK_PREFIX_COUNTERS,
    attend_part,
    block_prefix_attend_parts,
    block_prefix_counts,
    block_prefix_rung,
    causal_chunk_attend_parts,
    merge_attention_parts,
    prefix_attend_parts,
    write_block,
)
from k8s_llm_scheduler_tpu.ops.ssd_scan import ssd_chunk_scan

Params = dict[str, Any]

# What a wave's Mamba-2 layers count (models/_state.py says what), then the
# prefix query positions its block decode's attention computed
# (models/llama.py says what).
COUNTERS = STATE_COUNTERS + BLOCK_PREFIX_COUNTERS

# Positions a chunk of the scan holds in prefill; block decode's chunk is
# the block. With no solve a chunk's cost a position grows with the chunk
# through C B^T alone, and the products with the state are once a chunk.
CHUNK = 64
# The tied table's init (`init_params` says why; benchmark/configs/granite-4_0-h-micro.json
# `assumed` holds the readings).
EMBED_STD = 0.02


# ------------------------------------------------------- what a sequence carries
def cache_token_shapes(cfg: Mamba2HybridConfig) -> tuple[tuple[int, ...], ...]:
    """Per-token trailing shapes of the cache tuple: (k, v) of the layers
    that attend."""
    return ((cfg.n_kv_heads, cfg.head_dim),) * 2


def cache_layers(cfg: Mamba2HybridConfig) -> int:
    """Leading axis of the cache tuple: the attention layers."""
    return cfg.n_attn_layers


def state_shapes(cfg: Mamba2HybridConfig) -> tuple[tuple[tuple[int, ...], Any], ...]:
    """(trailing shape, dtype) of each member of the per-sequence state: S a
    head for each Mamba-2 position of the period, then the convolution's
    window for each."""
    n = cfg.period - 1
    s = ((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
    w = ((cfg.conv_kernel - 1, cfg.conv_width), jnp.float32)
    return (s,) * n + (w,) * n


def state_layers(cfg: Mamba2HybridConfig) -> int:
    """Leading axis of every state member: the periods."""
    return cfg.n_periods


def zero_state(cfg: Mamba2HybridConfig, *lead: int) -> tuple[jax.Array, ...]:
    """The state before any token: [n_periods, *lead, *shape] zeros."""
    return tuple(jnp.zeros((cfg.n_periods, *lead, *shape), dtype)
                 for shape, dtype in state_shapes(cfg))


# --------------------------------------------------------------------- init
def init_params(rng: jax.Array, cfg: Mamba2HybridConfig, quantize: str | None = None) -> Params:
    """Random init: normal x 1/sqrt(fan_in) for every matrix, norms at one,
    the convolution's taps normal x 1/sqrt(conv_kernel) and its bias uniform
    on +-1/sqrt(conv_kernel), D at one. THE TIED TABLE is drawn at
    EMBED_STD: the table is the head too, so the part of the final stream
    that is its own token's embedding (x0 = embedding_multiplier E[token])
    enters every logit as E[token] . E[v], a term no layer computes and no
    precision moves; drawn at the scale that makes logits of unit scale it
    carries the stream (cosine 0.94 with the own embedding after one
    period) and decides every choice, so no rounding of the layers shows (on
    the chip, 0 of 11,520 choices moved for the program and 2 for the int8
    control). At 0.02 the layers carry the stream and the logits are of
    scale ~0.12 at the published widths. THE DECAY IS DRAWN SMALL, as the
    Mamba-2 reference init draws it: dt log-uniform on (1e-3, 1e-1) through
    the inverse softplus into `dt_bias`, A uniform on (1, 16), so that a head
    forgets at dt A ~ 1e-3 .. 1.6 a token (benchmark/configs/granite-4_0-h-micro.json
    `assumed` holds the readings)."""
    if quantize is not None:
        raise ValueError(
            f"{cfg.name}: llm.quantization {quantize!r} is not served by "
            f"models/mamba2_hybrid.py"
        )
    D, L, Lm, La, F = cfg.d_model, cfg.n_layers, cfg.n_ssm_layers, cfg.n_attn_layers, cfg.d_ff
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hs, inner, cw, taps = cfg.ssm_heads, cfg.ssm_inner, cfg.conv_width, cfg.conv_kernel
    k_embed, k_layers, k_ssm, k_attn = jax.random.split(rng, 4)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5).astype(cfg.dtype)

    def ones(shape):
        return jnp.ones(shape, dtype=cfg.dtype)

    kl, ks, ka = jax.random.split(k_layers, 2), jax.random.split(k_ssm, 6), jax.random.split(k_attn, 4)
    dt = jnp.exp(jax.random.uniform(ks[3], (Lm, Hs), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    a = jax.random.uniform(ks[4], (Lm, Hs), jnp.float32, 1.0, 16.0)
    return {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, D), dtype=jnp.float32) * EMBED_STD).astype(cfg.dtype),
        "final_norm": ones((D,)),
        "layers": {
            "attn_norm": ones((L, D)),
            "mlp_norm": ones((L, D)),
            "w_in": dense(kl[0], (L, D, 2 * F), D),
            "w_out": dense(kl[1], (L, F, D), F),
        },
        "ssm": {
            "w_in": dense(ks[0], (Lm, D, inner + cw + Hs), D),
            "conv": dense(ks[1], (Lm, taps, cw), taps),
            "conv_bias": (jax.random.uniform(ks[2], (Lm, cw), jnp.float32, -1.0, 1.0) * taps**-0.5
                          ).astype(cfg.dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) = dt
            "A_log": jnp.log(a),
            "D": jnp.ones((Lm, Hs), jnp.float32),
            "norm": ones((Lm, inner)),
            "w_out": dense(ks[5], (Lm, inner, D), inner),
        },
        "attn": {
            "wq": dense(ka[0], (La, D, H * hd), D),
            "wk": dense(ka[1], (La, D, Hkv * hd), D),
            "wv": dense(ka[2], (La, D, Hkv * hd), D),
            "wo": dense(ka[3], (La, H * hd, D), H * hd),
        },
    }


# -------------------------------------------------------------------- norms
def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """x rsqrt(mean x^2 + eps) w, in float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


@jax.named_scope("embed")
def _stream(params: Params, cfg: Mamba2HybridConfig, tokens: jax.Array) -> jax.Array:
    """The residual stream at its start, in float32."""
    return params["embed"][tokens].astype(jnp.float32) * cfg.embedding_multiplier


@jax.named_scope("lm_head")
def _last_valid_logits(params: Params, cfg: Mamba2HybridConfig, x: jax.Array, lens: jax.Array) -> jax.Array:
    """Logits [B, V] f32 at each row's last valid token of the stream x,
    through the tied table."""
    last = jnp.maximum(lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    h = _rms(x_last, params["final_norm"], cfg.rms_eps).astype(cfg.dtype)
    logits = jnp.einsum("bd,vd->bv", h, params["embed"], preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


# ----------------------------------------------------------------- Mamba-2
def _chunk(S: int) -> int:
    """The chunk of a call S wide: the call itself up to CHUNK positions."""
    return min(S, CHUNK)


def _chunked(S: int) -> int:
    """Positions the scan runs over for a call S wide."""
    return -(-S // _chunk(S)) * _chunk(S)


def ssd_chunks(x, dt, a, b, c, lens, state, period, chunk: int):
    """The Mamba-2 recurrence over T = n x `chunk` positions in its chunked
    form, which ops/ssd_scan.py writes out and runs as one kernel. x [B, H,
    T, P], dt [B, H, T] (0 where a position is not valid), a [H] (A, < 0),
    b and c [B, T, N], all float32; row r's first `lens[r]` positions are
    valid; `state` [periods, B, H, P, N] is a whole member of what the
    sequences carry, of which these rows' is entry `period`. Returns (y [B,
    H, T, P] without the skip, `state` with that entry after the T
    positions, UPDATED WHERE IT LIES)."""
    B, H, T, P = x.shape
    n = T // chunk
    gamma = jnp.cumsum((dt * a[None, :, None]).reshape(B, H, n, chunk), axis=-1)
    y, state = ssd_chunk_scan(x.reshape(B, H, n, chunk, P), dt.reshape(B, H, n, chunk), gamma,
                              b.reshape(B, n, chunk, -1), c.reshape(B, n, chunk, -1), lens, state, period)
    return y.reshape(B, H, T, P), state


def ssm_mixer(lp: Params, cfg: Mamba2HybridConfig, u: jax.Array, valid: jax.Array, lens: jax.Array,
              state: jax.Array, period, window: jax.Array):
    """The Mamba-2 mixer's output [B, S, D] f32 for normed tokens u [B, S, D]
    (the weights' dtype), of which row r's first `lens[r]` are `valid` [B,
    S]; entry `period` of `state` [periods, B, H, P, N] and `window` [B,
    conv_kernel - 1, channels] are each row's state before the call. Returns
    (output, `state` with that entry after each row's valid tokens, the
    window at them). S is padded up to whole chunks here; padding is not
    valid."""
    B, S, _ = u.shape
    H, P, N, inner, taps = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_inner, cfg.conv_kernel
    with jax.named_scope("ssm_proj"):
        zxd = jnp.einsum("bsd,dn->bsn", u, lp["w_in"], preferred_element_type=jnp.float32)
        z, xbc, dt = zxd[..., :inner], zxd[..., inner: inner + cfg.conv_width], zxd[..., inner + cfg.conv_width:]
        dt = jnp.where(valid[..., None], jax.nn.softplus(dt + lp["dt_bias"]), 0.0)
    with jax.named_scope("ssm_conv"):
        xx = jnp.concatenate([window, xbc], axis=1)                # [B, taps - 1 + S, C]
        conv = lp["conv"].astype(jnp.float32)
        xbc = jax.nn.silu(sum(xx[:, j: j + S] * conv[j] for j in range(taps))
                          + lp["conv_bias"].astype(jnp.float32))
    with jax.named_scope("state_writeback"):
        window = window_at(xx, lens, taps - 1)
    with jax.named_scope("ssm_scan"):
        x = jnp.moveaxis(xbc[..., :inner].reshape(B, S, H, P), 1, 2)       # heads lead, then positions
        b, c = xbc[..., inner: inner + N], xbc[..., inner + N:]
        dt = jnp.moveaxis(dt, 1, 2)
        chunk = _chunk(S)
        pad = -S % chunk
        if pad:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad)))
            b, c = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (b, c))
        y, state = ssd_chunks(x, dt, -jnp.exp(lp["A_log"]), b, c, lens, state, period, chunk)
        y = y[:, :, :S] + lp["D"][None, :, None, None] * x[:, :, :S]
        y = jnp.moveaxis(y, 1, 2).reshape(B, S, inner)
    with jax.named_scope("ssm_out"):
        y = _rms(y * jax.nn.silu(z), lp["norm"], cfg.rms_eps)
        out = jnp.einsum("bsn,nd->bsd", y.astype(cfg.dtype), lp["w_out"], preferred_element_type=jnp.float32)
    return out, state, window


# ---------------------------------------------------------------- attention
def attention(lp: Params, cfg: Mamba2HybridConfig, u: jax.Array, attend):
    """The attention's output [B, S, D] f32 for normed tokens u and the (k,
    v) [B, S, Hkv, hd] of these tokens. `attend(q, qg, k, v)`: the flash
    parts of what the queries may see, merged, [B, Hkv, G, S, hd]; q [B, S,
    H, hd] in the kernels' layout, qg [B, S, Hkv, G, hd] float32 scaled by
    `attention_multiplier`."""
    B, S, _ = u.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dn->bsn", u, lp["wq"]).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,dn->bsn", u, lp["wk"]).reshape(B, S, Hkv, hd)
    v = jnp.einsum("bsd,dn->bsn", u, lp["wv"]).reshape(B, S, Hkv, hd)
    # the kernels scale q by hd^-1/2: hand them q times attention_multiplier sqrt(hd)
    q = (q.astype(jnp.float32) * (cfg.attention_multiplier * hd**0.5)).astype(cfg.dtype)
    qg = (q.astype(jnp.float32) * hd**-0.5).reshape(B, S, Hkv, H // Hkv, hd)
    o = jnp.moveaxis(attend(q, qg, k, v), 3, 1).reshape(B, S, H * hd)
    out = jnp.einsum("bsn,nd->bsd", o.astype(cfg.dtype), lp["wo"], preferred_element_type=jnp.float32)
    return out, (k, v)


# ---------------------------------------------------------------------- mlp
@jax.named_scope("mlp")
def _mlp(layers: Params, idx, cfg: Mamba2HybridConfig, x: jax.Array) -> jax.Array:
    """The SwiGLU's output in float32 for layer `idx` of the whole stack."""
    u = _rms(x, layers["mlp_norm"][idx], cfg.rms_eps).astype(cfg.dtype)
    gu = jnp.einsum("bsd,df->bsf", u, layers["w_in"][idx], preferred_element_type=jnp.float32)
    h = jax.nn.silu(gu[..., : cfg.d_ff]) * gu[..., cfg.d_ff:]
    return jnp.einsum("bsf,fd->bsd", h.astype(cfg.dtype), layers["w_out"][idx], preferred_element_type=jnp.float32)


# ----------------------------------------------------------- the layer scan
def _run_periods(params, cfg: Mamba2HybridConfig, x, valid, lens, state, cache_xs, attend):
    """Every layer over the float32 stream x [B, S, D]: a scan over the
    periods, the period's Mamba-2 layers and its attention written out in
    its body, the attention at `attn_position`. Of `state` (`state_shapes`:
    a member a Mamba-2 position of the period, [periods, B, ..]) the
    matrices ride the scan's CARRY whole: each Mamba-2 layer advances its own
    entry of its member where it lies (ops/ssd_scan.py), once; the windows
    are scanned over. `cache_xs`: cache arrays [La, ..] handed to the
    period's attention; `attend(cache_l, p, q, qg, k, v)` says what its
    queries see. Returns (x, (k, v) of these tokens [La, B, S, Hkv, hd],
    state)."""
    per, at_attn, m = cfg.period, cfg.attn_position, cfg.residual_multiplier
    layers, ssm, attn = params["layers"], params["ssm"], params["attn"]

    def body(carry, inp):
        x, matrices = carry
        cache_l, windows, p = inp
        matrices, new_w = list(matrices), []
        for j in range(per):
            idx = p * per + j
            with jax.named_scope("attn"):
                u = _rms(x, layers["attn_norm"][idx], cfg.rms_eps).astype(cfg.dtype)
                if j == at_attn:
                    with jax.named_scope("full_attn"):
                        y, kv = attention({k_: a[p] for k_, a in attn.items()}, cfg, u,
                                          lambda *qkv: attend(cache_l, p, *qkv))
                else:
                    s = j - (j > at_attn)        # which of the period's Mamba-2 layers
                    with jax.named_scope("ssm"):
                        y, matrices[s], w = ssm_mixer({k_: a[p * (per - 1) + s] for k_, a in ssm.items()}, cfg,
                                                      u, valid, lens, matrices[s], p, windows[s])
                        new_w.append(w)
                x = x + m * y
            x = x + m * _mlp(layers, idx, cfg, x)
        return (x, tuple(matrices)), (kv, tuple(new_w))

    state = tuple(state)
    (x, matrices), (kv, windows) = jax.lax.scan(
        body, (x, state[: per - 1]), (cache_xs, state[per - 1:], jnp.arange(cfg.n_periods)))
    return x, kv, (*matrices, *windows)


def _counters(lens, computed: int, block_prefix) -> jax.Array:
    """COUNTERS: STATE_COUNTERS of `lens` valid tokens in `computed`, then
    the call's BLOCK_PREFIX_COUNTERS."""
    return jnp.concatenate([jnp.stack([jnp.sum(lens), jnp.int32(computed)]).astype(jnp.int32), block_prefix])


# ------------------------------------------------------------------ prefill
def forward_prefill_kv(params: Params, cfg: Mamba2HybridConfig, tokens, seq_lens, state=None):
    """Full-prompt prefill for the cache and the state, under scope
    `prefix_prefill`: (None, k [La, B, S, Hkv, hd], v, the state
    (`state_shapes`, each member [periods, B, ..]) AFTER `seq_lens` tokens of
    the padded bucket). `state`: what the sequence carried before `tokens`
    (None: nothing, a sequence's start)."""
    B, S = tokens.shape
    valid = jnp.arange(S)[None, :] < seq_lens[:, None]

    def attend(_cache, _p, q, qg, k, v):
        return merge_attention_parts([causal_chunk_attend_parts(q, qg, k, v, seq_lens)])

    with jax.named_scope("prefix_prefill"):
        _, (k_all, v_all), state = _run_periods(
            params, cfg, _stream(params, cfg, tokens), valid, seq_lens,
            zero_state(cfg, B) if state is None else state, (), attend)
    return None, k_all, v_all, state


def forward_prefill_suffix_dense(
    params: Params,
    cfg: Mamba2HybridConfig,
    tokens: jax.Array,       # [B, Ss] int32, per-request suffix, left-aligned
    suffix_lens: jax.Array,  # [B] valid suffix tokens (0 = row unused)
    prefix_k: jax.Array,     # [La, Sp, Hkv, hd] shared prefix cache
    prefix_v: jax.Array,
    prefix_len: jax.Array,   # scalar int32
    prefix_impl: Any = None,  # static: ops/attention.prefix_attend_parts
    *,
    state,                   # the prefix's state: `state_shapes`, each member [periods, ..]
):
    """Batched suffix prefill against the shared prefix: every row is SEEDED
    from the prefix's state (a copy of its own, scope `state_seed`: the
    prefix's arrays are read, never written) and its first tokens see the
    prefix's last through the convolution. Returns (last_logits [B, V] f32,
    k_sfx [La, B, Ss, Hkv, hd], v_sfx, each row's state after its
    `suffix_lens` tokens (each member [periods, B, ..]), COUNTERS)."""
    B, S = tokens.shape
    valid = jnp.arange(S)[None, :] < suffix_lens[:, None]
    with jax.named_scope("state_seed"):
        rows = tuple(jnp.repeat(a[:, None], B, axis=1) for a in state)

    def attend(cache_l, _p, q, qg, k, v):
        pk, pv = cache_l
        return merge_attention_parts([
            prefix_attend_parts(q, qg, pk, pv, prefix_len, impl=prefix_impl),
            causal_chunk_attend_parts(q, qg, k, v, suffix_lens, impl=prefix_impl)])

    x, (k_sfx, v_sfx), rows = _run_periods(
        params, cfg, _stream(params, cfg, tokens), valid, suffix_lens, rows, (prefix_k, prefix_v), attend)
    return (_last_valid_logits(params, cfg, x, suffix_lens), k_sfx, v_sfx, rows,
            _counters(suffix_lens, B * _chunked(S), jnp.zeros((len(BLOCK_PREFIX_COUNTERS),), jnp.int32)))


# ------------------------------------------------------------- block decode
def forward_block_decode(
    params: Params,
    cfg: Mamba2HybridConfig,
    blk_tok: jax.Array,    # [R, F] int32, this iteration's token block
    blk_valid: jax.Array,  # [R, F] bool, left-aligned valid tokens
    blk_len: jax.Array,    # [R] int32
    positions: jax.Array,  # [R, F] absolute positions (no position encoding: unused)
    k_sfx: jax.Array,      # [La, R, Ss, Hkv, hd] suffix cache
    v_sfx: jax.Array,
    suffix_lens: jax.Array,  # [R]
    gen_k: jax.Array,      # [La, R, cap+F, Hkv, hd] generated-token cache
    gen_v: jax.Array,
    tail: jax.Array,       # [R] tokens already in gen_k / gen_v
    prefix_k: jax.Array,   # [La, Sp, Hkv, hd] shared prefix cache
    prefix_v: jax.Array,
    prefix_len: jax.Array,
    prefix_impl: Any = None,
    ragged: bool = False,
    *,
    state,                 # each row's state: `state_shapes`, each member [periods, R, ..]
):
    """One grammar-accelerated decode iteration (models/llama.py
    `forward_block_decode` says what that is): the block's positions are ONE
    chunk of the scan, so each Mamba-2 layer reads a row's state once and
    writes it once, advanced by the row's `blk_len` valid tokens; a row with
    none keeps its state. The attention's prefix part runs at the block
    positions the call carries (ops/attention.py block_prefix_attend_parts).
    Returns (logits [R, V] f32 at each row's last
    valid position, gen_k, gen_v, state, COUNTERS)."""
    if ragged:
        raise ValueError(f"{cfg.name}: llm.decode_matmul 'ragged' is not served by models/mamba2_hybrid.py")
    R, F = blk_tok.shape
    j = jnp.arange(F)
    sfx_mask = (jnp.arange(k_sfx.shape[2])[None, :] < suffix_lens[:, None])[:, None, None, None, :]
    gen_mask = (jnp.arange(gen_k.shape[2])[None, :] < tail[:, None])[:, None, None, None, :]
    blk_mask = ((j[:, None] >= j[None, :])[None] & blk_valid[:, None, :])[:, None, None]
    eq = "bqkgh,bskh->bkgqs"
    rung = block_prefix_rung(blk_len, F)

    def attend(cache_l, p, q, qg, k, v):
        pk, pv, sk, sv = cache_l
        # gen_mask exposes entries < tail only: never this block's own
        return merge_attention_parts([
            block_prefix_attend_parts(rung, q, qg, pk, pv, prefix_len, impl=prefix_impl),
            attend_part(qg, sk, sv, sfx_mask, eq), attend_part(qg, gen_k[p], gen_v[p], gen_mask, eq),
            attend_part(qg, k, v, blk_mask, eq)])

    x, (k_blk, v_blk), state = _run_periods(
        params, cfg, _stream(params, cfg, blk_tok), blk_valid, blk_len, state,
        (prefix_k, prefix_v, k_sfx, v_sfx), attend)
    with jax.named_scope("kv_writeback"):
        gen_k = write_block(gen_k, tail, k_blk)
        gen_v = write_block(gen_v, tail, v_blk)
    return (_last_valid_logits(params, cfg, x, blk_len), gen_k, gen_v, state,
            _counters(blk_len, R * F, block_prefix_counts(rung, R, F)))
