"""Gated-delta-rule linear attention and gated softmax attention, three to
one, over sparse experts with a gated shared expert, in functional JAX: the
`qwen3_next` layer (Qwen3-Next-80B-A3B), on the decision path. One chip's
share of an expert-parallel deployment: the mixers and the shared expert
whole, a range of the routed experts.

THE LAYER EQUATIONS. `x` is the residual stream, KEPT IN FLOAT32 as in
models/mla_moe.py, whose `route`, `routed_experts` and grouped kernels run
here BY IMPORT. Every norm is `x rsqrt(mean x^2 + eps) (1 + w)` except the
mixer's gated norm. D = d_model.

- Layer i: `h = x + mixer_i(norm(x))`, `out = h + moe(norm(h))`; `mixer_i`
  is the gated attention where (i + 1) % `full_attention_interval` == 0,
  else the delta-rule mixer. Every layer's feed-forward is the sparse block.
- Delta-rule mixer (Hk key heads x dk, Hv value heads x dv, each key head
  serving Hv / Hk value heads): `[q, k, v, z] = u W_qkvz` (columns q | k |
  v | z), `[b, a] = u W_ba`; `[q, k, v] <- silu(conv([q, k, v]))`, causal,
  depthwise over the 2 Hk dk + Hv dv channels, `conv_kernel` taps, no bias
  (tap `conv_kernel - 1` meets the token itself); `beta = sigmoid(b)`, `g =
  -exp(A_log) softplus(a + dt_bias)` in float32, one each a value head; q
  and k L2-normalised over the head (eps 1e-6), q times dk^-1/2. For each
  value head with S in R^{dk x dv}, token by token: `S <- e^{g_t} S`;
  `delta = beta_t (v_t - S^T k_t)`; `S <- S + k_t delta^T`; `o_t = S^T
  q_t`. Then `o <- w (o rsqrt(mean o^2 + eps)) silu(z)` a head (weight
  [dv]), `W_o`.
  THE PROGRAM RUNS THE CHUNKED FORM of that recurrence, as ONE KERNEL
  (ops/gdn_scan.py writes the form out): over a chunk of C positions one
  unit-lower-triangular solve in float32 and matrix products, a (row, block
  of heads)'s state read from HBM once, held in VMEM across the call's
  chunks and written once, WHERE IT LIES. A position that is not valid has
  g = 0 and beta = 0, which leaves the state as it was BY CONSTRUCTION, and
  the convolution's window is cut at the row's valid length.
- Gated attention (H query heads, Hkv key/value heads, width hd): `W_q` D ->
  H x 2 hd, split a head into query and gate; `W_k`, `W_v` D -> Hkv x hd;
  (1 + w) RMS norm of q and of k over the head; rotary (theta
  `cfg.rope_theta`, half-split) on the first `rotary_dim` of the hd dims;
  causal softmax of q k / sqrt(hd); output times sigmoid(gate); `W_o`.
- Sparse block on h = norm(x): router logits `h_f32 W_g` in float32,
  softmax over all `n_routed_experts`, top `n_experts_per_tok`, weights
  renormalised to sum 1; experts SwiGLU of width d_ff_expert; plus
  `sigmoid(h w_sg) SwiGLU_shared(h)`, computed alike on every share. Of the
  routed experts this share computes `expert_first .. + experts_held`.
- Head: final norm, untied output head.
- Left out: the multi-token-prediction module.

WHAT A SEQUENCE CARRIES. The attention layers have a per-token cache, (k, v)
[n_attn_layers, ..., tokens, Hkv, hd], as the dense family's. The delta-rule
layers have none: they have a STATE a sequence: S [Hv, dk, dv] float32 and
the last `conv_kernel - 1` inputs of the convolution [conv_kernel - 1,
channels], AFTER a given number of tokens. `state_shapes` lists them ONE
MEMBER A POSITION IN THE PERIOD (S of the period's first, second, third
delta-rule layer, then their windows), each with the periods as its leading
axis. THE MATRICES RIDE THE LAYER SCAN'S CARRY WHOLE: a delta-rule layer's
kernel is handed its member and the period's index, advances that entry in
place (the kernel's output is aliased to its input; the other entries are
not touched) and hands the member on, so a state is read once and written
once a layer a call and never copied: not by the scan (a layer that
updated its entry of ONE [layers, ..] array with a `dynamic_update_slice`
cost two copies of the whole 151 MB state a period a call, and members
handed back as the scan's `ys` one copy of them a call into the block
loop's carry: PERF.md §6 PRs 37 and 38), not by the block loop. The windows
are small and are scanned over: a layer reads its own slice and hands the
new one back. The three forwards take and return the state beside the
cache (`state=`): prefix prefill returns the state after `seq_lens` tokens;
the suffix call seeds every row from the prefix's (a copy of the rows' own:
the prefix's arrays are read, never written) and returns each row's after
its suffix; block decode advances each row's by `blk_len`.

Params: `params["layers"]` holds what every layer has (norms, router,
experts, shared expert; leading axis = layer), `params["gdn"]` the delta-rule
mixers (leading axis = their count, in layer order) and `params["attn"]` the
attentions. ONE `lax.scan` over the periods, the period's layers written out
in its body; every weight is read out of its whole stack at a traced index
(models/mla_scmoe.py `_layer` says why).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from k8s_llm_scheduler_tpu.models._state import STATE_COUNTERS, window_at
from k8s_llm_scheduler_tpu.models.configs import GdnMoeConfig
from k8s_llm_scheduler_tpu.models.llama import apply_rope
from k8s_llm_scheduler_tpu.models.mla_moe import BOUND_COUNTERS, EXPERT_LEAVES
from k8s_llm_scheduler_tpu.models.mla_moe import COUNTERS as EXPERT_COUNTERS
from k8s_llm_scheduler_tpu.models.mla_moe import (
    _softmax_part,
    _stream,
    _swiglu,
    routed_experts,
)
from k8s_llm_scheduler_tpu.ops.attention import merge_attention_parts, write_block
from k8s_llm_scheduler_tpu.ops.gdn_scan import gdn_chunk_scan

Params = dict[str, Any]

COUNTERS = EXPERT_COUNTERS + BOUND_COUNTERS + STATE_COUNTERS

# Positions a chunk of the delta rule holds in prefill: the solve's cost a
# position grows with the chunk, the products with the state are once a
# chunk. Block decode's chunk is the block. On the chip (PERF.md §6 PR 38) a
# layer's scan alone read 649 us at 32 and 728 at 64 for a suffix call,
# 1,335 and 1,574 for a prefix prefill (16: no better than 32).
CHUNK = 32
L2_EPS = 1e-6

# ------------------------------------------------------- what a sequence carries
def cache_token_shapes(cfg: GdnMoeConfig) -> tuple[tuple[int, ...], ...]:
    """Per-token trailing shapes of the cache tuple: (k, v) of the layers
    that attend."""
    return ((cfg.n_kv_heads, cfg.head_dim),) * 2


def cache_layers(cfg: GdnMoeConfig) -> int:
    """Leading axis of the cache tuple: the attention layers."""
    return cfg.n_attn_layers


def state_shapes(cfg: GdnMoeConfig) -> tuple[tuple[tuple[int, ...], Any], ...]:
    """(trailing shape, dtype) of each member of the per-sequence state: the
    delta rule's S a value head for each delta-rule position of the period,
    then the convolution's window for each."""
    n = cfg.full_attention_interval - 1
    s = ((cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim), jnp.float32)
    w = ((cfg.conv_kernel - 1, cfg.conv_width), jnp.float32)
    return (s,) * n + (w,) * n


def state_layers(cfg: GdnMoeConfig) -> int:
    """Leading axis of every state member: the periods."""
    return cfg.n_periods


def zero_state(cfg: GdnMoeConfig, *lead: int) -> tuple[jax.Array, ...]:
    """The state before any token: [n_periods, *lead, *shape] zeros."""
    return tuple(jnp.zeros((cfg.n_periods, *lead, *shape), dtype)
                 for shape, dtype in state_shapes(cfg))


# --------------------------------------------------------------------- init
def init_params(rng: jax.Array, cfg: GdnMoeConfig, quantize: str | None = None) -> Params:
    """Random-init as models/mla_moe.py's: normal x 1/sqrt(fan_in), the
    embedding at unit scale, (1 + w) norms at w = 0, the mixer's gated norm
    at one. The convolution's taps normal x 1/sqrt(conv_kernel). THE DECAY
    IS DRAWN SMALL, as the delta-rule literature's own init draws it: a step
    dt log-uniform on (1e-3, 1e-1) enters `dt_bias` through the inverse
    softplus and exp(A_log) is uniform on (0, 16), so a value head forgets
    at A dt ~ 1e-3 .. 1 a token and a good share of them carry a prefix
    through a suffix (with dt_bias one, g ~ -10 a token: a state that
    forgets at once, and a comparison that could not see a lost state;
    benchmark/configs/qwen3-next-80b-a3b.json `assumed` holds the reading).
    Expert weights are drawn a layer at a time (`lax.map`)."""
    if quantize is not None:
        raise ValueError(
            f"{cfg.name}: llm.quantization {quantize!r} is not served by "
            f"models/gdn_moe.py (int8 expert weights: models/quant.py)"
        )
    D, L, Lg, La = cfg.d_model, cfg.n_layers, cfg.n_gdn_layers, cfg.n_attn_layers
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hv, dv = cfg.gdn_value_heads, cfg.gdn_value_dim
    kw, vw = cfg.gdn_key_width, cfg.gdn_value_width
    E, Fe, Fs = cfg.experts_held, cfg.d_ff_expert, cfg.d_ff_shared
    k_embed, k_head, k_layers, k_gdn, k_attn = jax.random.split(rng, 5)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5).astype(cfg.dtype)

    def stacked(key, shape, fan_in, n):
        return jax.lax.map(lambda k: dense(k, shape, fan_in), jax.random.split(key, n))

    def zeros(shape):
        return jnp.zeros(shape, dtype=cfg.dtype)

    kl, kg, ka = jax.random.split(k_layers, 8), jax.random.split(k_gdn, 6), jax.random.split(k_attn, 4)
    dt = jnp.exp(jax.random.uniform(kg[4], (Lg, Hv), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    a_decay = jnp.maximum(jax.random.uniform(kg[3], (Lg, Hv), jnp.float32, 0.0, 16.0), 1e-3)
    return {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, D), dtype=jnp.float32)).astype(cfg.dtype),
        "final_norm": zeros((D,)),
        "lm_head": dense(k_head, (D, cfg.vocab_size), D),
        "layers": {
            "attn_norm": zeros((L, D)),
            "mlp_norm": zeros((L, D)),
            "router": dense(kl[0], (L, D, cfg.n_routed_experts), D),
            "we_gate": stacked(kl[1], (E, D, Fe), D, L),
            "we_up": stacked(kl[2], (E, D, Fe), D, L),
            "we_down": stacked(kl[3], (E, Fe, D), Fe, L),
            "ws_gate": dense(kl[4], (L, D, Fs), D),
            "ws_up": dense(kl[5], (L, D, Fs), D),
            "ws_down": dense(kl[6], (L, Fs, D), Fs),
            "ws_sel": dense(kl[7], (L, D), D),
        },
        "gdn": {
            "w_qkvz": dense(kg[0], (Lg, D, 2 * kw + 2 * vw), D),
            "w_ba": dense(kg[1], (Lg, D, 2 * Hv), D),
            "conv": dense(kg[2], (Lg, cfg.conv_kernel, cfg.conv_width), cfg.conv_kernel),
            "A_log": jnp.log(a_decay),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) = dt
            "o_norm": jnp.ones((Lg, dv), dtype=cfg.dtype),
            "wo": dense(kg[5], (Lg, vw, D), vw),
        },
        "attn": {
            "wq": dense(ka[0], (La, D, H * 2 * hd), D),
            "wk": dense(ka[1], (La, D, Hkv * hd), D),
            "wv": dense(ka[2], (La, D, Hkv * hd), D),
            "q_norm": zeros((La, hd)),
            "k_norm": zeros((La, hd)),
            "wo": dense(ka[3], (La, H * hd, D), H * hd),
        },
    }


# -------------------------------------------------------------------- norms
def _norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """x rsqrt(mean x^2 + eps) (1 + w), in float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * (1.0 + w.astype(jnp.float32))


def _l2(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


@jax.named_scope("lm_head")
def _last_valid_logits(params: Params, cfg: GdnMoeConfig, x: jax.Array, lens: jax.Array) -> jax.Array:
    """Logits [B, V] f32 at each row's last valid token of the stream x."""
    last = jnp.maximum(lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    h = _norm(x_last, params["final_norm"], cfg.rms_eps).astype(cfg.dtype)
    return jnp.einsum("bd,dv->bv", h, params["lm_head"], preferred_element_type=jnp.float32)


# ----------------------------------------------------------- the delta rule
def gated_delta_chunks(q, k, v, g, beta, lens, state, period, chunk: int):
    """The gated delta rule over T = n x `chunk` positions in its chunked
    form, which ops/gdn_scan.py writes out and runs as one kernel. q, k [B,
    Hk, T, dk] (normalised, q scaled; value head h reads key head h // (H /
    Hk)), v [B, H, T, dv], g (log decay, <= 0) and beta [B, H, T], all
    float32; row r's first `lens[r]` positions are valid (the others have g
    = 0 and beta = 0: no delta, no decay); `state` [P, B, H, dk, dv] is a
    whole member of what the sequences carry, of which these rows' is entry
    `period`. Returns (o [B, H, T, dv], `state` with that entry after the T
    positions, UPDATED WHERE IT LIES)."""
    T = k.shape[2]

    def cut(a):
        return a.reshape(*a.shape[:2], T // chunk, chunk, *a.shape[3:])

    q, k, v, g, beta = map(cut, (q, k, v, g, beta))
    o, state = gdn_chunk_scan(q, k, v, jnp.cumsum(g, axis=-1), beta, lens, state, period)
    return o.reshape(*v.shape[:2], T, -1), state


def _chunk(S: int) -> int:
    """The chunk of a call S wide: the call itself up to CHUNK positions."""
    return min(S, CHUNK)


def gdn_mixer(lp: Params, cfg: GdnMoeConfig, u: jax.Array, valid: jax.Array, lens: jax.Array,
              state: jax.Array, period, window: jax.Array):
    """The delta-rule mixer's output [B, S, D] f32 for normed tokens u [B,
    S, D] (the weights' dtype), of which row r's first `lens[r]` are `valid`
    [B, S]; entry `period` of `state` [periods, B, Hv, dk, dv] and `window`
    [B, conv_kernel - 1, channels] are each row's state before the call.
    Returns (output, `state` with that entry after each row's valid tokens,
    the window at them). S is padded up to whole chunks here; padding is
    not valid."""
    B, S, _ = u.shape
    Hk, Hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    kw, vw, taps = cfg.gdn_key_width, cfg.gdn_value_width, cfg.conv_kernel
    with jax.named_scope("gdn_proj"):
        qkvz = jnp.einsum("bsd,dn->bsn", u, lp["w_qkvz"], preferred_element_type=jnp.float32)
        ba = jnp.einsum("bsd,dn->bsn", u, lp["w_ba"], preferred_element_type=jnp.float32)
        qkv, z = qkvz[..., : 2 * kw + vw], qkvz[..., 2 * kw + vw:]
        beta = jnp.where(valid[..., None], jax.nn.sigmoid(ba[..., :Hv]), 0.0)
        g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[..., Hv:] + lp["dt_bias"])
        g = jnp.where(valid[..., None], g, 0.0)
    with jax.named_scope("gdn_conv"):
        xx = jnp.concatenate([window, qkv], axis=1)               # [B, taps - 1 + S, C]
        conv = lp["conv"].astype(jnp.float32)
        mixed = sum(xx[:, j: j + S] * conv[j] for j in range(taps))
        mixed = jax.nn.silu(mixed)
    with jax.named_scope("state_writeback"):
        window = window_at(xx, lens, taps - 1)
    with jax.named_scope("gdn_scan"):
        q = _l2(mixed[..., :kw].reshape(B, S, Hk, dk)) * dk**-0.5
        k = _l2(mixed[..., kw: 2 * kw].reshape(B, S, Hk, dk))
        v = mixed[..., 2 * kw:].reshape(B, S, Hv, dv)
        # heads lead, then positions
        q, k, v, g, beta = (jnp.moveaxis(a, 1, 2) for a in (q, k, v, g, beta))
        chunk = _chunk(S)
        pad = -S % chunk
        if pad:
            q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (q, k, v))
            g, beta = (jnp.pad(a, ((0, 0), (0, 0), (0, pad))) for a in (g, beta))
        o, state = gated_delta_chunks(q, k, v, g, beta, lens, state, period, chunk)
        o = jnp.moveaxis(o[:, :, :S], 1, 2)                       # [B, S, Hv, dv]
    with jax.named_scope("gdn_out"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps)
        o = o * lp["o_norm"].astype(jnp.float32) * jax.nn.silu(z.reshape(B, S, Hv, dv))
        out = jnp.einsum("bsn,nd->bsd", o.reshape(B, S, vw).astype(cfg.dtype), lp["wo"],
                         preferred_element_type=jnp.float32)
    return out, state, window


# ------------------------------------------------------ the gated attention
def _rope_inv_freq(cfg: GdnMoeConfig) -> jax.Array:
    dr = cfg.rotary_dim
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))


def _partial_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array) -> jax.Array:
    """x [B, S, n, hd] with its first 2 x len(inv_freq) dims rotated."""
    dr = 2 * inv_freq.shape[0]
    return jnp.concatenate([apply_rope(x[..., :dr], positions, inv_freq), x[..., dr:]], axis=-1)


def full_attention(lp: Params, cfg: GdnMoeConfig, u: jax.Array, positions: jax.Array,
                   inv_freq: jax.Array, segments):
    """The gated attention's output [B, S, D] f32 for normed tokens u and
    the (k, v) [B, S, Hkv, hd] of these tokens. `segments(k, v)`: what the
    queries may see is the forward's, [(keys [B?, T, Hkv, hd], values, mask
    broadcastable to [B, Hkv, G, S, T])]; a segment without a batch axis
    (the shared prefix) is read once for all rows."""
    B, S, _ = u.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qg = jnp.einsum("bsd,dn->bsn", u, lp["wq"]).reshape(B, S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = jnp.einsum("bsd,dn->bsn", u, lp["wk"]).reshape(B, S, Hkv, hd)
    v = jnp.einsum("bsd,dn->bsn", u, lp["wv"]).reshape(B, S, Hkv, hd)
    q = _partial_rope(_norm(q, lp["q_norm"], cfg.rms_eps).astype(cfg.dtype), positions, inv_freq)
    k = _partial_rope(_norm(k, lp["k_norm"], cfg.rms_eps).astype(cfg.dtype), positions, inv_freq)
    q = q.reshape(B, S, Hkv, H // Hkv, hd)
    parts = []
    for keys, values, mask in segments(k, v):
        t = "btkd" if keys.ndim == 4 else "tkd"
        logits = jnp.einsum(f"bskgd,{t}->bkgst", q, keys, preferred_element_type=jnp.float32) * hd**-0.5
        parts.append(_softmax_part(logits, mask, values, f"bkgst,{t}->bkgsd"))
    o = merge_attention_parts(parts)                              # [B, Hkv, G, S, hd]
    o = jnp.moveaxis(o, 3, 1).reshape(B, S, H, hd) * jax.nn.sigmoid(gate.astype(jnp.float32))
    out = jnp.einsum("bsn,nd->bsd", o.reshape(B, S, H * hd).astype(cfg.dtype), lp["wo"],
                     preferred_element_type=jnp.float32)
    return out, (k, v)


# --------------------------------------------------------- the sparse block
@jax.named_scope("mlp")
def _sparse_block(layers: Params, idx, cfg: GdnMoeConfig, x: jax.Array, valid: jax.Array):
    """(the sparse block's output in float32, EXPERT + BOUND counters) of
    the float32 stream x for layer `idx` of the whole stack `layers`."""
    h = _norm(x, layers["mlp_norm"][idx], cfg.rms_eps)
    flat = h.reshape(-1, h.shape[-1])
    routed = {"router": layers["router"][idx], **{k: layers[k] for k in EXPERT_LEAVES}, "layer": idx}
    y, counters = routed_experts(routed, cfg, flat, valid.reshape(-1))
    if counters.shape[0] == len(EXPERT_COUNTERS):
        # a layer that holds every expert has no short path: every call is within its bound
        counters = jnp.concatenate([counters, jnp.ones((1,), jnp.int32)])
    with jax.named_scope("moe_shared"):
        hb = flat.astype(cfg.dtype)
        sel = jnp.einsum("td,d->t", hb, layers["ws_sel"][idx], preferred_element_type=jnp.float32)
        shared = _swiglu(hb, layers["ws_gate"][idx], layers["ws_up"][idx], layers["ws_down"][idx])
        y = y + jax.nn.sigmoid(sel)[:, None] * shared
    return y.reshape(x.shape), counters


# ----------------------------------------------------------- the layer scan
def _run_periods(params, cfg: GdnMoeConfig, x, valid, lens, positions, state, cache_xs, segments):
    """Every layer over the float32 stream x [B, S, D]: a scan over the
    periods, `full_attention_interval - 1` delta-rule layers and one
    attention written out in its body. Of `state` (`state_shapes`: a member
    a delta-rule position of the period, [periods, B, ..]) the matrices ride
    the scan's CARRY whole: each delta-rule layer advances its own entry of
    its member where it lies (ops/gdn_scan.py), once; the windows are
    scanned over, each layer handed its own and handing the new one back.
    `cache_xs`: cache arrays [La, ..] handed to the period's attention;
    `segments(cache_l, p, k, v)` says what its queries may see. Returns (x,
    (k, v) of these tokens [La, B, S, Hkv, hd], state, EXPERT + BOUND
    counters)."""
    per = cfg.full_attention_interval
    layers, gdn, attn = params["layers"], params["gdn"], params["attn"]
    inv_freq = _rope_inv_freq(cfg)

    def body(carry, inp):
        x, counters, matrices = carry
        cache_l, windows, p = inp
        matrices, new_w = list(matrices), []
        for j in range(per):
            idx = p * per + j
            with jax.named_scope("attn"):
                u = _norm(x, layers["attn_norm"][idx], cfg.rms_eps).astype(cfg.dtype)
                if j < per - 1:
                    at = p * (per - 1) + j
                    with jax.named_scope("gdn"):
                        y, matrices[j], w = gdn_mixer({k_: a[at] for k_, a in gdn.items()}, cfg, u, valid, lens,
                                                      matrices[j], p, windows[j])
                        new_w.append(w)
                else:
                    with jax.named_scope("full_attn"):
                        y, kv = full_attention(
                            {k_: a[p] for k_, a in attn.items()}, cfg, u, positions, inv_freq,
                            lambda k_new, v_new: segments(cache_l, p, k_new, v_new))
                x = x + y
            y, c = _sparse_block(layers, idx, cfg, x, valid)
            x = x + y
            counters = counters + c
        return (x, counters, tuple(matrices)), (kv, tuple(new_w))

    zero = jnp.zeros((len(EXPERT_COUNTERS) + len(BOUND_COUNTERS),), jnp.int32)
    state = tuple(state)
    (x, counters, matrices), (kv, windows) = jax.lax.scan(
        body, (x, zero, state[: per - 1]), (cache_xs, state[per - 1:], jnp.arange(cfg.n_periods)))
    return x, kv, (*matrices, *windows), counters


def _with_state_counters(counters, lens, computed: int):
    return jnp.concatenate([counters, jnp.stack([jnp.sum(lens), jnp.int32(computed)]).astype(jnp.int32)])


def _chunked(S: int) -> int:
    """Positions the delta rule's scan runs over for a call S wide."""
    return -(-S // _chunk(S)) * _chunk(S)


# ------------------------------------------------------------------ prefill
def forward_prefill_kv(params: Params, cfg: GdnMoeConfig, tokens, seq_lens, state=None):
    """Full-prompt prefill for the cache and the state, under scope
    `prefix_prefill`: (None, k [La, B, S, Hkv, hd], v, the state
    (`state_shapes`, each member [periods, B, ..]) AFTER `seq_lens` tokens of
    the padded bucket). `state`: what the sequence carried before `tokens`
    (None: nothing, a sequence's start)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    valid = positions < seq_lens[:, None]
    mask = (positions[0][:, None] >= positions[0][None, :])[None, None, None] & valid[:, None, None, None, :]

    def segments(_cache, _p, k, v):
        return [(k, v, mask)]

    with jax.named_scope("prefix_prefill"):
        _, (k_all, v_all), state, _ = _run_periods(
            params, cfg, _stream(params, tokens), valid, seq_lens, positions,
            zero_state(cfg, B) if state is None else state, (), segments)
    return None, k_all, v_all, state


def forward_prefill_suffix_dense(
    params: Params,
    cfg: GdnMoeConfig,
    tokens: jax.Array,       # [B, Ss] int32, per-request suffix, left-aligned
    suffix_lens: jax.Array,  # [B] valid suffix tokens (0 = row unused)
    prefix_k: jax.Array,     # [La, Sp, Hkv, hd] shared prefix cache
    prefix_v: jax.Array,
    prefix_len: jax.Array,   # scalar int32
    prefix_impl: Any = None,  # the dense family's kernel choice; no kernel here
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
    j = jnp.arange(S)
    positions = prefix_len + jnp.broadcast_to(j, (B, S))
    valid = j[None, :] < suffix_lens[:, None]
    pre_mask = (jnp.arange(prefix_k.shape[1]) < prefix_len)[None, None, None, None, :]
    own_mask = (j[:, None] >= j[None, :])[None, None, None] & valid[:, None, None, None, :]
    with jax.named_scope("state_seed"):
        rows = tuple(jnp.repeat(a[:, None], B, axis=1) for a in state)

    def segments(cache_l, _p, k, v):
        pk, pv = cache_l
        return [(pk, pv, pre_mask), (k, v, own_mask)]

    x, (k_sfx, v_sfx), rows, counters = _run_periods(
        params, cfg, _stream(params, tokens), valid, suffix_lens, positions, rows,
        (prefix_k, prefix_v), segments)
    return (_last_valid_logits(params, cfg, x, suffix_lens), k_sfx, v_sfx, rows,
            _with_state_counters(counters, suffix_lens, B * _chunked(S)))


# ------------------------------------------------------------- block decode
def forward_block_decode(
    params: Params,
    cfg: GdnMoeConfig,
    blk_tok: jax.Array,    # [R, F] int32, this iteration's token block
    blk_valid: jax.Array,  # [R, F] bool, left-aligned valid tokens
    blk_len: jax.Array,    # [R] int32
    positions: jax.Array,  # [R, F] absolute positions
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
    chunk of the delta rule, so each delta-rule layer reads a row's state
    once and writes it once, advanced by the row's `blk_len` valid tokens; a
    row with none keeps its state. Returns (logits [R, V] f32 at each row's
    last valid position, gen_k, gen_v, state, COUNTERS)."""
    if ragged:
        raise ValueError(f"{cfg.name}: llm.decode_matmul 'ragged' is not served by models/gdn_moe.py")
    R, F = blk_tok.shape
    j = jnp.arange(F)
    pre_mask = (jnp.arange(prefix_k.shape[1]) < prefix_len)[None, None, None, None, :]
    sfx_mask = (jnp.arange(k_sfx.shape[2])[None, :] < suffix_lens[:, None])[:, None, None, None, :]
    gen_mask = (jnp.arange(gen_k.shape[2])[None, :] < tail[:, None])[:, None, None, None, :]
    blk_mask = ((j[:, None] >= j[None, :])[None] & blk_valid[:, None, :])[:, None, None]

    def segments(cache_l, p, k, v):
        pk, pv, sk, sv = cache_l
        # gen_mask exposes entries < tail only: never this block's own
        return [(pk, pv, pre_mask), (sk, sv, sfx_mask), (gen_k[p], gen_v[p], gen_mask), (k, v, blk_mask)]

    x, (k_blk, v_blk), state, counters = _run_periods(
        params, cfg, _stream(params, blk_tok), blk_valid, blk_len, positions, state,
        (prefix_k, prefix_v, k_sfx, v_sfx), segments)
    with jax.named_scope("kv_writeback"):
        gen_k = write_block(gen_k, tail, k_blk)
        gen_v = write_block(gen_v, tail, v_blk)
    return (_last_valid_logits(params, cfg, x, blk_len), gen_k, gen_v, state,
            _with_state_counters(counters, blk_len, R * F))
