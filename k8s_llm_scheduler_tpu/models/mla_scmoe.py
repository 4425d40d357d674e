"""Shortcut-connected double layers over latent attention, in functional
JAX: the LongCat-Flash layer, on the decision path. One chip's share of an
expert-parallel deployment: the attention and dense sublayers whole, a range
of the feed-forward experts, every identity expert.

THE LAYER EQUATIONS. `x` is the residual stream, KEPT IN FLOAT32 as in
models/mla_moe.py, whose attention sublayer, absorbed attention and routed
experts run here BY IMPORT: one copy serves both families, and what differs
is said by the config (`router_score`, `n_zero_experts`, `q_lora_scale`,
`kv_lora_scale`), not by a flag on a call.

- Latent attention `A_j` (j = 0, 1, weights of its own each): as
  models/mla_moe.py writes it, with two fixed factors: the normed query
  latent c_q times sqrt(D / dq) (`mla_scale_q_lora`: both q_nope and q_rope
  carry it) and the normed key/value latent c_kv times sqrt(D / dc)
  (`mla_scale_kv_lora`; the rotary key k_r is NOT scaled). The cache is
  (c_kv, k_r) a token an attention SUBLAYER, the latent with its factor:
  the cache tuple's leading axis is 2 x n_layers (`cache_layers`), sublayer
  j of layer l at 2 l + j.
- Dense feed-forward `F_j`: SwiGLU of width d_ff on RMSNorm(x), the
  post-attention norm of its sublayer.
- Routed feed-forward `M`, on h = RMSNorm(x) with the FIRST sublayer's
  post-attention norm (the same normed stream `F_0` reads): logits `h_f32
  W_g` in float32 over n_routed_experts + n_zero_experts outputs; s =
  softmax(logits); the `n_experts_per_tok` outputs with the largest s + b
  (b: `e_score_correction_bias`, selection only); weights
  `routed_scaling_factor` x s_e, NOT renormalised; output `sum_{e <
  n_routed} w_e SwiGLU_e(h)` (width d_ff_expert) `+ sum_{e >= n_routed} w_e
  h`: the identity experts return their input and cost nothing, so the
  work a token needs varies token by token. No shared expert, no capacity,
  no dropped token; padding is not routed. Of the feed-forward experts this
  share computes `expert_first .. + experts_held`; the rest are other
  chips'.
- One layer, in order: x1 = x + A_0(x); m = M(x1); x2 = x1 + F_0(x1);
  x3 = x2 + A_1(x2); x4 = x3 + F_1(x3) + m. THE SHORTCUT is that m is
  computed from x1 and joins the stream only at the end: the second
  attention and both dense feed-forwards do not wait for the experts.
- Head: final RMSNorm, untied output head.
- Left out: the multi-token-prediction module.

Params are ONE stack, `params["layers"]` (leading axis = layer): the
attention and dense leaves carry a second axis of 2 (the sublayer), the
router and the experts are one a layer. The three entry points keep the
contracts of models/mla_moe.py's, the cache tuple 2 x n_layers deep.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from k8s_llm_scheduler_tpu.models.configs import MlaScmoeConfig
from k8s_llm_scheduler_tpu.models.llama import _last_valid_logits, rms_norm
from k8s_llm_scheduler_tpu.models.mla_moe import COUNTERS as EXPERT_COUNTERS
from k8s_llm_scheduler_tpu.models.mla_moe import (
    BOUND_COUNTERS,
    EXPERT_LEAVES,
    ZERO_COUNTERS,
    _inv_freq,
    _stream,
    _swiglu,
    attend_absorbed,
    attention_sublayer,
    cache_token_shapes,  # noqa: F401  (the family's contract: models.family)
    routed_experts,
    state_layers,  # noqa: F401
    state_shapes,  # noqa: F401
)
from k8s_llm_scheduler_tpu.ops.attention import write_block

Params = dict[str, Any]

COUNTERS = EXPERT_COUNTERS + ZERO_COUNTERS + BOUND_COUNTERS

# std of the drawn selection bias. The published model LEARNS the bias to
# level the experts' load. Softmax scores over 768 outputs are small: at this
# init the 12th largest is ~6e-3 and lies ~2e-4 over the 13th (read on the
# CPU at the published router width, PERF.md §6 PR 34), so a draw of that
# size reorders neighbours at the selection's edge and gives no output a
# standing lead.
BIAS_SCALE = 2e-4

# leaves with a sublayer axis behind the layer axis
ATTENTION_LEAVES = ("attn_norm", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_ukv", "wo")
DENSE_LEAVES = ("mlp_norm", "w_gate", "w_up", "w_down")


def cache_layers(cfg: MlaScmoeConfig) -> int:
    """Leading axis of the cache tuple: two attention sublayers a layer."""
    return 2 * cfg.n_layers


# --------------------------------------------------------------------- init
def init_params(rng: jax.Array, cfg: MlaScmoeConfig, quantize: str | None = None) -> Params:
    """Random-init as models/mla_moe.py's: normal x 1/sqrt(fan_in), the
    embedding at unit scale, norms at one, the selection bias normal x
    BIAS_SCALE in float32. W_uq and W_ukv take as fan-in what the family's
    scale factors stand for, d_model (the factors correct a low-rank path's
    variance to a full-rank one's; drawn at 1/sqrt(rank) the queries come
    out at twice and the keys at sqrt(12) times unit scale, every softmax is
    all but one-hot and rounding decides which key wins: PERF.md §6 PR 34).
    Every matrix of the stack is drawn a layer at a
    time (`lax.map`): the float32 draw of a whole leaf (2.4 GB for a dense
    feed-forward's at the published widths) would not fit beside the
    weights."""
    if quantize is not None:
        raise ValueError(
            f"{cfg.name}: llm.quantization {quantize!r} is not served by "
            f"models/mla_scmoe.py (int8 expert weights: models/quant.py)"
        )
    D, H, L = cfg.d_model, cfg.n_heads, cfg.n_layers
    dq, dc, dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    E, Fe, F = cfg.experts_held, cfg.d_ff_expert, cfg.d_ff
    k_embed, k_head, k_layers = jax.random.split(rng, 3)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5).astype(cfg.dtype)

    def stacked(key, shape, fan_in):
        return jax.lax.map(lambda k: dense(k, shape, fan_in), jax.random.split(key, L))

    def ones(shape):
        return jnp.ones(shape, dtype=cfg.dtype)

    k = jax.random.split(k_layers, 13)
    fan_uq = D if cfg.mla_scale_q_lora else dq
    fan_ukv = D if cfg.mla_scale_kv_lora else dc
    return {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, D), dtype=jnp.float32)).astype(cfg.dtype),
        "final_norm": ones((D,)),
        "lm_head": dense(k_head, (D, cfg.vocab_size), D),
        "layers": {
            "attn_norm": ones((L, 2, D)),
            "w_dq": stacked(k[0], (2, D, dq), D),
            "q_norm": ones((L, 2, dq)),
            "w_uq": stacked(k[1], (2, dq, H * (dn + dr)), fan_uq),
            "w_dkv": stacked(k[2], (2, D, dc + dr), D),
            "kv_norm": ones((L, 2, dc)),
            "w_ukv": stacked(k[3], (2, dc, H * (dn + dv)), fan_ukv),
            "wo": stacked(k[4], (2, H * dv, D), H * dv),
            "mlp_norm": ones((L, 2, D)),
            "w_gate": stacked(k[5], (2, D, F), D),
            "w_up": stacked(k[6], (2, D, F), D),
            "w_down": stacked(k[7], (2, F, D), F),
            "router": stacked(k[8], (D, cfg.n_router_outputs), D),
            "router_bias": jax.random.normal(k[9], (L, cfg.n_router_outputs), dtype=jnp.float32) * BIAS_SCALE,
            "we_gate": stacked(k[10], (E, D, Fe), D),
            "we_up": stacked(k[11], (E, D, Fe), D),
            "we_down": stacked(k[12], (E, Fe, D), Fe),
        },
    }


# ---------------------------------------------------------------- the layer
def _dense_ffn(lp: Params, cfg: MlaScmoeConfig, h: jax.Array) -> jax.Array:
    with jax.named_scope("dense_ffn"):
        return _swiglu(h.astype(cfg.dtype), lp["w_gate"], lp["w_up"], lp["w_down"])


def _layer(layers, idx, cfg, x, positions, valid, inv_freq, attend):
    """Double layer `idx` (traced) of the stack `layers`, for every forward,
    over the float32 stream x. `attend(j, lp, q_nope, q_rope, c_kv, k_r)`:
    what sublayer j's queries may see is the forward's. Returns (x, ((c_kv,
    k_r) of these tokens, each with a leading sublayer axis of 2), COUNTERS).

    Every weight is read out of the WHOLE stack at (idx, j) by a slice of its
    own, which the compiler fuses into the one matmul that uses it. Scanning
    over the [L, 2, ..] leaves instead hands the body a layer's [2, ..] pair,
    which both sublayers use: the pair is then copied out whole, 1.28 GB a
    layer a model call at the published widths (PERF.md §6 PR 34)."""
    sub = [{k: layers[k][idx, j] for k in ATTENTION_LEAVES + DENSE_LEAVES} for j in (0, 1)]
    routed = {"router": layers["router"][idx], "router_bias": layers["router_bias"][idx],
              **{k: layers[k] for k in EXPERT_LEAVES}, "layer": idx}
    x, cache0 = attention_sublayer(sub[0], cfg, x, positions, inv_freq, functools.partial(attend, 0))
    with jax.named_scope("mlp"):
        h = rms_norm(x, sub[0]["mlp_norm"], cfg.rms_eps)  # F_0 and M read the same normed stream
        m, counters = routed_experts(routed, cfg, h.reshape(-1, h.shape[-1]), valid.reshape(-1))
        x = x + _dense_ffn(sub[0], cfg, h)
    x, cache1 = attention_sublayer(sub[1], cfg, x, positions, inv_freq, functools.partial(attend, 1))
    with jax.named_scope("mlp"):
        h = rms_norm(x, sub[1]["mlp_norm"], cfg.rms_eps)
        x = x + _dense_ffn(sub[1], cfg, h) + m.reshape(x.shape)  # the shortcut joins here
    return x, tuple(jnp.stack(pair) for pair in zip(cache0, cache1)), counters


def _sublayers(a: jax.Array, cfg: MlaScmoeConfig) -> jax.Array:
    """A cache array [2 L, ..] as [L, 2, ..], for the layer scan."""
    return a.reshape(cfg.n_layers, 2, *a.shape[1:])


def _run_layers(params, cfg, x, xs, step):
    """The layer scan, over the layer index and the caches alone: the
    weights stay whole (`_layer` says why). `step(layers, idx, x, xs_l) ->
    (x, (c, r), counters)`; `xs`: cache arrays [2 L, ..], handed to the step
    a layer's two at a time; the step's caches come back [2 L, ..]."""
    layers = params["layers"]

    def body(state, inp):
        x, counters = state
        xs_l, idx = inp
        x, ys, c = step(layers, idx, x, xs_l)
        return (x, counters + c), ys

    (x, counters), ys = jax.lax.scan(
        body, (x, jnp.zeros((len(COUNTERS),), jnp.int32)),
        (tuple(_sublayers(a, cfg) for a in xs), jnp.arange(cfg.n_layers)))
    return x, tuple(a.reshape(-1, *a.shape[2:]) for a in ys), counters


# ------------------------------------------------------------------ prefill
def forward_prefill_kv(params: Params, cfg: MlaScmoeConfig, tokens, seq_lens):
    """Full-prompt prefill for the cache alone, under scope
    `prefix_prefill`: (None, c_kv [2 L, B, S, dc], k_r [2 L, B, S, dr])."""
    B, S = tokens.shape
    inv_freq = _inv_freq(cfg)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    valid = positions < seq_lens[:, None]
    mask = (positions[0][:, None] >= positions[0][None, :])[None, None] & valid[:, None, None, :]

    def attend(_j, lp, q_nope, q_rope, c_kv, k_r):
        return attend_absorbed(lp, cfg, q_nope, q_rope, [(c_kv, k_r, mask)])

    def step(layers, idx, x, _xs):
        return _layer(layers, idx, cfg, x, positions, valid, inv_freq, attend)

    with jax.named_scope("prefix_prefill"):
        _, (c_all, r_all), _ = _run_layers(params, cfg, _stream(params, tokens), (), step)
    return None, c_all, r_all


def forward_prefill_suffix_dense(
    params: Params,
    cfg: MlaScmoeConfig,
    tokens: jax.Array,       # [B, Ss] int32, per-request suffix, left-aligned
    suffix_lens: jax.Array,  # [B] valid suffix tokens (0 = row unused)
    prefix_c: jax.Array,     # [2 L, Sp, dc] shared latent prefix
    prefix_r: jax.Array,     # [2 L, Sp, dr]
    prefix_len: jax.Array,   # scalar int32
    prefix_impl: Any = None,  # the dense family's kernel choice; no kernel here
):
    """Batched suffix prefill against the shared latent prefix, the suffix
    cache kept dense: (last_logits [B, V] f32, c_sfx [2 L, B, Ss, dc], r_sfx
    [2 L, B, Ss, dr], COUNTERS). Padding tokens of a suffix are not routed."""
    B, S = tokens.shape
    inv_freq = _inv_freq(cfg)
    j = jnp.arange(S)
    positions = prefix_len + jnp.broadcast_to(j, (B, S))
    valid = j[None, :] < suffix_lens[:, None]
    pre_mask = (jnp.arange(prefix_c.shape[1]) < prefix_len)[None, None, None, :]
    own_mask = (j[:, None] >= j[None, :])[None, None] & valid[:, None, None, :]

    def step(layers, idx, x, xs_l):
        pc, pr = xs_l

        def attend(s, lp, q_nope, q_rope, c_kv, k_r):
            return attend_absorbed(lp, cfg, q_nope, q_rope,
                                   [(pc[s], pr[s], pre_mask), (c_kv, k_r, own_mask)])

        return _layer(layers, idx, cfg, x, positions, valid, inv_freq, attend)

    x, (c_sfx, r_sfx), counters = _run_layers(
        params, cfg, _stream(params, tokens), (prefix_c, prefix_r), step)
    return _last_valid_logits(params, cfg, x.astype(cfg.dtype), suffix_lens), c_sfx, r_sfx, counters


# ------------------------------------------------------------- block decode
def forward_block_decode(
    params: Params,
    cfg: MlaScmoeConfig,
    blk_tok: jax.Array,    # [R, F] int32, this iteration's token block
    blk_valid: jax.Array,  # [R, F] bool, left-aligned valid tokens
    blk_len: jax.Array,    # [R] int32
    positions: jax.Array,  # [R, F] absolute positions
    c_sfx: jax.Array,      # [2 L, R, Ss, dc] latent suffix cache
    r_sfx: jax.Array,      # [2 L, R, Ss, dr]
    suffix_lens: jax.Array,  # [R]
    gen_c: jax.Array,      # [2 L, R, cap+F, dc] generated-token latents
    gen_r: jax.Array,      # [2 L, R, cap+F, dr]
    tail: jax.Array,       # [R] tokens already in gen_c / gen_r
    prefix_c: jax.Array,   # [2 L, Sp, dc] shared latent prefix
    prefix_r: jax.Array,
    prefix_len: jax.Array,
    prefix_impl: Any = None,
    ragged: bool = False,
):
    """One grammar-accelerated decode iteration through the latent caches,
    as models/mla_moe.py `forward_block_decode`: (logits [R, V] f32 at each
    row's last valid position, gen_c, gen_r, COUNTERS). The block's latents
    are written once every layer has run (ops/attention.write_block)."""
    if ragged:
        raise ValueError(f"{cfg.name}: llm.decode_matmul 'ragged' is not served by models/mla_scmoe.py")
    inv_freq = _inv_freq(cfg)
    j = jnp.arange(blk_tok.shape[1])
    pre_mask = (jnp.arange(prefix_c.shape[1]) < prefix_len)[None, None, None, :]
    sfx_mask = (jnp.arange(c_sfx.shape[2])[None, :] < suffix_lens[:, None])[:, None, None, :]
    gen_mask = (jnp.arange(gen_c.shape[2])[None, :] < tail[:, None])[:, None, None, :]
    blk_mask = ((j[:, None] >= j[None, :])[None] & blk_valid[:, None, :])[:, None]

    def step(layers, idx, x, xs_l):
        pc, pr, sc, sr = xs_l

        def attend(s, lp, q_nope, q_rope, c_kv, k_r):
            # gen_mask exposes entries < tail only: never this block's own
            at = 2 * idx + s
            return attend_absorbed(lp, cfg, q_nope, q_rope, [
                (pc[s], pr[s], pre_mask), (sc[s], sr[s], sfx_mask),
                (gen_c[at], gen_r[at], gen_mask), (c_kv, k_r, blk_mask),
            ])

        return _layer(layers, idx, cfg, x, positions, blk_valid, inv_freq, attend)

    x, (c_blk, r_blk), counters = _run_layers(
        params, cfg, _stream(params, blk_tok), (prefix_c, prefix_r, c_sfx, r_sfx), step)
    with jax.named_scope("kv_writeback"):
        gen_c = write_block(gen_c, tail, c_blk)
        gen_r = write_block(gen_r, tail, r_blk)
    return _last_valid_logits(params, cfg, x.astype(cfg.dtype), blk_len), gen_c, gen_r, counters
