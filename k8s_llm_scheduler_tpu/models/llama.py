"""Llama 3.x in pure functional JAX: RMSNorm, RoPE, GQA, SwiGLU.

This is the in-tree decision model that replaces the reference's network call
to HF-hosted Llama (reference scheduler.py:425-433). Design choices are
TPU/XLA-first, not a torch translation:

- **Pure pytrees, no Module system**: params are nested dicts of arrays;
  every entry point is a pure function of (params, inputs) and jit/pjit
  composes directly. Sharding is applied to the pytree from
  parallel/sharding.py PartitionSpecs.
- **Stacked layers + lax.scan**: all transformer blocks live in ONE stacked
  pytree (leading axis = layer), so XLA compiles one block body regardless of
  depth — 80-layer 70B compiles as fast as the 4-layer test config and the
  weights pytree is scan/pjit friendly.
- **Static shapes everywhere**: padded prompt buckets, fixed decode batch,
  masking instead of dynamic shapes, so nothing falls off the jit path.
- **Paged KV cache at decode**: the decode step scatters the new token's K/V
  into cache pages and attends via ops/attention.paged_decode_attention.
- bf16 weights/activations, f32 norm/softmax/logits accumulation (MXU-native).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.ops.attention import (
    BLOCK_PREFIX_COUNTERS,
    attend_part,
    block_prefix_attend_parts,
    block_prefix_counts,
    block_prefix_rung,
    causal_prefill_attention,
    chunk_attention_with_prefix,
    merge_attention_parts,
    paged_decode_attention,
    prefix_attend_parts,
    write_block,
)

Params = dict[str, Any]

# What the wave forwards count on the device for engine.stats: the prefix
# query positions block decode computed, narrowed and at full width
# (ops/attention.py block_prefix_attend_parts; the suffix call counts none).
COUNTERS = BLOCK_PREFIX_COUNTERS


def cache_token_shapes(cfg: LlamaConfig) -> tuple[tuple[int, ...], ...]:
    """Per-token trailing shapes of the cache tuple: (k, v)."""
    return ((cfg.n_kv_heads, cfg.head_dim),) * 2


def cache_layers(cfg: LlamaConfig) -> int:
    """Leading axis of the cache tuple: one attention a layer."""
    return cfg.n_layers


def state_shapes(cfg: LlamaConfig) -> tuple:
    """What a sequence carries besides its per-token cache: nothing."""
    return ()


def state_layers(cfg: LlamaConfig) -> int:
    return 0


# --------------------------------------------------------------------- norm
def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in f32, result back in input dtype."""
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * weight


# --------------------------------------------------------------------- rope
def rope_inv_freq(cfg: LlamaConfig) -> jax.Array:
    """Inverse RoPE frequencies with optional llama3 long-context scaling."""
    head_dim = cfg.head_dim
    inv = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    s = cfg.rope_scaling
    if s is None:
        return inv
    # llama3 scheme: low-freq bands divided by factor, high-freq kept,
    # smooth interpolation in between.
    wavelen = 2.0 * jnp.pi / inv
    low_wl = s.original_max_position / s.low_freq_factor
    high_wl = s.original_max_position / s.high_freq_factor
    smooth = (s.original_max_position / wavelen - s.low_freq_factor) / (
        s.high_freq_factor - s.low_freq_factor
    )
    smooth = jnp.clip(smooth, 0.0, 1.0)
    scaled = jnp.where(
        wavelen > low_wl,
        inv / s.factor,
        jnp.where(wavelen < high_wl, inv, (1 - smooth) * inv / s.factor + smooth * inv),
    )
    return scaled


def apply_rope(
    x: jax.Array,  # [..., n_heads, head_dim]
    positions: jax.Array,  # broadcastable to x's leading dims
    inv_freq: jax.Array,  # [head_dim//2]
) -> jax.Array:
    """Rotary embedding at absolute positions (half-split layout)."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., hd//2]
    cos = jnp.cos(angles)[..., None, :]  # [..., 1, hd//2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- init
def init_params(
    rng: jax.Array, cfg: LlamaConfig, quantize: str | None = None
) -> Params:
    """Random-init params with stacked layers (leading axis = n_layers).

    quantize="int8" converts each dense weight AS IT IS CREATED
    (models/quant.py, donated) — peak device memory is the int8 model plus
    one bf16 weight, which is what lets an 8B config random-init on a
    single 16 GB chip.
    """
    hd = cfg.head_dim
    keys = jax.random.split(rng, 10)
    if quantize is not None:
        from k8s_llm_scheduler_tpu.models.quant import _quantize_weight_donated

        if quantize != "int8":
            raise ValueError(f"unknown quantization {quantize!r}")

    def norm_init(shape):
        return jnp.ones(shape, dtype=cfg.dtype)

    def dense_init(key, shape, in_dim):
        scale = in_dim**-0.5
        w = (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(
            cfg.dtype
        )
        if quantize is not None and len(shape) == 3:  # stacked layer weights
            return _quantize_weight_donated(w)
        return w

    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    params: Params = {
        "embed": (
            jax.random.normal(keys[0], (cfg.vocab_size, D), dtype=jnp.float32) * 0.02
        ).astype(cfg.dtype),
        "final_norm": norm_init((D,)),
        "layers": {
            "attn_norm": norm_init((L, D)),
            "wq": dense_init(keys[1], (L, D, cfg.n_heads * hd), D),
            "wk": dense_init(keys[2], (L, D, cfg.n_kv_heads * hd), D),
            "wv": dense_init(keys[3], (L, D, cfg.n_kv_heads * hd), D),
            "wo": dense_init(keys[4], (L, cfg.n_heads * hd, D), cfg.n_heads * hd),
            "mlp_norm": norm_init((L, D)),
            "w_gate": dense_init(keys[5], (L, D, F), D),
            "w_up": dense_init(keys[6], (L, D, F), D),
            "w_down": dense_init(keys[7], (L, F, D), F),
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[8], (D, cfg.vocab_size), D)
    return params


# Named scopes (embed, attn, mlp, kv_writeback, lm_head) are set HERE, once,
# so that every step built from these functions carries them: a device
# trace then reads time by scope (observability/scopes.py). They are
# metadata on the compiled operations and change no fusion.
@jax.named_scope("embed")
def _embed(params: Params, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens]


def _layer_slice(layers: Params, i: int | jax.Array) -> Params:
    return jax.tree_util.tree_map(lambda a: a[i], layers)


@jax.named_scope("lm_head")
def _logits(params: Params, cfg: LlamaConfig, x: jax.Array) -> jax.Array:
    """LM head with f32 ACCUMULATION but native-dtype operands: casting a
    128k-vocab embedding to f32 materializes a multi-GB transient per model
    call (it OOMed the 8B single-chip config); preferred_element_type gets
    the f32 accumulate without the f32 copy."""
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if cfg.tie_embeddings:
        return jnp.einsum(
            "...d,vd->...v", x, params["embed"],
            preferred_element_type=jnp.float32,
        )
    return jnp.einsum(
        "...d,dv->...v", x, params["lm_head"],
        preferred_element_type=jnp.float32,
    )


def _dense(x: jax.Array, w, eq: str) -> jax.Array:
    """Dense projection dispatching on weight form: plain array, or the
    int8 weight-only pair {"q", "scale"} (models/quant.py) — the dequant
    convert fuses into the matmul, the per-channel scale broadcasts over
    the output axis."""
    if isinstance(w, dict):
        out = jnp.einsum(eq, x, w["q"].astype(x.dtype))
        return (out.astype(jnp.float32) * w["scale"]).astype(x.dtype)
    return jnp.einsum(eq, x, w)


@jax.named_scope("mlp")
def _mlp(lp: Params, cfg: LlamaConfig, x: jax.Array) -> jax.Array:
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    gate = _dense(h, lp["w_gate"], "...d,df->...f")
    up = _dense(h, lp["w_up"], "...d,df->...f")
    fused = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return _dense(fused, lp["w_down"], "...f,fd->...d")


# ------------------------------------------------------------------ prefill
def prefill_layer(
    lp: Params,
    cfg: LlamaConfig,
    x: jax.Array,  # [B, S, D]
    positions: jax.Array,  # [B, S]
    seq_lens: jax.Array,  # [B]
    inv_freq: jax.Array,
    attn_fn: Any = None,
) -> jax.Array:
    """One transformer layer of full-prompt prefill (shared by
    forward_prefill and the pipeline-parallel trunk, train/pipeline.py)."""
    B, S = x.shape[:2]
    hd = cfg.head_dim
    attn_impl = attn_fn if attn_fn is not None else causal_prefill_attention
    with jax.named_scope("attn"):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q = _dense(h, lp["wq"], "bsd,dh->bsh").reshape(B, S, cfg.n_heads, hd)
        k = _dense(h, lp["wk"], "bsd,dh->bsh").reshape(B, S, cfg.n_kv_heads, hd)
        v = _dense(h, lp["wv"], "bsd,dh->bsh").reshape(B, S, cfg.n_kv_heads, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        attn = attn_impl(q, k, v, seq_lens)
        attn = _dense(attn.reshape(B, S, cfg.n_heads * hd), lp["wo"], "bsh,hd->bsd")
        x = x + attn
    x = x + _mlp(lp, cfg, x)
    return x, (k, v)


def forward_prefill(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] int32, left-aligned, padded
    seq_lens: jax.Array,  # [B]
    attn_impl: Any = None,  # (q,k,v,seq_lens)->out; default causal full attn
    return_logits: bool = True,  # static; False skips the LM head (KV-only)
    remat: bool = False,  # static; checkpoint each layer (training path)
    return_hidden: bool = False,  # static; also return the final-layer
    # pre-norm residual stream [B, S, D] (hidden-transfer head training)
) -> tuple[jax.Array | None, jax.Array, jax.Array] | tuple[
    jax.Array | None, jax.Array, jax.Array, jax.Array
]:
    """Full-prompt forward pass.

    Returns (logits [B,S,V] f32, k_all [L,B,S,n_kv,hd], v_all [...]) — the
    engine scatters k_all/v_all into KV cache pages (engine/kv_cache.py).
    With return_logits=False, logits is None — the prefix-prefill path only
    needs KV, and a full-bucket [S, vocab] logits tensor is pure waste
    (~8 GB at 128k vocab x 16k bucket).

    `attn_impl` swaps the attention kernel: the training path passes a
    ring-attention wrapper (parallel/ring_attention.py) when the mesh has a
    sequence-parallel axis. Must be static under jit (pass via closure or
    static_argnums).

    `remat=True` wraps each scanned layer in jax.checkpoint so the
    backward pass rematerializes per-layer activations instead of keeping
    all L layers' intermediates live — the standard HBM-for-FLOPs trade
    (~25-30% more compute for ~1/L the activation memory). Inference
    callers never set it; the train step does (measured: the small config
    at batch 6 x seq 2048 compiles to 16.7 GB without remat — over a
    v5e's 15.75 GB — and well under with it).
    """
    B, S = tokens.shape
    inv_freq = rope_inv_freq(cfg)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    x = _embed(params, tokens)  # [B, S, D]

    def body(x, lp):
        return prefill_layer(lp, cfg, x, positions, seq_lens, inv_freq, attn_impl)

    if remat:
        body = jax.checkpoint(body)
    x, (k_all, v_all) = jax.lax.scan(body, x, params["layers"])
    logits = _logits(params, cfg, x) if return_logits else None
    if return_hidden:
        return logits, k_all, v_all, x
    return logits, k_all, v_all


def forward_prefill_kv(params: Params, cfg: LlamaConfig, tokens, seq_lens):
    """`forward_prefill` for KV alone, under scope `prefix_prefill`: what the
    engine's prefix prefill runs (engine/engine.py `_prefill_kv`)."""
    with jax.named_scope("prefix_prefill"):
        return forward_prefill(params, cfg, tokens, seq_lens, return_logits=False)


# ------------------------------------------------- suffix prefill (cascade)
def _suffix_layer(
    lp: Params,
    cfg: LlamaConfig,
    x: jax.Array,  # [B, S, D]
    positions: jax.Array,  # [B, S]
    suffix_lens: jax.Array,  # [B]
    pk: jax.Array,  # [Sp, n_kv, hd] this layer's shared prefix KV
    pv: jax.Array,
    prefix_len: jax.Array,
    inv_freq: jax.Array,
    prefix_impl: str | None = None,  # static — ops/attention.prefix_attend_parts
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One transformer layer of cascade suffix prefill: attends to the
    shared dense prefix + causally within the suffix. Shared by the paged
    (forward_prefill_suffix) and dense/wave (forward_prefill_suffix_dense)
    paths, which differ only in where the suffix K/V is sunk.
    Returns (x_out, k, v)."""
    B, S = x.shape[:2]
    hd = cfg.head_dim
    with jax.named_scope("attn"):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q = _dense(h, lp["wq"], "bsd,dh->bsh").reshape(B, S, cfg.n_heads, hd)
        k = _dense(h, lp["wk"], "bsd,dh->bsh").reshape(B, S, cfg.n_kv_heads, hd)
        v = _dense(h, lp["wv"], "bsd,dh->bsh").reshape(B, S, cfg.n_kv_heads, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        attn = chunk_attention_with_prefix(
            q, k, v, suffix_lens, pk, pv, prefix_len, prefix_impl=prefix_impl
        )
        attn = _dense(attn.reshape(B, S, cfg.n_heads * hd), lp["wo"], "bsh,hd->bsd")
        x = x + attn
    x = x + _mlp(lp, cfg, x)
    return x, k, v


def _last_valid_logits(
    params: Params, cfg: LlamaConfig, x: jax.Array, lens: jax.Array
) -> jax.Array:
    """Logits at each row's final valid token ([B, S, D], [B] -> [B, V])."""
    last_idx = jnp.maximum(lens - 1, 0)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    return _logits(params, cfg, x_last)


def forward_prefill_suffix(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, Ss] int32 — per-request suffix, left-aligned
    suffix_lens: jax.Array,  # [B] valid suffix tokens (0 = row unused)
    prefix_k_all: jax.Array,  # [L, Sp, n_kv, hd] — shared dense prefix KV
    prefix_v_all: jax.Array,
    prefix_len: jax.Array,  # scalar int32 — valid prefix tokens (0 = none)
    k_cache: jax.Array,  # [L, num_pages, page_size, n_kv, hd] (donate)
    v_cache: jax.Array,
    page_ids: jax.Array,  # [B, Ss/page_size] dest page per suffix block (0=scratch)
    prefix_impl: str | None = None,  # static
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched suffix prefill against a shared dense prefix.

    The whole burst's per-pod prompt tails prefill in ONE program: each row
    attends to the burst-shared cluster-state prefix (read once from HBM via
    cascade attention, ops/attention.py) plus causally within its own
    suffix; the suffix K/V is scattered straight into the paged KV cache.
    Returns (last_logits [B,V] f32 — logits at each row's final valid token,
    k_cache, v_cache). This replaces per-request full-prompt prefill for the
    scheduling-burst workload (the reference pays a full remote prefill per
    pod, reference scheduler.py:425-433).
    """
    B, S = tokens.shape
    hd = cfg.head_dim
    page_size = k_cache.shape[2]
    n_blocks = S // page_size
    inv_freq = rope_inv_freq(cfg)
    positions = prefix_len + jnp.broadcast_to(jnp.arange(S), (B, S))

    x = _embed(params, tokens)  # [B, S, D]
    layer_ids = jnp.arange(cfg.n_layers)

    def body(carry, xs):
        x, kc, vc = carry
        lp, pk, pv, idx = xs
        x, k, v = _suffix_layer(
            lp, cfg, x, positions, suffix_lens, pk, pv, prefix_len, inv_freq,
            prefix_impl=prefix_impl,
        )
        # Scatter this layer's suffix K/V blocks into their pages (padding
        # blocks were routed to the reserved scratch page 0 by the caller).
        blocks_k = k.reshape(B, n_blocks, page_size, cfg.n_kv_heads, hd)
        blocks_v = v.reshape(B, n_blocks, page_size, cfg.n_kv_heads, hd)
        kc = kc.at[idx, page_ids].set(blocks_k.astype(kc.dtype))
        vc = vc.at[idx, page_ids].set(blocks_v.astype(vc.dtype))
        return (x, kc, vc), None

    (x, k_cache, v_cache), _ = jax.lax.scan(
        body, (x, k_cache, v_cache),
        (params["layers"], prefix_k_all, prefix_v_all, layer_ids),
    )
    return _last_valid_logits(params, cfg, x, suffix_lens), k_cache, v_cache


def forward_prefill_suffix_dense(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, Ss] int32 — per-request suffix, left-aligned
    suffix_lens: jax.Array,  # [B] valid suffix tokens (0 = row unused)
    prefix_k_all: jax.Array,  # [L, Sp, n_kv, hd] — shared dense prefix KV
    prefix_v_all: jax.Array,
    prefix_len: jax.Array,  # scalar int32 — valid prefix tokens (0 = none)
    prefix_impl: str | None = None,  # static
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Batched suffix prefill against a shared dense prefix, KV kept DENSE.

    Identical attention semantics to forward_prefill_suffix, but instead of
    scattering suffix K/V into paged-cache pages it returns the stacked
    dense buffers (k_sfx, v_sfx) [L, B, Ss, n_kv, hd]. This is the first
    stage of the fused decision wave (engine/engine.py _wave_impl): the wave
    decodes to completion against (prefix | dense suffix | chunk buffer)
    without ever touching the paged KV cache — no page allocation, no
    gather/flush traffic, no multi-hundred-MB donation per dispatch.
    Returns (last_logits [B, V] f32, k_sfx, v_sfx, COUNTERS: zero).
    """
    B, S = tokens.shape
    inv_freq = rope_inv_freq(cfg)
    positions = prefix_len + jnp.broadcast_to(jnp.arange(S), (B, S))

    x = _embed(params, tokens)  # [B, S, D]

    def body(x, xs):
        lp, pk, pv = xs
        x, k, v = _suffix_layer(
            lp, cfg, x, positions, suffix_lens, pk, pv, prefix_len, inv_freq,
            prefix_impl=prefix_impl,
        )
        return x, (k, v)

    x, (k_sfx, v_sfx) = jax.lax.scan(
        body, x, (params["layers"], prefix_k_all, prefix_v_all)
    )
    return (_last_valid_logits(params, cfg, x, suffix_lens), k_sfx, v_sfx,
            jnp.zeros((len(COUNTERS),), jnp.int32))


def forward_prefill_packed(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,     # [C] int32 — one packed chunk (pad holes)
    seg: jax.Array,        # [C] int32 — segment id per token, -1 on padding
    positions: jax.Array,  # [C] int32 — ABSOLUTE position of each token
    prefix_k_all: jax.Array,  # [L, Sp, n_kv, hd] — shared dense prefix KV
    prefix_v_all: jax.Array,
    prefix_len: jax.Array,    # scalar int32
    carry_k: jax.Array,    # [L, CAP, n_kv, hd] pack carry (donate)
    carry_v: jax.Array,
    carry_seg: jax.Array,  # [CAP] int32 segment per carry entry (-1 empty)
    carry_len: jax.Array,  # scalar int32 — tokens already in the carry
    k_cache: jax.Array,    # [L, num_pages, page_size, n_kv, hd] (donate)
    v_cache: jax.Array,
    page_ids: jax.Array,   # [C] per-token dest page (0 = scratch)
    offs: jax.Array,       # [C] per-token dest offset within the page
    end_idx: jax.Array,    # [E] chunk-local indices of prompt-final tokens
    prefix_impl: str | None = None,  # static
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One PACKED prefill chunk: many prompts in one token stream with
    BLOCK-DIAGONAL attention (the Prepacking scheme, arXiv:2404.09529).

    Token i (segment s_i) attends to:
    - the burst-shared dense prefix (every real token — the prompts all
      continue the same cluster-state prefix);
    - carry entries of the SAME segment (this prompt's tokens from earlier
      chunks of the pack — how a prompt spans a chunk boundary);
    - chunk tokens j <= i of the SAME segment (causal within the prompt,
      blocked across prompts).

    Padding tokens (seg -1) only ever match other padding (their K/V
    lands in the scratch page / is never attended by real queries), so a
    partially-filled final chunk needs no special casing. The chunk's K/V
    is scattered per token into the paged KV cache (each prompt's slot
    pages) AND appended to the pack carry at `carry_len`.

    Returns (end_logits [E, V] f32 — logits at each listed prompt-final
    token, carry_k, carry_v, carry_seg, k_cache, v_cache). Semantically
    this computes EXACTLY what per-prompt serial prefill computes — the
    token-identity test pins packed+chunked greedy decode against the
    serial whole-prompt path (tests/test_admission.py).
    """
    C = tokens.shape[0]
    CAP = carry_k.shape[1]
    hd = cfg.head_dim
    inv_freq = rope_inv_freq(cfg)

    x = _embed(params, tokens)[None]  # [1, C, D]
    pos_b = positions[None, :]  # [1, C]

    # Masks are layer-independent: build once outside the scan.
    carry_mask = (
        (jnp.arange(CAP)[None, :] < carry_len)
        & (carry_seg[None, :] == seg[:, None])
    )[None, None, None, :, :]  # [1, 1, 1, C, CAP]
    j = jnp.arange(C)
    blk_mask = (
        (j[:, None] >= j[None, :]) & (seg[:, None] == seg[None, :])
    )[None, None, None, :, :]  # [1, 1, 1, C, C]

    def body(carry, xs):
        x, ck, cv, kc, vc = carry
        lp, pk, pv, idx = xs
        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
            q = _dense(h, lp["wq"], "bsd,dh->bsh").reshape(1, C, cfg.n_heads, hd)
            k = _dense(h, lp["wk"], "bsd,dh->bsh").reshape(1, C, cfg.n_kv_heads, hd)
            v = _dense(h, lp["wv"], "bsd,dh->bsh").reshape(1, C, cfg.n_kv_heads, hd)
            q = apply_rope(q, pos_b, inv_freq)
            k = apply_rope(k, pos_b, inv_freq)
            qg = (q.astype(jnp.float32) * hd**-0.5).reshape(
                1, C, cfg.n_kv_heads, cfg.q_per_kv, hd
            )
            parts = [
                prefix_attend_parts(q, qg, pk, pv, prefix_len, impl=prefix_impl),
                attend_part(
                    qg, ck[idx][None], cv[idx][None], carry_mask,
                    "bqkgh,bskh->bkgqs",
                ),
                attend_part(qg, k, v, blk_mask, "bqkgh,bskh->bkgqs"),
            ]
            attn = merge_attention_parts(parts)  # [1, n_kv, g, C, hd]
            attn = jnp.moveaxis(attn, 3, 1).reshape(1, C, cfg.n_heads * hd)
            attn = _dense(attn.astype(x.dtype), lp["wo"], "bsh,hd->bsd")
            x = x + attn
        x = x + _mlp(lp, cfg, x)
        # Scatter this chunk's K/V into the paged cache (per-token dests;
        # padding routed to the reserved scratch page 0 by the caller)...
        kc = kc.at[idx, page_ids, offs].set(k[0].astype(kc.dtype))
        vc = vc.at[idx, page_ids, offs].set(v[0].astype(vc.dtype))
        # ...and append it to the pack carry so later chunks of a
        # boundary-spanning prompt can attend their earlier tokens.
        layer_k = jax.lax.dynamic_update_slice_in_dim(
            ck[idx], k[0].astype(ck.dtype), carry_len, axis=0
        )
        layer_v = jax.lax.dynamic_update_slice_in_dim(
            cv[idx], v[0].astype(cv.dtype), carry_len, axis=0
        )
        ck = jax.lax.dynamic_update_index_in_dim(ck, layer_k, idx, axis=0)
        cv = jax.lax.dynamic_update_index_in_dim(cv, layer_v, idx, axis=0)
        return (x, ck, cv, kc, vc), None

    (x, carry_k, carry_v, k_cache, v_cache), _ = jax.lax.scan(
        body,
        (x, carry_k, carry_v, k_cache, v_cache),
        (
            params["layers"], prefix_k_all, prefix_v_all,
            jnp.arange(cfg.n_layers),
        ),
    )
    carry_seg = jax.lax.dynamic_update_slice(carry_seg, seg, (carry_len,))
    # LM head only at the prompt-final tokens: the full [C, V] logits
    # tensor is pure waste on the admission path.
    x_end = x[0][end_idx]  # [E, D]
    return _logits(params, cfg, x_end), carry_k, carry_v, carry_seg, k_cache, v_cache


def forward_block_decode(
    params: Params,
    cfg: LlamaConfig,
    blk_tok: jax.Array,  # [R, F] int32 — this iteration's token block
    blk_valid: jax.Array,  # [R, F] bool — left-aligned valid tokens
    blk_len: jax.Array,  # [R] int32 — number of valid tokens (= blk_valid sum)
    positions: jax.Array,  # [R, F] absolute positions
    k_sfx: jax.Array,  # [L, R, Ss, n_kv, hd] dense suffix KV
    v_sfx: jax.Array,
    suffix_lens: jax.Array,  # [R]
    gen_k: jax.Array,  # [L, R, cap+F, n_kv, hd] generated-token KV (donated)
    gen_v: jax.Array,
    tail: jax.Array,  # [R] tokens already in gen_k/gen_v
    prefix_k_all: jax.Array,  # [L, Sp, n_kv, hd] shared dense prefix
    prefix_v_all: jax.Array,
    prefix_len: jax.Array,  # scalar int32
    prefix_impl: str | None = None,  # static
    ragged: bool = False,  # static: ragged-M Pallas matmuls (single device)
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One grammar-accelerated decode iteration: an F-wide mini-prefill.

    Where per-token decode runs the model once per emitted token, block
    decode runs it once per ITERATION, consuming a whole (sampled + forced)
    token run: every valid block token attends to the shared dense prefix,
    its row's dense suffix, the generated-so-far buffer, and causally within
    the block, all in one pass — so a forced JSON-skeleton span costs one
    model call instead of one per character. The layers read the generated
    buffers and hand back the block's K/V, which is written once they have
    all run (ops/attention.write_block: row r's F-wide window at tail[r];
    the padded positions' values land past the new tail, where nothing
    reads them). The shared-prefix part alone runs at the first w block
    positions, w the narrowest width of ops/attention.py
    block_prefix_widths that holds max(blk_len), picked once a call; the
    suffix, generated and in-block parts keep width F.

    `ragged=True` removes the F-width padding from every projection/MLP
    matmul (SCALING.md wave roofline: 62% of decode compute at the
    250-token point): valid tokens are compacted to the front of the
    flattened [R*F] axis once per iteration (argsort shared by all
    layers), the residual stream stays compacted through the scan, and
    matmuls run in ops/ragged_matmul with the valid-token count scalar-
    prefetched so FLOPs scale with real tokens. Attention and K/V
    bookkeeping stay in the [R, F] layout (they are the small term and
    are row-structured); q/k/v scatter back through the inverse
    permutation. Dead compacted rows carry garbage — every consumer
    masks by blk_valid (the generated buffers by tail), exactly as the
    dense path already requires.

    Returns (logits [R, V] f32 at each row's LAST VALID block position,
    gen_k, gen_v, COUNTERS).
    """
    R, F = blk_tok.shape
    hd = cfg.head_dim
    inv_freq = rope_inv_freq(cfg)

    x = _embed(params, blk_tok)  # [R, F, D]
    Ss = k_sfx.shape[2]
    rung = block_prefix_rung(blk_len, F)  # every layer's prefix part, one width

    if ragged:
        from k8s_llm_scheduler_tpu.ops.ragged_matmul import ragged_matmul

        flat_valid = blk_valid.reshape(R * F)
        perm = jnp.argsort(jnp.logical_not(flat_valid), stable=True)
        inv_perm = jnp.argsort(perm)
        total = jnp.sum(blk_len)
        # last valid token of row r in compacted order (rows with len 0
        # clamp to 0 — their logits are never consumed, same contract as
        # the dense path's max(len-1, 0))
        last_c = jnp.maximum(jnp.cumsum(blk_len) - 1, 0)

        def _rdense(h, w):
            return ragged_matmul(h, w, total)

    sfx_mask = (jnp.arange(Ss)[None, :] < suffix_lens[:, None])[
        :, None, None, None, :
    ]
    gen_mask = (jnp.arange(gen_k.shape[2])[None, :] < tail[:, None])[
        :, None, None, None, :
    ]
    j = jnp.arange(F)
    blk_mask = (
        (j[:, None] >= j[None, :])[None, :, :] & blk_valid[:, None, :]
    )[:, None, None, :, :]  # [R, 1, 1, F_q, F_kv]
    # The generated buffers are read like the suffix KV, a layer's slab a
    # step; the layers' block K/V comes back stacked [L, R, F, n_kv, hd].
    xs = (
        params["layers"], prefix_k_all, prefix_v_all,
        k_sfx, v_sfx, gen_k, gen_v,
    )

    @jax.named_scope("kv_writeback")
    def written(k_blk, v_blk):
        return write_block(gen_k, tail, k_blk), write_block(gen_v, tail, v_blk)

    if ragged:
        xc = x.reshape(R * F, -1)[perm]  # valid tokens first

        def body_ragged(xc, xs):
            lp, pk, pv, ks, vs, gk, gv = xs
            with jax.named_scope("attn"):
                h = rms_norm(xc, lp["attn_norm"], cfg.rms_eps)
                q = _rdense(h, lp["wq"])[inv_perm].reshape(
                    R, F, cfg.n_heads, hd
                )
                k = _rdense(h, lp["wk"])[inv_perm].reshape(
                    R, F, cfg.n_kv_heads, hd
                )
                v = _rdense(h, lp["wv"])[inv_perm].reshape(
                    R, F, cfg.n_kv_heads, hd
                )
                q = apply_rope(q, positions, inv_freq)
                k = apply_rope(k, positions, inv_freq)
                qg = (q.astype(jnp.float32) * hd**-0.5).reshape(
                    R, F, cfg.n_kv_heads, cfg.q_per_kv, hd
                )
                parts = [
                    block_prefix_attend_parts(rung, q, qg, pk, pv, prefix_len, impl=prefix_impl),
                    attend_part(qg, ks, vs, sfx_mask, "bqkgh,bskh->bkgqs"),
                    attend_part(qg, gk, gv, gen_mask, "bqkgh,bskh->bkgqs"),
                    attend_part(qg, k, v, blk_mask, "bqkgh,bskh->bkgqs"),
                ]
                attn = merge_attention_parts(parts)
                attn = jnp.moveaxis(attn, 3, 1).reshape(R * F, cfg.n_heads * hd)
                attn_c = attn[perm].astype(xc.dtype)
                xc = xc + _rdense(attn_c, lp["wo"])
            with jax.named_scope("mlp"):
                h2 = rms_norm(xc, lp["mlp_norm"], cfg.rms_eps)
                gate = _rdense(h2, lp["w_gate"])
                up = _rdense(h2, lp["w_up"])
                fused = jax.nn.silu(gate.astype(jnp.float32)).astype(xc.dtype) * up
                xc = xc + _rdense(fused, lp["w_down"])
            return xc, (k, v)

        xc, blk_kv = jax.lax.scan(body_ragged, xc, xs)
        return _logits(params, cfg, xc[last_c]), *written(*blk_kv), block_prefix_counts(rung, R, F)

    def body(x, xs):
        lp, pk, pv, ks, vs, gk, gv = xs
        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
            q = _dense(h, lp["wq"], "bfd,dh->bfh").reshape(R, F, cfg.n_heads, hd)
            k = _dense(h, lp["wk"], "bfd,dh->bfh").reshape(R, F, cfg.n_kv_heads, hd)
            v = _dense(h, lp["wv"], "bfd,dh->bfh").reshape(R, F, cfg.n_kv_heads, hd)
            q = apply_rope(q, positions, inv_freq)
            k = apply_rope(k, positions, inv_freq)

            qg = (q.astype(jnp.float32) * hd**-0.5).reshape(
                R, F, cfg.n_kv_heads, cfg.q_per_kv, hd
            )
            # gen_mask only exposes entries < tail (previous iterations);
            # in-block attention comes from the dense k/v just computed.
            parts = [
                block_prefix_attend_parts(rung, q, qg, pk, pv, prefix_len, impl=prefix_impl),
                attend_part(qg, ks, vs, sfx_mask, "bqkgh,bskh->bkgqs"),
                attend_part(qg, gk, gv, gen_mask, "bqkgh,bskh->bkgqs"),
                attend_part(qg, k, v, blk_mask, "bqkgh,bskh->bkgqs"),
            ]
            attn = merge_attention_parts(parts)  # [R, n_kv, g, F, hd]
            attn = jnp.moveaxis(attn, 3, 1).reshape(R, F, cfg.n_heads * hd)
            attn = _dense(attn.astype(x.dtype), lp["wo"], "bfh,hd->bfd")
            x = x + attn
        x = x + _mlp(lp, cfg, x)
        return x, (k, v)

    x, blk_kv = jax.lax.scan(body, x, xs)
    return _last_valid_logits(params, cfg, x, blk_len), *written(*blk_kv), block_prefix_counts(rung, R, F)


def forward_decode_buffered(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B] int32 — one new token per slot
    positions: jax.Array,  # [B] ABSOLUTE position of that token
    k_own: jax.Array,  # own-token KV, layout per own_impl (see below)
    v_own: jax.Array,
    own_lens: jax.Array,  # [B] valid own tokens (chunk-start lengths)
    chunk_k: jax.Array,  # [L, B, n_steps, n_kv, hd] — this chunk's new KV
    chunk_v: jax.Array,
    tail_len: jax.Array,  # [B] entries already in the chunk buffer
    prefix_k_all: jax.Array,  # [L, Sp, n_kv, hd] shared dense prefix
    prefix_v_all: jax.Array,
    prefix_len: jax.Array,  # scalar int32
    page_tables: jax.Array | None = None,  # [B, P] (own_impl="pallas" only)
    own_impl: str = "dense",  # static: "dense" pre-gathered | "pallas" kernel
    shmap: Any = None,  # static ops.attention.AttnImpl | None —
    # wraps the paged kernel in shard_map over the tp kv-head axis
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step against (prefix | own tokens | chunk buffer).

    The fused-chunk fast path (engine/engine.py): per-step K/V appends go to
    a small dense chunk buffer instead of the big paged cache — the paged
    scatter measured ~1.8 ms/step on this size class vs ~0.05 ms for the
    buffer append; the engine flushes the buffer to pages ONCE per chunk.
    Attention is a 3-part cascade merged exactly via log-sum-exp:
      A. shared dense prefix (read once for the whole batch),
      B. the slot's own tokens — own_impl="dense": pre-gathered dense KV
         [L, B, L_own, n_kv, hd] frozen for the chunk; own_impl="pallas":
         the paged caches [L, num_pages, ps, n_kv, hd] + page_tables,
         streamed page-by-page by the Pallas kernel
         (ops/pallas_paged_attention.paged_decode_attention_parts) with no
         materialized gather,
      C. the chunk buffer (this chunk's tokens, including the current one).
    Returns (logits [B,V] f32, chunk_k, chunk_v).
    """
    B = tokens.shape[0]
    hd = cfg.head_dim
    n_steps = chunk_k.shape[2]
    inv_freq = rope_inv_freq(cfg)
    if own_impl == "pallas":
        from k8s_llm_scheduler_tpu.ops.pallas_paged_attention import (
            paged_decode_attention_parts,
            paged_decode_attention_parts_shmap,
        )

        if shmap is not None:
            def paged_parts(q, ko, vo, pt, lens):
                return paged_decode_attention_parts_shmap(
                    q, ko, vo, pt, lens, shmap.mesh, shmap.axis
                )
        else:
            paged_parts = paged_decode_attention_parts

    x = _embed(params, tokens)  # [B, D]
    layer_ids = jnp.arange(cfg.n_layers)
    q_per_kv = cfg.q_per_kv
    row = jnp.arange(B)

    Sp = prefix_k_all.shape[1]
    pre_mask = (jnp.arange(Sp) < prefix_len)[None, None, None, :]
    if own_impl == "dense":
        L_own = k_own.shape[2]
        own_mask = (jnp.arange(L_own)[None, :] < own_lens[:, None])[:, None, None, :]
    # current token attends itself: include the entry written this step
    tail_mask = (jnp.arange(n_steps)[None, :] <= tail_len[:, None])[:, None, None, :]

    def body(carry, xs):
        x, ck, cv = carry
        lp, pk, pv, ko, vo, idx = xs
        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
            q = _dense(h, lp["wq"], "bd,dh->bh").reshape(B, cfg.n_heads, hd)
            k = _dense(h, lp["wk"], "bd,dh->bh").reshape(B, cfg.n_kv_heads, hd)
            v = _dense(h, lp["wv"], "bd,dh->bh").reshape(B, cfg.n_kv_heads, hd)
            q = apply_rope(q, positions, inv_freq)
            k = apply_rope(k, positions, inv_freq)

            ck = ck.at[idx, row, tail_len].set(k.astype(ck.dtype))
            cv = cv.at[idx, row, tail_len].set(v.astype(cv.dtype))

            qg = (q.astype(jnp.float32) * hd**-0.5).reshape(B, cfg.n_kv_heads, q_per_kv, hd)
            if own_impl == "pallas":
                own_part = paged_parts(q, ko, vo, page_tables, own_lens)
            else:
                own_part = attend_part(qg, ko, vo, own_mask, "bkgh,blkh->bkgl")
            parts = [
                attend_part(qg, pk, pv, pre_mask, "bkgh,skh->bkgs"),
                own_part,
                attend_part(qg, ck[idx], cv[idx], tail_mask, "bkgh,blkh->bkgl"),
            ]
            attn = merge_attention_parts(parts).reshape(B, cfg.n_heads * hd).astype(x.dtype)
            attn = _dense(attn, lp["wo"], "bh,hd->bd")
            x = x + attn
        x = x + _mlp(lp, cfg, x)
        return (x, ck, cv), None

    (x, chunk_k, chunk_v), _ = jax.lax.scan(
        body, (x, chunk_k, chunk_v),
        (params["layers"], prefix_k_all, prefix_v_all, k_own, v_own, layer_ids),
    )
    return _logits(params, cfg, x), chunk_k, chunk_v


def forward_decode_fused_body(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,
    positions: jax.Array,
    k_own: jax.Array,
    v_own: jax.Array,
    own_lens: jax.Array,
    chunk_k: jax.Array,
    chunk_v: jax.Array,
    tail_len: jax.Array,
    prefix_k_all: jax.Array,
    prefix_v_all: jax.Array,
    prefix_len: jax.Array,
    page_tables: jax.Array | None = None,
    own_impl: str = "dense",
    shmap: Any = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The fused decode loop's BODY forward (engine/fused/loop.py).

    Identical math to `forward_decode_buffered` — the one-step cascade
    the chunked scan runs — re-exported under the fused loop's contract so
    the two decode paths provably share one forward (greedy fused ==
    chunked token identity rests on this being the SAME function, not a
    lookalike):

    - every array keeps a STATIC shape across iterations (`tail_len` is
      the only induction input; the chunk buffer is preallocated at the
      chunk length), which is what lets `lax.while_loop` carry the state
      without re-tracing;
    - the frozen own-page KV (`k_own`/`v_own`) is closed over by the loop
      body as a while_loop constant — the gather happens once per chunk
      outside the loop, never per iteration;
    - per-step K/V lands in the chunk buffer at `tail_len`, so the fused
      loop's post-exit page flush sees exactly the layout the chunked
      path's flush was written for.
    """
    return forward_decode_buffered(
        params, cfg, tokens, positions, k_own, v_own, own_lens,
        chunk_k, chunk_v, tail_len, prefix_k_all, prefix_v_all, prefix_len,
        page_tables=page_tables, own_impl=own_impl, shmap=shmap,
    )


# ------------------------------------------------------------------- decode
def forward_decode(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B] int32 — one new token per slot
    positions: jax.Array,  # [B] 0-indexed position of the new token
    k_cache: jax.Array,  # [L, num_pages, page_size, n_kv, hd]
    v_cache: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    active: jax.Array,  # [B] bool — inactive slots neither write nor matter
    paged_attn: str = "xla",  # static: "xla" gather path | "pallas" kernel
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One autoregressive decode step over the paged KV cache.

    Scatters the new token's K/V into the cache pages, attends over all
    cached tokens (including the new one), returns (logits [B,V] f32,
    k_cache, v_cache). Pass caches as donated args under jit so updates
    happen in place. paged_attn="pallas" swaps the gather-then-attend XLA
    path for the streaming Pallas kernel
    (ops/pallas_paged_attention.py); must be static under jit.
    """
    B = tokens.shape[0]
    hd = cfg.head_dim
    page_size = k_cache.shape[2]
    inv_freq = rope_inv_freq(cfg)

    if paged_attn == "pallas":
        from k8s_llm_scheduler_tpu.ops.pallas_paged_attention import (
            paged_decode_attention_pallas,
        )

        attn_kernel = paged_decode_attention_pallas
    else:
        attn_kernel = paged_decode_attention

    page_slot = positions // page_size  # which entry of the page table
    page_ids = jnp.take_along_axis(page_tables, page_slot[:, None], axis=1)[:, 0]
    offsets = positions % page_size
    # Inactive slots must not write through their (possibly recycled) page
    # table — redirect them to page 0, which the KV cache manager reserves
    # as scratch and never allocates to a sequence.
    page_ids = jnp.where(active, page_ids, 0)
    offsets = jnp.where(active, offsets, 0)
    seq_lens = positions + 1

    x = _embed(params, tokens)  # [B, D]

    def body(carry, lp_with_idx):
        x, kc, vc = carry
        lp, idx = lp_with_idx
        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
            q = _dense(h, lp["wq"], "bd,dh->bh").reshape(B, cfg.n_heads, hd)
            k = _dense(h, lp["wk"], "bd,dh->bh").reshape(B, cfg.n_kv_heads, hd)
            v = _dense(h, lp["wv"], "bd,dh->bh").reshape(B, cfg.n_kv_heads, hd)
            q = apply_rope(q, positions, inv_freq)
            k = apply_rope(k, positions, inv_freq)

            # Scatter new K/V into this layer's pages (inactive slots were
            # redirected to the reserved scratch page 0 above).
            layer_k = kc[idx]
            layer_v = vc[idx]
            layer_k = layer_k.at[page_ids, offsets].set(k)
            layer_v = layer_v.at[page_ids, offsets].set(v)
            kc = jax.lax.dynamic_update_index_in_dim(kc, layer_k, idx, axis=0)
            vc = jax.lax.dynamic_update_index_in_dim(vc, layer_v, idx, axis=0)

            attn = attn_kernel(q, layer_k, layer_v, page_tables, seq_lens)
            attn = _dense(attn.reshape(B, cfg.n_heads * hd), lp["wo"], "bh,hd->bd")
            x = x + attn
        x = x + _mlp(lp, cfg, x)
        return (x, kc, vc), None

    layer_ids = jnp.arange(cfg.n_layers)
    (x, k_cache, v_cache), _ = jax.lax.scan(
        body, (x, k_cache, v_cache), (params["layers"], layer_ids)
    )
    return _logits(params, cfg, x), k_cache, v_cache


# ------------------------------------------------ hidden-transfer head
def init_hidden_transfer(rng: jax.Array, cfg: LlamaConfig, k: int) -> Params:
    """Random-init a hidden-transfer multi-token prediction head
    (*Hidden Transfer*, PAPERS.md): `k` per-offset transfer matrices
    [k, D, D] applied RESIDUALLY to the target's final-layer hidden state
    — x_h = x + x @ T_h — then pushed through the model's OWN final norm
    and LM head (no second vocab projection to train or store).

    Init is small (0.02/sqrt(D)) so x_h ~= x at step 0: the untrained
    head predicts roughly the current position's distribution for every
    future offset — a sane warm start for train/hidden.py, and never a
    correctness hazard (the spec verifier accepts only target-consistent
    tokens regardless of what the head proposes).
    """
    if k < 1:
        raise ValueError(f"hidden-transfer k must be >= 1, got {k}")
    D = cfg.d_model
    scale = 0.02 * D**-0.5
    t = (
        jax.random.normal(rng, (k, D, D), dtype=jnp.float32) * scale
    ).astype(cfg.dtype)
    return {"transfer": t}


def hidden_transfer_hidden(ht: Params, x: jax.Array, h: int) -> jax.Array:
    """Pseudo hidden state for future offset `h` (0-based head index):
    x [..., D] -> x + x @ T_h. The caller runs _logits on the result."""
    return x + _dense(x, ht["transfer"][h], "...d,de->...e")


def hidden_transfer_logits(
    params: Params, cfg: LlamaConfig, ht: Params, x: jax.Array
) -> jax.Array:
    """All heads' logits from one hidden state: x [..., D] ->
    [..., k, V]. Training (train/hidden.py) and the fused verify+propose
    program (spec/hidden.py) share this exact math."""
    xs = jnp.stack(
        [
            hidden_transfer_hidden(ht, x, h)
            for h in range(ht["transfer"].shape[0])
        ],
        axis=-2,
    )  # [..., k, D]
    return _logits(params, cfg, xs)


def param_count(params: Params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))
