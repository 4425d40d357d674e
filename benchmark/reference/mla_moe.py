"""Plain reference for latent attention (MLA) with a sigmoid-routed
sparse-expert feed-forward behind leading dense layers (`glm4_moe_lite`:
GLM-4.7-Flash), in straightforward jax.numpy and float32 at `highest`
matmul precision. No kernels, no cache, no absorbed products, no grouping,
and nothing imported from the program or from harness/.

THE LAYER EQUATIONS (x: the residual stream; RMSNorm eps `rms_norm_eps`;
H heads; ranks dq = q_lora_rank, dc = kv_lora_rank; head widths dn =
qk_nope_head_dim, dr = qk_rope_head_dim, dv = v_head_dim).

- Attention, every layer: h = RMSNorm(x); c_q = RMSNorm(h W_dq);
  [q_nope | q_rope] = c_q W_uq as H x (dn + dr); [c_kv | k_r] = h W_dkv;
  c_kv = RMSNorm(c_kv); q_rope and k_r rotated at the token's position
  (`rope_theta` over the dr rope dims, half-split layout, no scaling; k_r
  one vector shared by all heads); [k_nope | v] = c_kv W_ukv as H x (dn +
  dv), WRITTEN OUT for every token; score = (q_nope . k_nope + q_rope .
  k_r) / sqrt(dn + dr); causal softmax in float32; o = sum p v; x += o W_o.
- Dense feed-forward (the first `first_k_dense_replace` layers): SwiGLU of
  width `intermediate_size`.
- Expert feed-forward (the rest): h = RMSNorm(x); logits = h W_g in float32
  [n_routed_experts]; s = sigmoid(logits); the `num_experts_per_tok`
  experts with the largest s + b (b: `e_score_correction_bias`; n_group =
  topk_group = 1: no group limit); weights s_e / sum over the selected s
  (the bias does not enter), times `routed_scaling_factor`; output
  sum_e w_e SwiGLU_e(h) (width `moe_intermediate_size`) + SwiGLU_shared(h).
  Written as A LOOP OVER THE EXPERTS, each over every token with the
  unselected tokens' weight at zero, so no [T, experts, width] array is
  ever alive. No capacity, no dropped token.
- Head: final RMSNorm, untied output head.
- Left out: the multi-token-prediction module (`num_nextn_predict_layers`),
  a draft head the main model's logits do not depend on.

It also holds what the comparison needs beside the forward pass: the same
seeded draws as the served model's one jitted init (so the reference makes
its own weights and takes none), and the control, mode "int8": the same
forward with every matrix multiplication of the layers (the projections,
every expert, the shared expert) and of the head computed in int8 (weights
rounded per output channel, activations per token), as
reference/dense_gqa.py does it. THE ROUTER STAYS IN FLOAT32 in the control
too, as an int8 serving path leaves it (64 outputs: nothing to gain, and
its scores decide which weights a token meets); attention scores, softmax
and norms stay in bfloat16 / float32.

One forward covers a whole wave: the shared prompt prefix followed by each
row's tail (pod suffix + served tokens). A tail sees the prefix and itself,
at the positions it had when served, so the result equals running each
prompt alone; the prefix is computed once instead of once per row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512        # query rows per attention block
BIAS_SCALE = 0.02  # std of the drawn selection bias


def _dims(conf: dict) -> tuple:
    return (conf["num_attention_heads"], conf["q_lora_rank"], conf["kv_lora_rank"],
            conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"],
            conf["num_experts_per_tok"], conf["norm_topk_prob"], conf["routed_scaling_factor"],
            conf["rms_norm_eps"], float(conf["rope_theta"]))


# ------------------------------------------------------------------ weights
def init_weights(conf: dict, seed: int):
    """bfloat16 weights from the seed, drawn as the served model's init
    draws them: PRNGKey(seed) split in four (embedding, head, dense stack,
    expert stack), the dense stack's key in 8 and the expert stack's in 13,
    one key a leaf; normal draws in float32 scaled by 1/sqrt(fan_in) (1
    for the embedding), cast to bfloat16; norms at one; the router's
    selection bias normal x 0.02 in float32; the three routed-expert leaves
    drawn a layer at a time from their key split by layer. One jitted
    program, as the served model's init is, so the draws round alike."""
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    dq, dc, dr = conf["q_lora_rank"], conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    dn, dv = conf["qk_nope_head_dim"], conf["v_head_dim"]
    Ld = conf["first_k_dense_replace"]
    Lm = conf["num_hidden_layers"] - Ld
    E, Fe, F, V = conf["n_routed_experts"], conf["moe_intermediate_size"], conf["intermediate_size"], conf["vocab_size"]
    Fs = conf["n_shared_experts"] * Fe
    if conf["tie_word_embeddings"]:
        raise ValueError("the reference is written for an untied output head")

    def make(key):
        k_embed, k_head, k_dense, k_moe = jax.random.split(key, 4)

        def dense(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) * fan_in**-0.5).astype(jnp.bfloat16)

        def ones(shape):
            return jnp.ones(shape, jnp.bfloat16)

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

        def experts(k, shape, fan_in):
            return jax.lax.map(lambda kl: dense(kl, shape, fan_in), jax.random.split(k, Lm))

        kd, km = jax.random.split(k_dense, 8), jax.random.split(k_moe, 13)
        return {
            "embed": (jax.random.normal(k_embed, (V, D), dtype=jnp.float32)).astype(jnp.bfloat16),
            "final_norm": ones((D,)),
            "lm_head": dense(k_head, (D, V), D),
            "dense_layers": {
                **attention(kd, Ld),
                "w_gate": dense(kd[5], (Ld, D, F), D),
                "w_up": dense(kd[6], (Ld, D, F), D),
                "w_down": dense(kd[7], (Ld, F, D), F),
            },
            "moe_layers": {
                **attention(km, Lm),
                "router": dense(km[5], (Lm, D, E), D),
                "router_bias": jax.random.normal(km[6], (Lm, E), dtype=jnp.float32) * BIAS_SCALE,
                "we_gate": experts(km[7], (E, D, Fe), D),
                "we_up": experts(km[8], (E, D, Fe), D),
                "we_down": experts(km[9], (E, Fe, D), Fe),
                "ws_gate": dense(km[10], (Lm, D, Fs), D),
                "ws_up": dense(km[11], (Lm, D, Fs), D),
                "ws_down": dense(km[12], (Lm, Fs, D), Fs),
            },
        }

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2**31 - 1)))


def _int8(x, axis):
    """Symmetric int8 along `axis`: (integers in [-127, 127] as float32, scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(xf / scale), -127, 127), scale


def _int8_round(w):
    """Weights as an int8 path keeps them: rounded per output channel."""
    q, scale = _int8(w, -2)
    return q * scale


def _int8_matmul(a, w):
    """a @ w with both operands in int8: activations rounded per row (token),
    weights per output channel; the integer products are exact in bfloat16
    operands with float32 accumulation."""
    aq, sa = _int8(a, -1)
    wq, sw = _int8(w, -2)
    return jnp.matmul(aq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32) * sa * sw


# ------------------------------------------------------------------ forward
def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [T, n, dr] rotated at positions [T], half-split layout."""
    dr = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _mm(mode):
    if mode == "f32":
        return lambda a, b: jnp.matmul(a, b.astype(jnp.float32), precision="highest",
                                       preferred_element_type=jnp.float32)
    return _int8_matmul


def _swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _attention(x, lw, positions, seg, dims, mode):
    """x + attention(x) over the whole tree-shaped sequence [T, D]: K and V
    written out per head for every token."""
    H, _dq, dc, dn, dr, dv, _k, _norm, _scale, eps, theta = dims
    act = jnp.float32 if mode == "f32" else jnp.bfloat16
    prec = "highest" if mode == "f32" else "default"
    mm = _mm(mode)
    T = x.shape[0]
    h = _rms_norm(x, lw["attn_norm"], eps)
    c_q = _rms_norm(mm(h, lw["w_dq"]), lw["q_norm"], eps)
    q = mm(c_q, lw["w_uq"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], positions, theta)
    kv = mm(h, lw["w_dkv"])
    c_kv = _rms_norm(kv[:, :dc], lw["kv_norm"], eps)
    k_r = _rope(kv[:, None, dc:], positions, theta)          # [T, 1, dr]
    kv_up = mm(c_kv, lw["w_ukv"]).reshape(T, H, dn + dv)
    k = jnp.concatenate([kv_up[..., :dn], jnp.broadcast_to(k_r, (T, H, dr))], axis=-1)
    v = kv_up[..., dn:]
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    idx = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qq, start, BLOCK, 0)
        qi = start + jnp.arange(BLOCK)
        sq = jax.lax.dynamic_slice_in_dim(seg, start, BLOCK, 0)
        s = jnp.einsum("qhd,thd->hqt", qb.astype(act), k.astype(act), precision=prec,
                       preferred_element_type=jnp.float32) * (dn + dr) ** -0.5
        ok = (idx[None, :] <= qi[:, None]) & (seg[None, :] >= 0) & (
            (seg[None, :] == 0) | (seg[None, :] == sq[:, None]))
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        o = jnp.einsum("hqt,thd->qhd", p.astype(act), v.astype(act), precision=prec,
                       preferred_element_type=jnp.float32)
        return o.reshape(BLOCK, H * dv)

    attn = jax.lax.map(block, jnp.arange(0, T, BLOCK)).reshape(T, H * dv)
    return x + mm(attn, lw["wo"])


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _dense_ffn(x, lw, dims, mode):
    h = _rms_norm(x, lw["mlp_norm"], dims[9])
    return x + _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], _mm(mode))


def route(h, router, bias, k: int, norm: bool, scale: float):
    """Token -> expert weights [T, E] (zero where not selected): sigmoid
    scores in float32; the k largest of score + bias are selected; the
    weights are the selected SCORES (no bias), renormalised, times scale."""
    logits = jnp.matmul(h.astype(jnp.float32), router.astype(jnp.float32), precision="highest")
    s = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(s + bias, k)
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], sel].set(1.0)
    w = s * chosen
    if norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return w * scale


@functools.partial(jax.jit, static_argnames=("dims", "mode", "first"))
def _expert_ffn(x, lw, dims, mode, first=0):
    """x + the routed experts held (experts `first ..` of the router's)
    + the shared expert. One expert at a time over every token."""
    _H, _dq, _dc, _dn, _dr, _dv, k, norm, scale, eps, _theta = dims
    mm = _mm(mode)
    h = _rms_norm(x, lw["mlp_norm"], eps)
    w = route(h, lw["router"], lw["router_bias"], k, norm, scale)
    held = lw["we_gate"].shape[0]
    w_held = jax.lax.dynamic_slice_in_dim(w, first, held, axis=1)

    def one(y, inp):
        wg, wu, wd, w_e = inp
        return y + w_e[:, None] * _swiglu(h, wg, wu, wd, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (lw["we_gate"], lw["we_up"], lw["we_down"], w_held.T))
    return x + y + _swiglu(h, lw["ws_gate"], lw["ws_up"], lw["ws_down"], mm)


@functools.partial(jax.jit, static_argnames=("eps", "mode", "vocab_rows"))
def _head(x_rows, final_norm, lm_head, eps, mode, vocab_rows):
    """Logits of the rows in x_rows over the first `vocab_rows` ids (the
    tokenizer's: no served token and no grammar token lies above them)."""
    h = _rms_norm(x_rows, final_norm, eps)
    wh = lm_head[:, :vocab_rows]
    if mode == "f32":
        return jnp.matmul(h, wh.astype(jnp.float32), precision="highest")
    return _int8_matmul(h, wh)


def wave_logits(conf: dict, weights, prefix_ids, tails, pred_spans, mode: str, vocab_rows: int):
    """Logits [N, vocab_rows] at every position of the wave that predicts a
    served token. `tails[r]` is row r's suffix + served token ids;
    `pred_spans[r]` = (first, count): the tail-relative index of the token
    that predicts the first served token, and how many served tokens."""
    P = len(prefix_ids)
    toks, pos, seg, rows = list(prefix_ids), list(range(P)), [0] * P, []
    for r, tail in enumerate(tails):
        first, count = pred_spans[r]
        rows.extend(len(toks) + first + j for j in range(count))
        toks.extend(tail)
        pos.extend(range(P, P + len(tail)))
        seg.extend([r + 1] * len(tail))
    T = -(-len(toks) // 2048) * 2048  # few distinct lengths: few programs
    pad = T - len(toks)
    toks, pos, seg = toks + [0] * pad, pos + [0] * pad, seg + [-1] * pad
    toks, pos, seg = jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32), jnp.asarray(seg, jnp.int32)
    dims = _dims(conf)
    emb = weights["embed"][toks]
    x = emb.astype(jnp.float32) if mode == "f32" else _int8_round(weights["embed"])[toks]
    n_dense = conf["first_k_dense_replace"]
    for i in range(conf["num_hidden_layers"]):
        stack, j = ("dense_layers", i) if i < n_dense else ("moe_layers", i - n_dense)
        lw = {k: v[j] for k, v in weights[stack].items()}
        x = _attention(x, lw, pos, seg, dims, mode)
        x = _dense_ffn(x, lw, dims, mode) if i < n_dense else _expert_ffn(x, lw, dims, mode)
    return np.asarray(_head(x[jnp.asarray(rows)], weights["final_norm"], weights["lm_head"],
                            conf["rms_norm_eps"], mode, vocab_rows))
