"""Plain reference for shortcut-connected double layers over latent attention
(`longcat_flash`: LongCat-Flash-Chat), one chip's share of an expert-parallel
deployment, in straightforward jax.numpy and float32 at `highest` matmul
precision. No kernels, no cache, no absorbed products, no grouping, and
nothing imported from the program or from harness/.

THE LAYER EQUATIONS (x: the residual stream; RMSNorm eps `rms_norm_eps`; H
heads; D = hidden_size; ranks dq = q_lora_rank, dc = kv_lora_rank; head
widths dn = qk_nope_head_dim, dr = qk_rope_head_dim, dv = v_head_dim).

- Latent attention A_j (j = 0, 1; weights of its own each): h = RMSNorm(x);
  c_q = RMSNorm(h W_dq); [q_nope | q_rope] = c_q W_uq as H x (dn + dr),
  BOTH TIMES sqrt(D / dq) (`mla_scale_q_lora`); [c_kv | k_r] = h W_dkv; c_kv
  = RMSNorm(c_kv) TIMES sqrt(D / dc) (`mla_scale_kv_lora`; k_r is not
  scaled); q_rope and k_r rotated at the token's position (`rope_theta` over
  the dr rope dims, half-split layout, no scaling; k_r one vector shared by
  all heads); [k_nope | v] = c_kv W_ukv as H x (dn + dv), WRITTEN OUT for
  every token; score = (q_nope . k_nope + q_rope . k_r) / sqrt(dn + dr);
  causal softmax in float32; o = sum p v; x += o W_o.
- Dense feed-forward F_j: SwiGLU of width `ffn_hidden_size` on RMSNorm(x),
  the post-attention norm of its sublayer.
- Routed feed-forward M on h = RMSNorm(x) with the FIRST sublayer's
  post-attention norm (the stream F_0 reads): logits = h W_g in float32
  [n_routed_experts + zero_expert_num]; s = softmax(logits); the `moe_topk`
  outputs with the largest s + b (b: `e_score_correction_bias`, selection
  only); weights `routed_scaling_factor` x s_e, NOT renormalised
  (`norm_topk_prob` false); output sum_{e < n_routed_experts} w_e
  SwiGLU_e(h) (width `expert_ffn_hidden_size`) + sum_{e >= n_routed_experts}
  w_e h: the identity experts return their input. Written as A LOOP OVER THE
  EXPERTS HELD (`expert_first .. + experts_held`: the share's; the other
  experts' part is another chip's and is left out, here as in the program),
  each over every token with the unselected tokens' weight at zero. No
  shared expert, no capacity, no dropped token.
- One layer, in order: x1 = x + A_0(x); m = M(x1); x2 = x1 + F_0(x1); x3 =
  x2 + A_1(x2); x4 = x3 + F_1(x3) + m.
- Head: final RMSNorm, untied output head over the `vocab_size` rows held.
- Left out: the multi-token-prediction module.

It also holds what the comparison needs beside the forward pass: the same
seeded draws as the served model's one jitted init, and the control, mode
"int8": the same forward with every matrix multiplication of the layers (the
projections, both dense feed-forwards, every expert) and of the head in int8
(weights rounded per output channel, activations per token). THE ROUTER
STAYS IN FLOAT32 in the control too; the identity experts multiply nothing;
attention scores, softmax and norms stay in bfloat16 / float32.

One forward covers a whole wave: the shared prompt prefix followed by each
row's tail (pod suffix + served tokens), as reference/mla_moe.py does it.
The weights stay bfloat16 as drawn and are taken into float32 a sublayer,
and an expert, at a time: 10 GB of them lie beside the forward's arrays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 128        # query rows per attention block (64 heads: [64, 128, T] scores)
BIAS_SCALE = 2e-4  # std of the drawn selection bias

ATTENTION = ("attn_norm", "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_ukv", "wo")
DENSE = ("mlp_norm", "w_gate", "w_up", "w_down")


def _dims(conf: dict) -> tuple:
    D = conf["hidden_size"]
    return (conf["num_attention_heads"], conf["kv_lora_rank"], conf["qk_nope_head_dim"],
            conf["qk_rope_head_dim"], conf["v_head_dim"],
            (D / conf["q_lora_rank"]) ** 0.5 if conf["mla_scale_q_lora"] else 1.0,
            (D / conf["kv_lora_rank"]) ** 0.5 if conf["mla_scale_kv_lora"] else 1.0,
            conf["rms_norm_eps"], float(conf["rope_theta"]))


def _route_dims(conf: dict) -> tuple:
    return (conf["moe_topk"], conf["norm_topk_prob"], float(conf["routed_scaling_factor"]),
            conf["n_routed_experts"], conf["rms_norm_eps"])


# ------------------------------------------------------------------ weights
def init_weights(conf: dict, seed: int):
    """bfloat16 weights from the seed, drawn as the served model's init draws
    them: PRNGKey(seed) split in three (embedding, head, layers), the
    layers' key in 13, one key a leaf; every matrix of the stack drawn a
    layer at a time from its key split by layer, normal in float32 scaled by
    1/sqrt(fan_in) (1 for the embedding; hidden_size for W_uq and W_ukv
    where their scale factor is on: the full-rank path the factor stands
    for), cast to bfloat16; norms at one; the router's selection bias normal
    x BIAS_SCALE in float32. Attention and
    dense leaves carry a sublayer axis of 2 behind the layer axis; the
    experts are the `experts_held` of the share. One jitted program, as the
    served model's init is, so the draws round alike."""
    D, H, L = conf["hidden_size"], conf["num_attention_heads"], conf["num_layers"]
    dq, dc, dr = conf["q_lora_rank"], conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    dn, dv = conf["qk_nope_head_dim"], conf["v_head_dim"]
    E, Fe, F, V = conf["experts_held"], conf["expert_ffn_hidden_size"], conf["ffn_hidden_size"], conf["vocab_size"]
    R = conf["n_routed_experts"] + conf["zero_expert_num"]

    def make(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)

        def dense(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) * fan_in**-0.5).astype(jnp.bfloat16)

        def stacked(k, shape, fan_in):
            return jax.lax.map(lambda kl: dense(kl, shape, fan_in), jax.random.split(k, L))

        def ones(shape):
            return jnp.ones(shape, jnp.bfloat16)

        k = jax.random.split(k_layers, 13)
        fan_uq = D if conf["mla_scale_q_lora"] else dq
        fan_ukv = D if conf["mla_scale_kv_lora"] else dc
        return {
            "embed": (jax.random.normal(k_embed, (V, D), dtype=jnp.float32)).astype(jnp.bfloat16),
            "final_norm": ones((D,)),
            "lm_head": dense(k_head, (D, V), D),
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
                "router": stacked(k[8], (D, R), D),
                "router_bias": jax.random.normal(k[9], (L, R), dtype=jnp.float32) * BIAS_SCALE,
                "we_gate": stacked(k[10], (E, D, Fe), D),
                "we_up": stacked(k[11], (E, D, Fe), D),
                "we_down": stacked(k[12], (E, Fe, D), Fe),
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
    """x + A(x) over the whole tree-shaped sequence [T, D]: K and V written
    out per head for every token, both scale factors where the published
    layer has them."""
    H, dc, dn, dr, dv, q_scale, kv_scale, eps, theta = dims
    act = jnp.float32 if mode == "f32" else jnp.bfloat16
    prec = "highest" if mode == "f32" else "default"
    mm = _mm(mode)
    T = x.shape[0]
    h = _rms_norm(x, lw["attn_norm"], eps)
    c_q = _rms_norm(mm(h, lw["w_dq"]), lw["q_norm"], eps)
    q = (mm(c_q, lw["w_uq"]) * q_scale).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], positions, theta)
    kv = mm(h, lw["w_dkv"])
    c_kv = _rms_norm(kv[:, :dc], lw["kv_norm"], eps) * kv_scale
    k_r = _rope(kv[:, None, dc:], positions, theta)          # [T, 1, dr], not scaled
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


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _dense_ffn(x, lw, eps, mode):
    """F(x), without the stream."""
    h = _rms_norm(x, lw["mlp_norm"], eps)
    return _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], _mm(mode))


def route(h, router, bias, k: int, norm: bool, scale: float):
    """Token -> router-output weights [T, outputs] (zero where not selected):
    softmax scores in float32 over ALL the router's outputs; the k largest of
    score + bias are selected; the weights are the selected SCORES (no bias),
    renormalised only where `norm`, times scale."""
    logits = jnp.matmul(h.astype(jnp.float32), router.astype(jnp.float32), precision="highest")
    s = jax.nn.softmax(logits, axis=-1)
    _, sel = jax.lax.top_k(s + bias, k)
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], sel].set(1.0)
    w = s * chosen
    if norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return w * scale


@functools.partial(jax.jit, static_argnames=("rdims", "mode", "first", "zero"))
def _routed_ffn(x, norm_w, router, bias, we_gate, we_up, we_down, layer, rdims, mode, first=0,
                zero=True):
    """M(x), without the stream: the feed-forward experts held (experts
    `first ..` of the router's; `we_*` are the WHOLE stacks [L, E, ..], read
    an expert at a time at `layer`) + the identity experts (`zero`: a share
    test counts them once). One expert at a time over every token."""
    k, norm, scale, n_ffn, eps = rdims
    mm = _mm(mode)
    h = _rms_norm(x, norm_w, eps)
    w = route(h, router, bias, k, norm, scale)
    held = we_gate.shape[1]

    def one(e, y):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False), e, 0, keepdims=False)
            for a in (we_gate, we_up, we_down))
        w_e = jax.lax.dynamic_index_in_dim(w, first + e, 1, keepdims=False)
        return y + w_e[:, None] * _swiglu(h, wg, wu, wd, mm)

    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(h))
    if zero:
        y = y + h * jnp.sum(w[:, n_ffn:], axis=1, keepdims=True)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "mode", "vocab_rows"))
def _head(x_rows, final_norm, lm_head, eps, mode, vocab_rows):
    """Logits of the rows in x_rows over the first `vocab_rows` ids (the
    tokenizer's: no served token and no grammar token lies above them)."""
    h = _rms_norm(x_rows, final_norm, eps)
    wh = lm_head[:, :vocab_rows]
    if mode == "f32":
        return jnp.matmul(h, wh.astype(jnp.float32), precision="highest")
    return _int8_matmul(h, wh)


def layer(conf: dict, layers: dict, l: int, x, pos, seg, mode: str):
    """One double layer over the stream x [T, D]: m is made of x1 and joins
    at the end."""
    dims, rdims, eps = _dims(conf), _route_dims(conf), conf["rms_norm_eps"]
    sub = lambda names, j: {k: layers[k][l, j] for k in names}  # noqa: E731
    x = _attention(x, sub(ATTENTION, 0), pos, seg, dims, mode)
    m = _routed_ffn(x, layers["mlp_norm"][l, 0], layers["router"][l], layers["router_bias"][l],
                    layers["we_gate"], layers["we_up"], layers["we_down"], jnp.int32(l),
                    rdims, mode, first=conf["expert_first"])
    x = x + _dense_ffn(x, sub(DENSE, 0), eps, mode)
    x = _attention(x, sub(ATTENTION, 1), pos, seg, dims, mode)
    return x + _dense_ffn(x, sub(DENSE, 1), eps, mode) + m


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
    emb = weights["embed"][toks]
    x = emb.astype(jnp.float32) if mode == "f32" else _int8_round(weights["embed"])[toks]
    for l in range(conf["num_layers"]):
        x = layer(conf, weights["layers"], l, x, pos, seg, mode)
    return np.asarray(_head(x[jnp.asarray(rows)], weights["final_norm"], weights["lm_head"],
                            conf["rms_norm_eps"], mode, vocab_rows))
