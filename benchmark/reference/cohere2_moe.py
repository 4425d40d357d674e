"""Plain reference for window and global attention in parallel blocks over
sparse experts (`cohere2_moe`: Command A+ 218B-A25B), one chip's share of an
expert-parallel deployment, in straightforward jax.numpy and float32 at
`highest` matmul precision. No kernels, no cache, no grouping, and nothing
imported from the program or from harness/.

THE LAYER EQUATIONS (x: the residual stream; D = hidden_size; H =
num_attention_heads query heads and Hkv = num_key_value_heads key/value heads
of width hd = head_dim; W = sliding_window; bf16 weights, no biases).

- x0 = E[token] (E: the embedding table, tied to the head).
- Every layer: h = LN(x), Cohere's LayerNorm: (x - mean) / sqrt(var +
  layer_norm_eps) times a weight, no bias. Parallel block
  (`use_parallel_block`): x <- x + Attn(h) + FFN(h).
- Attn: q = h W_q [H, hd], k = h W_k, v = h W_v [Hkv, hd]; query head i
  reads key head i // (H / Hkv); score q . k / sqrt(hd); softmax in float32;
  o W_o. No q or k norm.
  - Window layers (`sliding_attention` in layer_types; three of every four,
    `local_attn_first`): q and k rotated at the token's position, theta
    `rope_theta` over all hd dims, in the HALF-SPLIT pairing (dims d and d +
    hd/2), a fixed permutation of the GPT-J pairing the published model uses
    (`position_embedding_type` rope_gptj; configs/command-a-plus-05-2026.json
    `assumed`); query at position i sees key at position j iff i - W < j <= i.
  - Global layers (`full_attention`): no position encoding, causal.
- FFN: s = sigmoid(h W_r) in float32 over all `num_experts` outputs; the
  `num_experts_per_tok` largest s (no selection bias); weights s_e / sum of
  the selected s (`norm_topk_prob`), no routed scaling. Routed part sum w_e
  SwiGLU_e(h) (width intermediate_size), written as A LOOP OVER THE EXPERTS
  HELD (`expert_first .. + experts_held`: the share's; the other experts'
  part is another chip's and is left out, here as in the program), each over
  every token with the unselected tokens' weight at zero. Shared part
  (1 / num_shared_experts) sum_j SwiGLU^s_j(h), the MEAN of the shared
  experts (`shared_expert_combination_strategy` "average"), computed as one
  SwiGLU of their widths side by side times 1 / num_shared_experts.
- Head: LN_f(x) E^T times `logit_scale`.

It also holds what the comparison needs beside the forward pass: the same
seeded draws as the served model's one jitted init, and the control, mode
"int8": the same forward with every matrix multiplication of the layers (the
projections, every routed and shared expert) and of the head in int8
(weights rounded per output channel, activations per token). THE ROUTER
STAYS IN FLOAT32 in the control too; attention scores, softmax and norms
stay in bfloat16 / float32.

One forward covers a whole wave: the shared prompt prefix followed by each
row's tail (pod suffix + served tokens), as reference/mla_moe.py does it.
The weights stay bfloat16 as drawn and are taken into float32 a sublayer,
and an expert, at a time: 9.5 GB of them lie beside the forward's arrays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 64          # query rows per attention block (128 heads: [8, 16, 64, T] scores)
EMBED_STD = 0.02    # the tied table's draw (configs/command-a-plus-05-2026.json `assumed`)
Q_GAIN = 2.5        # W_q's draw times this: the attention's logits' std (the same `assumed`)

ATTENTION = ("wq", "wk", "wv", "wo")
SHARED = ("ws_gate", "ws_up", "ws_down")


def _window_layers(conf: dict) -> list[bool]:
    """For each layer run, whether it is a window layer."""
    return [t == "sliding_attention" for t in conf["layer_types"][: conf["num_hidden_layers"]]]


def _dims(conf: dict) -> tuple:
    return (conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"],
            float(conf["rope_theta"]), conf["sliding_window"])


# ------------------------------------------------------------------ weights
def init_weights(conf: dict, seed: int):
    """bfloat16 weights from the seed, drawn as the served model's init draws
    them: PRNGKey(seed) split in two (embedding, layers), the layers' key in
    11, one key a leaf; every matrix drawn a layer at a time from its key
    split by layer, normal in float32 scaled by 1/sqrt(fan_in) (W_q times
    Q_GAIN: the attention's logits have std Q_GAIN), cast to bfloat16, W_q
    and W_k then kept by head, [L, heads, hd, D], as the program keeps them;
    the tied table normal x EMBED_STD; norms at one. The experts are the
    `experts_held` of the share; the shared experts are one stack of their
    widths side by side. One jitted program, as the served model's init is,
    so the draws round alike."""
    D, L, V = conf["hidden_size"], conf["num_hidden_layers"], conf["vocab_size"]
    H, Hkv, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    E, Fe = conf["experts_held"], conf["intermediate_size"]
    Fs = conf["num_shared_experts"] * Fe

    def make(key):
        k_embed, k_layers = jax.random.split(key)

        def dense(k, shape, fan_in, gain=1.0):
            return (jax.random.normal(k, shape, dtype=jnp.float32) * (gain * fan_in**-0.5)).astype(jnp.bfloat16)

        def stacked(k, shape, fan_in, gain=1.0):
            return jax.lax.map(lambda kl: dense(kl, shape, fan_in, gain), jax.random.split(k, L))

        k = jax.random.split(k_layers, 11)
        return {
            "embed": (jax.random.normal(k_embed, (V, D), dtype=jnp.float32) * EMBED_STD).astype(jnp.bfloat16),
            "final_norm": jnp.ones((D,), jnp.bfloat16),
            "layers": {
                "attn_norm": jnp.ones((L, D), jnp.bfloat16),
                "wq": jnp.swapaxes(stacked(k[0], (D, H * hd), D, Q_GAIN), 1, 2).reshape(L, H, hd, D),
                "wk": jnp.swapaxes(stacked(k[1], (D, Hkv * hd), D), 1, 2).reshape(L, Hkv, hd, D),
                "wv": stacked(k[2], (D, Hkv * hd), D),
                "wo": stacked(k[3], (H * hd, D), H * hd),
                "router": stacked(k[4], (D, conf["num_experts"]), D),
                "we_gate": stacked(k[5], (E, D, Fe), D),
                "we_up": stacked(k[6], (E, D, Fe), D),
                "we_down": stacked(k[7], (E, Fe, D), Fe),
                "ws_gate": stacked(k[8], (D, Fs), D),
                "ws_up": stacked(k[9], (D, Fs), D),
                "ws_down": stacked(k[10], (Fs, D), Fs),
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
def layer_norm(x, w, eps):
    """(x - mean) / sqrt(var + eps) w, no bias, in float32."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [T, n, hd] rotated at positions [T], half-split pairing."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
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


@functools.partial(jax.jit, static_argnames=("dims", "window", "mode"))
def _attention(h, lw, positions, seg, dims, window: bool, mode):
    """Attn(h) over the whole tree-shaped sequence [T, D] (without the
    stream): a token of segment r > 0 sees the prefix (segment 0) and its
    own segment, causally; a window layer (`window`) rotates q and k and
    sees the last W positions alone."""
    H, Hkv, hd, theta, W = dims
    act = jnp.float32 if mode == "f32" else jnp.bfloat16
    prec = "highest" if mode == "f32" else "default"
    mm = _mm(mode)
    T = h.shape[0]
    q = mm(h, lw["wq"].reshape(H * hd, -1).T).reshape(T, H, hd)
    k = mm(h, lw["wk"].reshape(Hkv * hd, -1).T).reshape(T, Hkv, hd)
    v = mm(h, lw["wv"]).reshape(T, Hkv, hd)
    if window:
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    q = q.reshape(T, Hkv, H // Hkv, hd)   # query head i reads key head i // (H / Hkv)
    idx = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, BLOCK, 0)
        qi = start + jnp.arange(BLOCK)
        sq = jax.lax.dynamic_slice_in_dim(seg, start, BLOCK, 0)
        pq = jax.lax.dynamic_slice_in_dim(positions, start, BLOCK, 0)
        s = jnp.einsum("qkgd,tkd->kgqt", qb.astype(act), k.astype(act), precision=prec,
                       preferred_element_type=jnp.float32) * hd**-0.5
        ok = (idx[None, :] <= qi[:, None]) & (seg[None, :] >= 0) & (
            (seg[None, :] == 0) | (seg[None, :] == sq[:, None]))
        if window:
            ok = ok & (pq[:, None] - positions[None, :] < W)
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p.astype(act), v.astype(act), precision=prec,
                       preferred_element_type=jnp.float32)
        return o.reshape(BLOCK, H * hd)

    attn = jax.lax.map(block, jnp.arange(0, T, BLOCK)).reshape(T, H * hd)
    return mm(attn, lw["wo"])


def route(h, router, k: int, norm: bool):
    """Token -> router-output weights [T, outputs] (zero where not selected):
    sigmoid scores in float32 over ALL the router's outputs; the k largest
    are selected; the weights are the selected scores, renormalised where
    `norm`."""
    s = jax.nn.sigmoid(jnp.matmul(h.astype(jnp.float32), router.astype(jnp.float32), precision="highest"))
    _, sel = jax.lax.top_k(s, k)
    w = s * jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], sel].set(1.0)
    if norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return w


@functools.partial(jax.jit, static_argnames=("k", "norm", "mode", "first"))
def _routed_ffn(h, router, we_gate, we_up, we_down, layer, k: int, norm: bool, mode, first=0):
    """The routed part for the normed tokens h (without the stream): the
    experts held (experts `first ..` of the router's; `we_*` are the WHOLE
    stacks [L, E, ..], read an expert at a time at `layer`), one expert at a
    time over every token."""
    mm = _mm(mode)
    w = route(h, router, k, norm)

    def one(e, y):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False), e, 0, keepdims=False)
            for a in (we_gate, we_up, we_down))
        w_e = jax.lax.dynamic_index_in_dim(w, first + e, 1, keepdims=False)
        return y + w_e[:, None] * _swiglu(h, wg, wu, wd, mm)

    return jax.lax.fori_loop(0, we_gate.shape[1], one, jnp.zeros_like(h))


@functools.partial(jax.jit, static_argnames=("scale", "mode"))
def _shared_ffn(h, lw, scale: float, mode):
    """The shared experts' mean for the normed tokens h (without the stream)."""
    return _swiglu(h, lw["ws_gate"], lw["ws_up"], lw["ws_down"], _mm(mode)) * scale


@functools.partial(jax.jit, static_argnames=("eps", "scale", "mode", "vocab_rows"))
def _head(x_rows, final_norm, embed, eps, scale, mode, vocab_rows):
    """Logits of the rows in x_rows over the first `vocab_rows` ids (the
    tokenizer's: no served token and no grammar token lies above them),
    through the tied table."""
    h = layer_norm(x_rows, final_norm, eps)
    wh = embed[:vocab_rows].T
    if mode == "f32":
        return jnp.matmul(h, wh.astype(jnp.float32), precision="highest") * scale
    return _int8_matmul(h, wh) * scale


def layer(conf: dict, layers: dict, l: int, x, pos, seg, mode: str, routed: bool = True, shared: bool = True):
    """One parallel block over the stream x [T, D]. `routed` / `shared`
    leave out a part (the share test adds the shares' routed parts and the
    shared part once)."""
    eps = conf["layer_norm_eps"]
    h = layer_norm(x, layers["attn_norm"][l], eps)
    y = _attention(h, {k: layers[k][l] for k in ATTENTION}, pos, seg, _dims(conf),
                   _window_layers(conf)[l], mode)
    if routed:
        y = y + _routed_ffn(h, layers["router"][l], layers["we_gate"], layers["we_up"], layers["we_down"],
                            jnp.int32(l), conf["num_experts_per_tok"], conf["norm_topk_prob"], mode,
                            first=conf["expert_first"])
    if shared:
        y = y + _shared_ffn(h, {k: layers[k][l] for k in SHARED}, 1.0 / conf["num_shared_experts"], mode)
    return x + y


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
    x = weights["embed"][toks].astype(jnp.float32) if mode == "f32" else _int8_round(weights["embed"])[toks]
    for l in range(conf["num_hidden_layers"]):
        x = layer(conf, weights["layers"], l, x, pos, seg, mode)
    return np.asarray(_head(x[jnp.asarray(rows)], weights["final_norm"], weights["embed"],
                            conf["layer_norm_eps"], float(conf["logit_scale"]), mode, vocab_rows))
