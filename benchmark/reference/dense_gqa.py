"""Plain reference for the dense grouped-query decoder both configurations are
(InternLM2.5: RMSNorm, rotary positions in the half-split layout, grouped
query attention, SwiGLU, no biases, untied output head), in straightforward
jax.numpy and float32 at `highest` matmul precision. No kernels, no cache,
no batching tricks, and nothing imported from the program.

It also holds what the comparison needs beside the forward pass: the same
seeded draws as the served model's one jitted init (so the reference makes
its own weights and takes none), and the control: the same forward with
every matrix multiplication of the layers and the head computed in int8
(weights rounded per output channel, activations per token, as an int8
serving path does to use the chip's int8 peak); attention scores, softmax
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

BLOCK = 512  # query rows per attention block


# ------------------------------------------------------------------ weights
def init_weights(conf: dict, seed: int):
    """bfloat16 weights from the seed: ten keys split from PRNGKey(seed);
    normal draws in float32 scaled by 1/sqrt(fan_in) (0.02 for the
    embedding), cast to bfloat16; norms at one. One jitted program, as the
    served model's init is, so the draws round alike."""
    D, L = conf["hidden_size"], conf["num_hidden_layers"]
    hd, nq, nkv = conf["head_dim"], conf["num_attention_heads"], conf["num_key_value_heads"]
    F, V = conf["intermediate_size"], conf["vocab_size"]

    def make(key):
        keys = jax.random.split(key, 10)

        def dense(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) * fan_in**-0.5).astype(jnp.bfloat16)

        return {
            "embed": (jax.random.normal(keys[0], (V, D), dtype=jnp.float32) * 0.02).astype(jnp.bfloat16),
            "final_norm": jnp.ones((D,), jnp.bfloat16),
            "layers": {
                "attn_norm": jnp.ones((L, D), jnp.bfloat16),
                "wq": dense(keys[1], (L, D, nq * hd), D),
                "wk": dense(keys[2], (L, D, nkv * hd), D),
                "wv": dense(keys[3], (L, D, nkv * hd), D),
                "wo": dense(keys[4], (L, nq * hd, D), nq * hd),
                "mlp_norm": jnp.ones((L, D), jnp.bfloat16),
                "w_gate": dense(keys[5], (L, D, F), D),
                "w_up": dense(keys[6], (L, D, F), D),
                "w_down": dense(keys[7], (L, F, D), F),
            },
            "lm_head": dense(keys[8], (D, V), D),
        }

    if conf["tie_word_embeddings"]:
        raise ValueError("the reference is written for an untied output head")
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
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer(x, lw, positions, seg, dims, mode):
    """One decoder layer over the whole tree-shaped sequence [T, D]."""
    nq, nkv, hd, eps, theta = dims
    act = jnp.float32 if mode == "f32" else jnp.bfloat16
    prec = "highest" if mode == "f32" else "default"

    def w(name):
        return lw[name].astype(jnp.float32) if mode == "f32" else lw[name]

    def mm(a, b):
        if mode != "f32":
            return _int8_matmul(a, b)
        return jnp.matmul(a, b, precision=prec, preferred_element_type=jnp.float32)

    T = x.shape[0]
    h = _rms_norm(x, lw["attn_norm"], eps)
    q = _rope(mm(h, w("wq")).reshape(T, nq, hd), positions, theta)
    k = _rope(mm(h, w("wk")).reshape(T, nkv, hd), positions, theta)
    v = mm(h, w("wv")).reshape(T, nkv, hd)
    g = nq // nkv
    idx = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, BLOCK, 0).reshape(BLOCK, nkv, g, hd)
        qi = start + jnp.arange(BLOCK)
        sq = jax.lax.dynamic_slice_in_dim(seg, start, BLOCK, 0)
        s = jnp.einsum("qkgd,tkd->kgqt", qb.astype(act), k.astype(act), precision=prec,
                       preferred_element_type=jnp.float32) * hd**-0.5
        ok = (idx[None, :] <= qi[:, None]) & (seg[None, :] >= 0) & (
            (seg[None, :] == 0) | (seg[None, :] == sq[:, None]))
        s = jnp.where(ok[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p.astype(act), v.astype(act), precision=prec,
                       preferred_element_type=jnp.float32)
        return o.reshape(BLOCK, nq * hd)

    attn = jax.lax.map(block, jnp.arange(0, T, BLOCK)).reshape(T, nq * hd)
    x = x + mm(attn, w("wo"))
    h = _rms_norm(x, lw["mlp_norm"], eps)
    x = x + mm(jax.nn.silu(mm(h, w("w_gate"))) * mm(h, w("w_up")), w("w_down"))
    return x


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
    dims = (conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"],
            conf["rms_norm_eps"], conf["rope_theta"])
    emb = weights["embed"][toks]
    x = emb.astype(jnp.float32) if mode == "f32" else _int8_round(weights["embed"])[toks]
    for i in range(conf["num_hidden_layers"]):
        lw = {k: v[i] for k, v in weights["layers"].items()}
        x = _layer(x, lw, pos, seg, dims, mode)
    return np.asarray(_head(x[jnp.asarray(rows)], weights["final_norm"], weights["lm_head"],
                            conf["rms_norm_eps"], mode, vocab_rows))
