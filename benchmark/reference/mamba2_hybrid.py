"""Plain reference for the Mamba-2 / attention hybrid over dense SwiGLUs
(`granitemoehybrid` with no experts: Granite 4.0-H Micro), the whole model,
in straightforward jax.numpy and float32 at `highest` matmul precision. No
kernels, no cache, no chunking, and nothing imported from the program or
harness/.

THE LAYER EQUATIONS (x: the residual stream; every norm is rms(u) = u
rsqrt(mean u^2 + `rms_norm_eps`) w with a plain weight; D = hidden_size; m =
`residual_multiplier`).

- Embedding: x0 = `embedding_multiplier` E[token].
- Layer i: h = x + m mixer_i(rms(x)); out = h + m W_down(silu(u W_gate) *
  u W_up), u = rms(h), width `shared_intermediate_size` (W_in holds [gate |
  up]). mixer_i is the attention where `layer_types[i]` is "attention",
  else the Mamba-2 mixer.
- Mamba-2 mixer (H = `mamba_n_heads` heads of P = `mamba_d_head`, state
  width N = `mamba_d_state`, one group of B and C, inner width H P): [z |
  xBC | dt] = u W_in, no bias; xBC <- silu(conv(xBC) + b_conv), causal,
  depthwise over the H P + 2 N channels, `mamba_d_conv` taps, the last
  meeting the token itself; split xBC into x [H, P], B [N], C [N]; dt <-
  softplus(dt + dt_bias), A = -exp(A_log), a head each. THE RECURRENCE,
  TOKEN BY TOKEN (a `lax.scan` over the sequence): S <- e^{dt A} S + dt x
  B^T, S [P, N] a head; y = S C + D x. Then y <- rms(y silu(z)) over the
  whole inner width, W_out.
- Attention (H = `num_attention_heads`, Hkv = `num_key_value_heads`, hd =
  D / H): W_q, W_k, W_v, W_o, no bias, no position encoding, causal softmax
  of q k `attention_multiplier`, H / Hkv query heads a KV head.
- Head: rms(x_L) E^T / `logits_scaling`, the embedding table tied.

One forward covers a whole wave: the shared prompt prefix once, from an
empty state; then each row's tail (pod suffix + served tokens) from the
state the prefix left (S and the convolution's last inputs), attending the
prefix's keys and values and its own. The control, mode "int8": every matrix
multiplication of the layers (projections, SwiGLUs) and of the head in int8
(weights rounded per output channel, activations per token); the
recurrence, the convolution, attention scores and norms stay in float32 /
bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256    # query rows per attention block of the prefix


def _sizes(conf: dict) -> dict:
    types = conf["layer_types"]
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    Hs, P, N = conf["mamba_n_heads"], conf["mamba_d_head"], conf["mamba_d_state"]
    return dict(D=D, L=len(types), La=types.count("attention"), Lm=types.count("mamba"),
                H=H, Hkv=conf["num_key_value_heads"], hd=D // H, F=conf["shared_intermediate_size"],
                Hs=Hs, P=P, N=N, inner=Hs * P, cw=Hs * P + 2 * N, taps=conf["mamba_d_conv"],
                V=conf["vocab_size"])


# ------------------------------------------------------------------ weights
def init_weights(conf: dict, seed: int):
    """bfloat16 weights from the seed, drawn as the served model's init draws
    them: PRNGKey(seed) split in four (embedding, what every layer has, the
    Mamba-2 mixers, the attentions), those keys in 2, 6 and 4, one a leaf;
    normal in float32 scaled by 1/sqrt(fan_in) (the tied table by 0.02, the
    convolution by 1/sqrt(taps)), cast to
    bfloat16; the convolution's bias uniform on +-1/sqrt(taps); norms at
    one; D at one; dt log-uniform on (1e-3, 1e-1) and dt_bias its inverse
    softplus, A uniform on (1, 16) and A_log its log, all three float32. One
    jitted program, as the served model's init is, so the draws round
    alike."""
    z = _sizes(conf)
    D, L, Lm, La, F = z["D"], z["L"], z["Lm"], z["La"], z["F"]

    def make(key):
        k_embed, k_layers, k_ssm, k_attn = jax.random.split(key, 4)

        def dense(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) * fan_in**-0.5).astype(jnp.bfloat16)

        def ones(shape):
            return jnp.ones(shape, jnp.bfloat16)

        kl, ks, ka = jax.random.split(k_layers, 2), jax.random.split(k_ssm, 6), jax.random.split(k_attn, 4)
        dt = jnp.exp(jax.random.uniform(ks[3], (Lm, z["Hs"]), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        a = jax.random.uniform(ks[4], (Lm, z["Hs"]), jnp.float32, 1.0, 16.0)
        return {
            "embed": (jax.random.normal(k_embed, (z["V"], D), dtype=jnp.float32) * 0.02).astype(jnp.bfloat16),
            "final_norm": ones((D,)),
            "layers": {
                "attn_norm": ones((L, D)),
                "mlp_norm": ones((L, D)),
                "w_in": dense(kl[0], (L, D, 2 * F), D),
                "w_out": dense(kl[1], (L, F, D), F),
            },
            "ssm": {
                "w_in": dense(ks[0], (Lm, D, z["inner"] + z["cw"] + z["Hs"]), D),
                "conv": dense(ks[1], (Lm, z["taps"], z["cw"]), z["taps"]),
                "conv_bias": (jax.random.uniform(ks[2], (Lm, z["cw"]), jnp.float32, -1.0, 1.0)
                              * z["taps"]**-0.5).astype(jnp.bfloat16),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(a),
                "D": jnp.ones((Lm, z["Hs"]), jnp.float32),
                "norm": ones((Lm, z["inner"])),
                "w_out": dense(ks[5], (Lm, z["inner"], D), z["inner"]),
            },
            "attn": {
                "wq": dense(ka[0], (La, D, D), D),
                "wk": dense(ka[1], (La, D, z["Hkv"] * z["hd"]), D),
                "wv": dense(ka[2], (La, D, z["Hkv"] * z["hd"]), D),
                "wo": dense(ka[3], (La, D, D), D),
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


def _mm(mode):
    if mode == "f32":
        return lambda a, b: jnp.matmul(a, b.astype(jnp.float32), precision="highest",
                                       preferred_element_type=jnp.float32)
    return _int8_matmul


# ------------------------------------------------------------------ forward
def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def ssd(x, dt, a, b, c, d, ok, s0):
    """THE RECURRENCE, token by token, for one sequence: x [T, H, P], dt
    [T, H], a and d [H], b and c [T, N], ok [T] (a token that is not there
    leaves the state alone), s0 [H, P, N]. Returns (y [T, H, P], the state
    after the last token that is there)."""

    def step(s, xs):
        x_t, dt_t, b_t, c_t, ok_t = xs
        s1 = s * jnp.exp(dt_t * a)[:, None, None] + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        y = jnp.einsum("hpn,n->hp", s1, c_t, precision="highest") + d[:, None] * x_t
        return jnp.where(ok_t, s1, s), y

    s, y = jax.lax.scan(step, s0, (x, dt, b, c, ok))
    return y, s


def _ssm_one(x, lw, ok, n_valid, s0, window, z, eps, mm):
    """The Mamba-2 mixer over ONE sequence x [T, D] that starts from the
    state (s0, window [taps - 1, channels]); `n_valid` of its tokens are
    there. Returns (mixer output [T, D], state and window after them)."""
    T = x.shape[0]
    Hs, P, N, inner, cw, taps = z["Hs"], z["P"], z["N"], z["inner"], z["cw"], z["taps"]
    u = _rms(x, lw["attn_norm"], eps)
    zxd = mm(u, lw["w_in"])
    gate, xbc, dt = zxd[:, :inner], zxd[:, inner: inner + cw], zxd[:, inner + cw:]
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    xx = jnp.concatenate([window, xbc], axis=0)
    conv = lw["conv"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(xx[j: j + T] * conv[j] for j in range(taps)) + lw["conv_bias"].astype(jnp.float32))
    xs, b, c = xbc[:, :inner].reshape(T, Hs, P), xbc[:, inner: inner + N], xbc[:, inner + N:]
    y, s = ssd(xs, dt, -jnp.exp(lw["A_log"]), b, c, lw["D"], ok, s0)
    y = _rms(y.reshape(T, inner) * jax.nn.silu(gate), lw["norm"], eps)
    return mm(y, lw["w_out"]), s, jax.lax.dynamic_slice_in_dim(xx, n_valid, taps - 1, 0)


@functools.partial(jax.jit, static_argnames=("mode", "eps", "zt", "mult"))
def _ssm_layer(x_pre, x_tails, lw, n_pre, mode, eps, zt, mult):
    """x + m mixer(x) for the prefix [P, D] from an empty state, then for
    every tail [R, T, D] from the state the prefix's `n_pre` tokens left."""
    z, mm = dict(zt), _mm(mode)
    P, T = x_pre.shape[0], x_tails.shape[1]
    s0 = jnp.zeros((z["Hs"], z["P"], z["N"]), jnp.float32)
    w0 = jnp.zeros((z["taps"] - 1, z["cw"]), jnp.float32)
    y_pre, s_pre, w_pre = _ssm_one(x_pre, lw, jnp.arange(P) < n_pre, n_pre, s0, w0, z, eps, mm)
    # every tail starts from the prefix's state; the rows side by side, the tokens one after another
    y_tails = jax.vmap(
        lambda x: _ssm_one(x, lw, jnp.ones((T,), bool), T, s_pre, w_pre, z, eps, mm)[0])(x_tails)
    return x_pre + mult * y_pre, x_tails + mult * y_tails


@functools.partial(jax.jit, static_argnames=("mode", "eps", "zt", "mult", "scale"))
def _attn_layer(x_pre, x_tails, lw, n_pre, mode, eps, zt, mult, scale):
    """x + m attention(x): the prefix causally over itself, every tail over
    the prefix's `n_pre` tokens and causally over itself."""
    z, mm = dict(zt), _mm(mode)
    H, Hkv, hd = z["H"], z["Hkv"], z["hd"]
    act = jnp.float32 if mode == "f32" else jnp.bfloat16
    prec = "highest" if mode == "f32" else "default"
    P, (R, T) = x_pre.shape[0], x_tails.shape[:2]

    def project(x):
        n = x.shape[0]
        u = _rms(x, lw["attn_norm"], eps)
        q = mm(u, lw["wq"]).reshape(n, Hkv, H // Hkv, hd)
        return q, mm(u, lw["wk"]).reshape(n, Hkv, hd), mm(u, lw["wv"]).reshape(n, Hkv, hd)

    def attend(q, keys, values, ok):
        """q [n, Hkv, G, hd] against keys [t, Hkv, hd]; ok [n, t]."""
        s = jnp.einsum("nkgd,tkd->kgnt", q.astype(act), keys.astype(act), precision=prec,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
        o = jnp.einsum("kgnt,tkd->nkgd", p.astype(act), values.astype(act), precision=prec,
                       preferred_element_type=jnp.float32)
        return mm(o.reshape(-1, H * hd), lw["wo"])

    q_p, k_p, v_p = project(x_pre)
    there = jnp.arange(P) < n_pre

    def pre_block(start):
        qi = start + jnp.arange(BLOCK)
        return attend(jax.lax.dynamic_slice_in_dim(q_p, start, BLOCK, 0), k_p, v_p,
                      (jnp.arange(P)[None, :] <= qi[:, None]) & there[None, :])

    y_pre = jax.lax.map(pre_block, jnp.arange(0, P, BLOCK)).reshape(P, -1)
    t = jnp.arange(T)
    ok_tail = jnp.concatenate([jnp.broadcast_to(there[None, :], (T, P)), t[None, :] <= t[:, None]], axis=1)

    def tail(x):
        q, k, v = project(x)
        return attend(q, jnp.concatenate([k_p, k]), jnp.concatenate([v_p, v]), ok_tail)

    return x_pre + mult * y_pre, x_tails + mult * jax.lax.map(tail, x_tails)


@functools.partial(jax.jit, static_argnames=("eps", "mode", "mult", "F"))
def _mlp_layer(x, w_in, w_out, norm, eps, mode, mult, F):
    """x + m SwiGLU(rms(x)) for x [T, D]."""
    mm = _mm(mode)
    gu = mm(_rms(x, norm, eps), w_in)
    return x + mult * mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], w_out)


@functools.partial(jax.jit, static_argnames=("eps", "mode", "vocab_rows", "scaling"))
def _head(x_rows, final_norm, embed, eps, mode, vocab_rows, scaling):
    """Logits of the rows in x_rows over the first `vocab_rows` ids (the
    tokenizer's: no served token and no grammar token lies above them),
    through the tied table."""
    h = _rms(x_rows, final_norm, eps)
    wh = embed[:vocab_rows].T
    if mode == "f32":
        return jnp.matmul(h, wh.astype(jnp.float32), precision="highest") / scaling
    return _int8_matmul(h, wh) / scaling


def forward(conf: dict, weights, x_pre, x_tails, n_pre, mode: str):
    """Every layer over the prefix's stream [P, D] and the tails' [R, T, D]."""
    z = _sizes(conf)
    zt, eps, mult = tuple(sorted(z.items())), conf["rms_norm_eps"], float(conf["residual_multiplier"])
    layers, ssm, attn = weights["layers"], weights["ssm"], weights["attn"]
    P, (R, T) = x_pre.shape[0], x_tails.shape[:2]
    n_ssm = n_attn = 0
    for l, kind in enumerate(conf["layer_types"]):
        if kind == "mamba":
            lw = {k: a[n_ssm] for k, a in ssm.items()}
            lw["attn_norm"] = layers["attn_norm"][l]
            x_pre, x_tails = _ssm_layer(x_pre, x_tails, lw, n_pre, mode, eps, zt, mult)
            n_ssm += 1
        else:
            lw = {k: a[n_attn] for k, a in attn.items()}
            lw["attn_norm"] = layers["attn_norm"][l]
            x_pre, x_tails = _attn_layer(x_pre, x_tails, lw, n_pre, mode, eps, zt, mult,
                                         float(conf["attention_multiplier"]))
            n_attn += 1
        flat = jnp.concatenate([x_pre, x_tails.reshape(R * T, -1)])
        flat = _mlp_layer(flat, layers["w_in"][l], layers["w_out"][l], layers["mlp_norm"][l], eps, mode,
                          mult, z["F"])
        x_pre, x_tails = flat[:P], flat[P:].reshape(R, T, -1)
    return x_pre, x_tails


def wave_logits(conf: dict, weights, prefix_ids, tails, pred_spans, mode: str, vocab_rows: int):
    """Logits [N, vocab_rows] at every position of the wave that predicts a
    served token. `tails[r]` is row r's suffix + served token ids;
    `pred_spans[r]` = (first, count): the tail-relative index of the token
    that predicts the first served token, and how many served tokens."""
    n_pre = len(prefix_ids)
    P = -(-n_pre // 1024) * 1024  # few distinct lengths: few programs
    T = -(-max(len(t) for t in tails) // 128) * 128
    pre = jnp.asarray(list(prefix_ids) + [0] * (P - n_pre), jnp.int32)
    tl = jnp.asarray([list(t) + [0] * (T - len(t)) for t in tails], jnp.int32)
    table = weights["embed"].astype(jnp.float32) if mode == "f32" else _int8_round(weights["embed"])
    table = table * float(conf["embedding_multiplier"])
    _, x_tails = forward(conf, weights, table[pre], table[tl], jnp.int32(n_pre), mode)
    rows = [(r, first + j) for r, (first, count) in enumerate(pred_spans) for j in range(count)]
    r_idx, t_idx = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    return np.asarray(_head(x_tails[r_idx, t_idx], weights["final_norm"], weights["embed"],
                            conf["rms_norm_eps"], mode, vocab_rows, float(conf["logits_scaling"])))
