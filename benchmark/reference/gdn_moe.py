"""Plain reference for the gated-delta-rule / gated-attention hybrid over
sparse experts (`qwen3_next`: Qwen3-Next-80B-A3B), one chip's share of an
expert-parallel deployment, in straightforward jax.numpy and float32 at
`highest` matmul precision. No kernels, no cache, no chunking, no grouping,
and nothing imported from the program or from harness/.

THE LAYER EQUATIONS (x: the residual stream; every norm is x rsqrt(mean x^2
+ `rms_norm_eps`) (1 + w) except the mixer's gated norm; D = hidden_size).

- Layer i: h = x + mixer_i(norm(x)); out = h + moe(norm(h)). mixer_i is the
  gated attention where (i + 1) % `full_attention_interval` == 0, else the
  delta-rule mixer; every layer's feed-forward is the sparse block.
- Delta-rule mixer (Hk = `linear_num_key_heads` x dk = `linear_key_head_dim`,
  Hv = `linear_num_value_heads` x dv = `linear_value_head_dim`): [q | k | v |
  z] = u W_qkvz, [b | a] = u W_ba; [q, k, v] <- silu(conv([q, k, v])),
  causal, depthwise, `linear_conv_kernel_dim` taps (the last meets the token
  itself), no bias; beta = sigmoid(b), g = -exp(A_log) softplus(a +
  dt_bias), one each a value head; q and k L2-normalised over the head (eps
  1e-6), q times dk^-1/2; value head h reads key head h // (Hv / Hk). THE
  RECURRENCE, TOKEN BY TOKEN (a `lax.scan` over the sequence): S <- e^{g_t}
  S; delta = beta_t (v_t - S^T k_t); S <- S + k_t delta^T; o_t = S^T q_t.
  Then o <- w (o rsqrt(mean o^2 + eps)) silu(z) a head, W_o.
- Gated attention (H = `num_attention_heads`, Hkv = `num_key_value_heads`,
  hd = `head_dim`): W_q D -> H x 2 hd, split a head into query and gate;
  W_k, W_v D -> Hkv x hd; (1 + w) RMS norm of q and of k over the head;
  rotary (`rope_theta`, half-split) on the first `partial_rotary_factor` x
  hd dims; causal softmax of q k / sqrt(hd), H / Hkv query heads a KV head;
  output times sigmoid(gate); W_o. No biases.
- Sparse block on h = norm(x): logits = h W_g in float32 [`num_experts`]; s
  = softmax(logits); the `num_experts_per_tok` largest, their scores
  renormalised to sum 1; sum_e w_e SwiGLU_e(h) (width
  `moe_intermediate_size`) over the experts HELD (`expert_first .. +
  experts_held`: the share's; the other experts' part is another chip's and
  is left out, here as in the program), one expert at a time over every
  token; plus sigmoid(h w_sg) SwiGLU_shared(h).
- Head: final norm, untied output head over the `vocab_size` rows held.
- Left out: the multi-token-prediction module.

One forward covers a whole wave: the shared prompt prefix once, from an
empty state; then each row's tail (pod suffix + served tokens) from the
state the prefix left (S and the convolution's last inputs), attending the
prefix's keys and values and its own. The control, mode "int8": every matrix
multiplication of the layers (projections, every expert, the shared expert)
and of the head in int8 (weights rounded per output channel, activations per
token); the router, the recurrence, the convolution, attention scores and
norms stay in float32 / bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256    # query rows per attention block of the prefix
L2_EPS = 1e-6


def _sizes(conf: dict) -> dict:
    hk, hv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    per = conf["full_attention_interval"]
    L = conf["num_hidden_layers"]
    return dict(D=conf["hidden_size"], L=L, per=per, La=L // per, Lg=L - L // per,
                H=conf["num_attention_heads"], Hkv=conf["num_key_value_heads"], hd=conf["head_dim"],
                Hk=hk, Hv=hv, dk=dk, dv=dv, kw=hk * dk, vw=hv * dv, taps=conf["linear_conv_kernel_dim"],
                E=conf["experts_held"], Fe=conf["moe_intermediate_size"],
                Fs=conf["shared_expert_intermediate_size"], V=conf["vocab_size"],
                n_experts=conf["num_experts"])


# ------------------------------------------------------------------ weights
def init_weights(conf: dict, seed: int):
    """bfloat16 weights from the seed, drawn as the served model's init draws
    them: PRNGKey(seed) split in five (embedding, head, what every layer has,
    the delta-rule mixers, the attentions), those keys in 8, 6 and 4, one a
    leaf; normal in float32 scaled by 1/sqrt(fan_in) (1 for the embedding,
    1/sqrt(taps) for the convolution), cast to bfloat16; the experts drawn a
    layer at a time from their key split by layer; (1 + w) norms at w = 0,
    the mixer's gated norm at one; exp(A_log) uniform on (0, 16), floored at
    1e-3; dt log-uniform on (1e-3, 1e-1) and dt_bias its inverse softplus,
    both float32. One jitted program, as the served model's init is, so the
    draws round alike."""
    z = _sizes(conf)
    D, L, Lg, La = z["D"], z["L"], z["Lg"], z["La"]

    def make(key):
        k_embed, k_head, k_layers, k_gdn, k_attn = jax.random.split(key, 5)

        def dense(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) * fan_in**-0.5).astype(jnp.bfloat16)

        def stacked(k, shape, fan_in, n):
            return jax.lax.map(lambda kl: dense(kl, shape, fan_in), jax.random.split(k, n))

        def zeros(shape):
            return jnp.zeros(shape, jnp.bfloat16)

        kl, kg, ka = jax.random.split(k_layers, 8), jax.random.split(k_gdn, 6), jax.random.split(k_attn, 4)
        dt = jnp.exp(jax.random.uniform(kg[4], (Lg, z["Hv"]), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        a_decay = jnp.maximum(jax.random.uniform(kg[3], (Lg, z["Hv"]), jnp.float32, 0.0, 16.0), 1e-3)
        conv_width = 2 * z["kw"] + z["vw"]
        return {
            "embed": (jax.random.normal(k_embed, (z["V"], D), dtype=jnp.float32)).astype(jnp.bfloat16),
            "final_norm": zeros((D,)),
            "lm_head": dense(k_head, (D, z["V"]), D),
            "layers": {
                "attn_norm": zeros((L, D)),
                "mlp_norm": zeros((L, D)),
                "router": dense(kl[0], (L, D, z["n_experts"]), D),
                "we_gate": stacked(kl[1], (z["E"], D, z["Fe"]), D, L),
                "we_up": stacked(kl[2], (z["E"], D, z["Fe"]), D, L),
                "we_down": stacked(kl[3], (z["E"], z["Fe"], D), z["Fe"], L),
                "ws_gate": dense(kl[4], (L, D, z["Fs"]), D),
                "ws_up": dense(kl[5], (L, D, z["Fs"]), D),
                "ws_down": dense(kl[6], (L, z["Fs"], D), z["Fs"]),
                "ws_sel": dense(kl[7], (L, D), D),
            },
            "gdn": {
                "w_qkvz": dense(kg[0], (Lg, D, 2 * z["kw"] + 2 * z["vw"]), D),
                "w_ba": dense(kg[1], (Lg, D, 2 * z["Hv"]), D),
                "conv": dense(kg[2], (Lg, z["taps"], conv_width), z["taps"]),
                "A_log": jnp.log(a_decay),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "o_norm": jnp.ones((Lg, z["dv"]), jnp.bfloat16),
                "wo": dense(kg[5], (Lg, z["vw"], D), z["vw"]),
            },
            "attn": {
                "wq": dense(ka[0], (La, D, z["H"] * 2 * z["hd"]), D),
                "wk": dense(ka[1], (La, D, z["Hkv"] * z["hd"]), D),
                "wv": dense(ka[2], (La, D, z["Hkv"] * z["hd"]), D),
                "q_norm": zeros((La, z["hd"])),
                "k_norm": zeros((La, z["hd"])),
                "wo": dense(ka[3], (La, z["H"] * z["hd"], D), z["H"] * z["hd"]),
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
def _norm(x, w, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * (1.0 + w.astype(jnp.float32))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def delta_rule(q, k, v, g, beta, ok, s0):
    """THE RECURRENCE, token by token, for one sequence: q, k [T, Hv, dk],
    v [T, Hv, dv], g and beta [T, Hv], ok [T] (a token that is not there
    leaves the state alone), s0 [Hv, dk, dv]. Returns (o [T, Hv, dv], the
    state after the last token that is there)."""

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t, ok_t = xs
        s1 = s * jnp.exp(g_t)[:, None, None]
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s1, k_t, precision="highest"))
        s1 = s1 + k_t[:, :, None] * delta[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s1, q_t, precision="highest")
        return jnp.where(ok_t, s1, s), o

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta, ok))
    return o, s


def _gdn_one(x, lw, ok, n_valid, s0, window, z, eps, mm):
    """The delta-rule mixer over ONE sequence x [T, D] that starts from the
    state (s0, window [taps - 1, channels]); `n_valid` of its tokens are
    there. Returns (mixer output [T, D], state and window after them)."""
    T = x.shape[0]
    Hk, Hv, dk, dv, kw, vw, taps = z["Hk"], z["Hv"], z["dk"], z["dv"], z["kw"], z["vw"], z["taps"]
    u = _norm(x, lw["attn_norm"], eps)
    qkvz, ba = mm(u, lw["w_qkvz"]), mm(u, lw["w_ba"])
    qkv, gate = qkvz[:, : 2 * kw + vw], qkvz[:, 2 * kw + vw:]
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(lw["A_log"]) * jax.nn.softplus(ba[:, Hv:] + lw["dt_bias"])
    xx = jnp.concatenate([window, qkv], axis=0)
    conv = lw["conv"].astype(jnp.float32)
    mixed = jax.nn.silu(sum(xx[j: j + T] * conv[j] for j in range(taps)))
    q = _l2(mixed[:, :kw].reshape(T, Hk, dk)) * dk**-0.5
    k = _l2(mixed[:, kw: 2 * kw].reshape(T, Hk, dk))
    v = mixed[:, 2 * kw:].reshape(T, Hv, dv)
    q, k = jnp.repeat(q, Hv // Hk, axis=1), jnp.repeat(k, Hv // Hk, axis=1)
    o, s = delta_rule(q, k, v, g, beta, ok, s0)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * lw["o_norm"].astype(jnp.float32) * jax.nn.silu(gate.reshape(T, Hv, dv))
    return mm(o.reshape(T, vw), lw["wo"]), s, jax.lax.dynamic_slice_in_dim(xx, n_valid, taps - 1, 0)


@functools.partial(jax.jit, static_argnames=("mode", "eps", "zt"))
def _gdn_layer(x_pre, x_tails, lw, n_pre, mode, eps, zt):
    """x + mixer(x) for the prefix [P, D] from an empty state, then for every
    tail [R, T, D] from the state the prefix's `n_pre` tokens left."""
    z, mm = dict(zt), _mm(mode)
    P, T = x_pre.shape[0], x_tails.shape[1]
    s0 = jnp.zeros((z["Hv"], z["dk"], z["dv"]), jnp.float32)
    w0 = jnp.zeros((z["taps"] - 1, 2 * z["kw"] + z["vw"]), jnp.float32)
    y_pre, s_pre, w_pre = _gdn_one(x_pre, lw, jnp.arange(P) < n_pre, n_pre, s0, w0, z, eps, mm)
    # every tail starts from the prefix's state; the rows side by side, the tokens one after another
    y_tails = jax.vmap(
        lambda x: _gdn_one(x, lw, jnp.ones((T,), bool), T, s_pre, w_pre, z, eps, mm)[0])(x_tails)
    return x_pre + y_pre, x_tails + y_tails


def _rope(x, positions, theta, dr):
    """x [T, n, hd] with its first dr dims rotated at positions [T], half-split."""
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : dr // 2], x[..., dr // 2: dr], x[..., dr:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], axis=-1)


@functools.partial(jax.jit, static_argnames=("mode", "eps", "zt", "theta", "dr"))
def _attn_layer(x_pre, x_tails, lw, n_pre, mode, eps, zt, theta, dr):
    """x + attention(x): the prefix causally over itself, every tail over
    the prefix's `n_pre` tokens and causally over itself."""
    z, mm = dict(zt), _mm(mode)
    H, Hkv, hd = z["H"], z["Hkv"], z["hd"]
    act = jnp.float32 if mode == "f32" else jnp.bfloat16
    prec = "highest" if mode == "f32" else "default"
    P, (R, T) = x_pre.shape[0], x_tails.shape[:2]

    def project(x, positions):
        n = x.shape[0]
        u = _norm(x, lw["attn_norm"], eps)
        qg = mm(u, lw["wq"]).reshape(n, H, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = mm(u, lw["wk"]).reshape(n, Hkv, hd)
        v = mm(u, lw["wv"]).reshape(n, Hkv, hd)
        q = _rope(_norm(q, lw["q_norm"], eps), positions, theta, dr)
        k = _rope(_norm(k, lw["k_norm"], eps), positions, theta, dr)
        return q.reshape(n, Hkv, H // Hkv, hd), gate, k, v

    def attend(q, gate, keys, values, ok):
        """q [n, Hkv, G, hd] against keys [t, Hkv, hd]; ok [n, t]."""
        s = jnp.einsum("nkgd,tkd->kgnt", q.astype(act), keys.astype(act), precision=prec,
                       preferred_element_type=jnp.float32) * hd**-0.5
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
        o = jnp.einsum("kgnt,tkd->nkgd", p.astype(act), values.astype(act), precision=prec,
                       preferred_element_type=jnp.float32)
        return mm((o.reshape(-1, H, hd) * jax.nn.sigmoid(gate)).reshape(-1, H * hd), lw["wo"])

    q_p, g_p, k_p, v_p = project(x_pre, jnp.arange(P))
    there = jnp.arange(P) < n_pre

    def pre_block(start):
        qi = start + jnp.arange(BLOCK)
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, BLOCK, 0)  # noqa: E731
        return attend(sl(q_p), sl(g_p), k_p, v_p, (jnp.arange(P)[None, :] <= qi[:, None]) & there[None, :])

    y_pre = jax.lax.map(pre_block, jnp.arange(0, P, BLOCK)).reshape(P, -1)
    t = jnp.arange(T)
    ok_tail = jnp.concatenate([jnp.broadcast_to(there[None, :], (T, P)), t[None, :] <= t[:, None]], axis=1)

    def tail(x):
        q, gate, k, v = project(x, n_pre + t)
        return attend(q, gate, jnp.concatenate([k_p, k]), jnp.concatenate([v_p, v]), ok_tail)

    return x_pre + y_pre, x_tails + jax.lax.map(tail, x_tails)


def route(h, router, k: int, norm: bool):
    """Token -> expert weights [T, experts] (zero where not selected):
    softmax scores in float32 over ALL experts, the k largest selected,
    renormalised to sum 1 where `norm`."""
    logits = jnp.matmul(h.astype(jnp.float32), router.astype(jnp.float32), precision="highest")
    s = jax.nn.softmax(logits, axis=-1)
    _, sel = jax.lax.top_k(s, k)
    w = s * jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], sel].set(1.0)
    if norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return w


@functools.partial(jax.jit, static_argnames=("k", "norm", "eps", "mode", "first", "shared"))
def _sparse_block(x, layers, layer, k, norm, eps, mode, first=0, shared=True):
    """moe(norm(x)) for x [T, D], without the stream: the experts held
    (experts `first ..` of the router's; `we_*` are the WHOLE stacks [L, E,
    ..], read an expert at a time at `layer`), one expert at a time over
    every token, + the gated shared expert (`shared`: a share test counts it
    once)."""
    mm = _mm(mode)
    at = lambda name: jax.lax.dynamic_index_in_dim(layers[name], layer, 0, keepdims=False)  # noqa: E731
    h = _norm(x, at("mlp_norm"), eps)
    w = route(h, at("router"), k, norm)

    def one(e, y):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(layers[name], layer, 0, keepdims=False), e, 0, keepdims=False)
            for name in ("we_gate", "we_up", "we_down"))
        w_e = jax.lax.dynamic_index_in_dim(w, first + e, 1, keepdims=False)
        return y + w_e[:, None] * _swiglu(h, wg, wu, wd, mm)

    y = jax.lax.fori_loop(0, layers["we_gate"].shape[1], one, jnp.zeros_like(h))
    if shared:
        sel = jnp.matmul(h, at("ws_sel").astype(jnp.float32)[:, None], precision="highest") \
            if mode == "f32" else _int8_matmul(h, at("ws_sel")[:, None])
        y = y + jax.nn.sigmoid(sel) * _swiglu(h, at("ws_gate"), at("ws_up"), at("ws_down"), mm)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "mode", "vocab_rows"))
def _head(x_rows, final_norm, lm_head, eps, mode, vocab_rows):
    """Logits of the rows in x_rows over the first `vocab_rows` ids (the
    tokenizer's: no served token and no grammar token lies above them)."""
    h = _norm(x_rows, final_norm, eps)
    wh = lm_head[:, :vocab_rows]
    if mode == "f32":
        return jnp.matmul(h, wh.astype(jnp.float32), precision="highest")
    return _int8_matmul(h, wh)


def forward(conf: dict, weights, x_pre, x_tails, n_pre, mode: str):
    """Every layer over the prefix's stream [P, D] and the tails' [R, T, D]."""
    z = _sizes(conf)
    zt, eps, per = tuple(sorted(z.items())), conf["rms_norm_eps"], z["per"]
    layers, gdn, attn = weights["layers"], weights["gdn"], weights["attn"]
    P, (R, T) = x_pre.shape[0], x_tails.shape[:2]
    for l in range(z["L"]):
        p, j = divmod(l, per)
        if j < per - 1:
            lw = {k: a[p * (per - 1) + j] for k, a in gdn.items()}
            lw["attn_norm"] = layers["attn_norm"][l]
            x_pre, x_tails = _gdn_layer(x_pre, x_tails, lw, n_pre, mode, eps, zt)
        else:
            lw = {k: a[p] for k, a in attn.items()}
            lw["attn_norm"] = layers["attn_norm"][l]
            x_pre, x_tails = _attn_layer(x_pre, x_tails, lw, n_pre, mode, eps, zt, float(conf["rope_theta"]),
                                         int(z["hd"] * conf["partial_rotary_factor"]))
        flat = jnp.concatenate([x_pre, x_tails.reshape(R * T, -1)])
        flat = flat + _sparse_block(flat, layers, jnp.int32(l), conf["num_experts_per_tok"],
                                    conf["norm_topk_prob"], eps, mode, first=conf["expert_first"])
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
    _, x_tails = forward(conf, weights, table[pre], table[tl], jnp.int32(n_pre), mode)
    rows = [(r, first + j) for r, (first, count) in enumerate(pred_spans) for j in range(count)]
    r_idx, t_idx = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    return np.asarray(_head(x_tails[r_idx, t_idx], weights["final_norm"], weights["lm_head"],
                            conf["rms_norm_eps"], mode, vocab_rows))
