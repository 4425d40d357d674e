"""Draft runner: the small model that speculates K tokens ahead.

The draft is a full (small) Llama — train/distill.py's output is the
intended checkpoint — kept in a DENSE per-request KV buffer rather than the
paged cache: draft KV at distill scale is a few MB, rollback is pure host
bookkeeping (stale entries past the accepted prefix are never attended
because every step masks by position), and the buffer never contends with
the target's page pool.

The whole K-token proposal runs as ONE fused device program
(`_propose_impl`): a lax.scan of K single-token decode steps with sampling
and DFA transitions inside, so proposing costs one dispatch regardless of K
— K per-token host round trips (dispatch + sync each) would serialize
what one program pipelines (the same economics that shaped the engine's
fused decision waves).

Grammar composition: each proposal step samples in K-space through the
SAME SparseDFATables the target uses (engine/engine._sample_sparse), so a
draft proposal is grammar-legal by construction.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from k8s_llm_scheduler_tpu.engine.engine import _pick
from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.models.llama import (
    Params,
    _dense,
    _logits,
    apply_rope,
    forward_prefill,
    rms_norm,
    rope_inv_freq,
)
from k8s_llm_scheduler_tpu.ops.attention import NEG_INF


def build_random_draft(
    draft_cfg: LlamaConfig, tokenizer_vocab: int, seed: int
) -> tuple[Params, LlamaConfig]:
    """Random-init a named draft config, widened to cover the tokenizer.

    THE single widening rule: a draft narrower than the tokenizer cannot
    propose every legal token, so its vocab pads up to the next multiple of
    128 (MXU lane width) at or above the tokenizer's. Serving
    (engine/local._attach_spec) and the bench A/B (bench.spec_ab) both
    build through here so they measure the same configuration."""
    if draft_cfg.vocab_size < tokenizer_vocab:
        draft_cfg = dataclasses.replace(
            draft_cfg, vocab_size=-(-tokenizer_vocab // 128) * 128
        )
    from k8s_llm_scheduler_tpu.models.llama import init_params

    return init_params(jax.random.PRNGKey(seed), draft_cfg), draft_cfg


def _draft_token_step(
    params: Params,
    cfg: LlamaConfig,
    tok,  # scalar int32 — the token being processed
    pos,  # scalar int32 — its absolute position (== dense-buffer index)
    k_buf,  # [L, cap, n_kv, hd] (carried)
    v_buf,
):
    """One draft decode step: write the token's K/V at `pos`, attend over
    buffer[0..pos], return (logits [V], k_buf, v_buf)."""
    hd = cfg.head_dim
    inv_freq = rope_inv_freq(cfg)
    x = params["embed"][tok]  # [D]

    def body(carry, xs):
        x, kb, vb = carry
        lp, idx = xs
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q = _dense(h, lp["wq"], "d,dh->h").reshape(cfg.n_heads, hd)
        k = _dense(h, lp["wk"], "d,dh->h").reshape(cfg.n_kv_heads, hd)
        v = _dense(h, lp["wv"], "d,dh->h").reshape(cfg.n_kv_heads, hd)
        q = apply_rope(q, pos, inv_freq)
        k = apply_rope(k, pos, inv_freq)
        kb = kb.at[idx, pos].set(k.astype(kb.dtype))
        vb = vb.at[idx, pos].set(v.astype(vb.dtype))
        qg = (q.astype(jnp.float32) * hd**-0.5).reshape(
            cfg.n_kv_heads, cfg.q_per_kv, hd
        )
        keys = kb[idx].astype(jnp.float32)
        vals = vb[idx].astype(jnp.float32)
        logits = jnp.einsum("kgh,skh->kgs", qg, keys)
        mask = (jnp.arange(keys.shape[0]) <= pos)[None, None, :]
        logits = jnp.where(mask, logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        attn = jnp.einsum("kgs,skh->kgh", w, vals)
        attn = attn.reshape(cfg.n_heads * hd).astype(x.dtype)
        x = x + _dense(attn, lp["wo"], "h,hd->d")
        h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        gate = _dense(h2, lp["w_gate"], "d,df->f")
        up = _dense(h2, lp["w_up"], "d,df->f")
        fused = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        x = x + _dense(fused, lp["w_down"], "f,fd->d")
        return (x, kb, vb), None

    (x, k_buf, v_buf), _ = jax.lax.scan(
        body, (x, k_buf, v_buf),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return _logits(params, cfg, x), k_buf, v_buf


def _propose_impl(
    params: Params,
    cfg: LlamaConfig,  # static
    k_buf, v_buf,  # donated [L, cap, n_kv, hd]
    tok,    # scalar int32 — current last emitted token (KV not yet written)
    pos,    # scalar int32 — its absolute position
    state,  # scalar int32 — DFA state after `tok`
    sp_tokens, sp_next,  # sparse grammar tables (unused when unconstrained)
    pad_id,
    rng, temperature,
    K: int,              # static — proposal depth
    constrained: bool,   # static
    vocab_limit: int | None = None,  # static — see engine._sample_unconstrained
):
    """Propose K draft tokens in one fused program.

    Step i processes the (i-1)-th proposal (step 0 processes `tok`), writes
    its K/V into the dense buffer, samples proposal i from the grammar- (or
    pad-)masked logits, and advances the DFA state. Returns
    (tokens [K+1], states [K+1] — state AFTER each proposal, choice_idx [K]
    — the sampled index into the step's masked distribution, step_logits
    [K, X] — that masked distribution's logits (X = grammar K-width when
    constrained, vocab when not; the verifier's rejection sampler needs the
    draft's actual proposal distribution), k_buf, v_buf).

    The scan runs K+1 steps — the extra step processes the K-th proposal
    itself so the buffer holds valid KV through position pos+K. Without
    it, a fully-accepted round (a == K plus the bonus token) leaves the
    K-th proposal's buffer slot stale, and the next round's draft attends
    garbage from then on (measured: self-draft acceptance collapsed from
    1.0 to ~0.53). The extra step's sample is no longer discarded: it is
    the draft's GUESS at the round's bonus token — tokens[K] / states[K] —
    and the async pipeline (spec/decoder.py) anchors the AHEAD proposal
    for round n+1 on it while round n's verify is still in flight. When
    the verify's bonus token matches the guess, the pre-proposed block is
    exactly what a fresh propose would produce (greedy: bit-identical;
    sampling: a valid draw from the same proposal distribution), so the
    next round starts with zero draft latency on the critical path.
    """

    def step(carry, _):
        kb, vb, tok, pos, st, key = carry
        logits, kb, vb = _draft_token_step(params, cfg, tok, pos, kb, vb)
        key, sub = jax.random.split(key)
        if constrained:
            rows = sp_tokens[st]  # [Kw]
            gathered = logits[jnp.maximum(rows, 0)]
            masked = jnp.where(rows >= 0, gathered, NEG_INF)
            k_idx = _pick(masked[None, :], sub, temperature)[0]
            nxt_tok = rows[k_idx]
            nxt_st = sp_next[st, k_idx]
        else:
            V = logits.shape[-1]
            ids = jnp.arange(V)
            bad = ids == pad_id
            if vocab_limit is not None and vocab_limit < V:
                bad = bad | (ids >= vocab_limit)
            masked = jnp.where(bad, NEG_INF, logits)
            k_idx = _pick(masked[None, :], sub, temperature)[0]
            nxt_tok = k_idx
            nxt_st = st
        carry = (kb, vb, nxt_tok.astype(jnp.int32), pos + 1,
                 nxt_st.astype(jnp.int32), key)
        return carry, (nxt_tok.astype(jnp.int32), nxt_st.astype(jnp.int32),
                       k_idx.astype(jnp.int32), masked)

    (k_buf, v_buf, _, _, _, _), (toks, states, idxs, step_logits) = (
        jax.lax.scan(
            step, (k_buf, v_buf, tok, pos, state, rng), None, length=K + 1
        )
    )
    return toks, states, idxs[:K], step_logits[:K], k_buf, v_buf


def _prefill_impl(params, cfg, tokens, n, k_buf, v_buf):
    """Prefill the draft's dense buffer with the prompt's KV (bucketed
    [1, S] tokens; rows >= n are padding and get overwritten/masked)."""
    _, k_all, v_all = forward_prefill(
        params, cfg, tokens, jnp.asarray([n], dtype=jnp.int32),
        return_logits=False,
    )
    k_buf = jax.lax.dynamic_update_slice_in_dim(
        k_buf, k_all[:, 0].astype(k_buf.dtype), 0, axis=1
    )
    v_buf = jax.lax.dynamic_update_slice_in_dim(
        v_buf, v_all[:, 0].astype(v_buf.dtype), 0, axis=1
    )
    return k_buf, v_buf


class DraftRunner:
    """Per-request draft state over one small model.

    Single-owner like the engine; one request in flight at a time (the
    spec path serves `generate()` — the single-stream general-completion
    surface). `begin()` prefills the full prompt (shared prefix included —
    the draft holds its own dense KV, it does not read the target's
    buffers); `propose()` runs the fused K-step program; rollback is
    implicit (host position bookkeeping — see module doc).
    """

    CAP_ROUND = 256  # dense-buffer size bucket (bounds compile variants)

    def __init__(
        self,
        params: Params,
        cfg: LlamaConfig,
        *,
        vocab_limit: int | None = None,
        prefill_round: int = 128,
    ) -> None:
        self.params = params
        self.cfg = cfg
        self.vocab_limit = vocab_limit
        self.prefill_round = int(prefill_round)
        self._k: jax.Array | None = None
        self._v: jax.Array | None = None
        self._cap = 0
        self._prefill = jax.jit(
            _prefill_impl, static_argnums=(1,), donate_argnums=(4, 5)
        )
        self._propose = jax.jit(
            functools.partial(_propose_impl, vocab_limit=vocab_limit),
            static_argnums=(1, 12, 13),
            donate_argnums=(2, 3),
        )

    @property
    def capacity(self) -> int:
        """Current dense-buffer capacity in tokens (0 before begin()) —
        the async pipeline checks AHEAD proposals against it instead of
        letting propose() raise mid-round."""
        return self._cap

    def begin(self, token_ids: list[int], pad_id: int, extra: int) -> None:
        """Start a request: allocate the dense buffer sized for
        `len(token_ids) + extra` tokens (bucketed) and prefill the prompt.

        `token_ids` is the FULL context (engine prefix tokens + request
        suffix); `extra` covers max_new + K + slack."""
        total = len(token_ids)
        cap = -(-(total + extra) // self.CAP_ROUND) * self.CAP_ROUND
        shape = (self.cfg.n_layers, cap, self.cfg.n_kv_heads, self.cfg.head_dim)
        if self._k is None or self._cap != cap:
            self._cap = cap
            self._k = jnp.zeros(shape, dtype=self.cfg.dtype)
            self._v = jnp.zeros(shape, dtype=self.cfg.dtype)
        bucket = -(-total // self.prefill_round) * self.prefill_round
        assert bucket <= cap, (bucket, cap)
        tokens = np.full((1, bucket), pad_id, dtype=np.int32)
        tokens[0, :total] = token_ids
        self._k, self._v = self._prefill(
            self.params, self.cfg, jnp.asarray(tokens), total, self._k, self._v
        )

    def propose(
        self, tok, pos: int, state,
        sp_tokens, sp_next, pad_id: int,
        rng, temperature: float, k: int, constrained: bool,
    ):
        """Fused K-token proposal from (tok @ pos, DFA state). Returns the
        device arrays from _propose_impl (no host sync — the verifier
        consumes them directly). `tok`/`state` may be host ints OR device
        scalars: the async pipeline's AHEAD propose anchors on the
        previous proposal's device-resident guess (toks[K]/states[K])
        without ever fetching it. `pos` stays a host int — the overflow
        check below is host bookkeeping."""
        if self._k is None:
            raise RuntimeError("DraftRunner.begin() not called")
        if pos + k + 1 > self._cap:  # K+1 steps write pos..pos+K
            raise RuntimeError(
                f"draft buffer overflow: pos {pos} + K+1 {k + 1} > cap {self._cap}"
            )
        toks, states, idxs, step_logits, self._k, self._v = self._propose(
            self.params, self.cfg, self._k, self._v,
            jnp.int32(tok), jnp.int32(pos), jnp.int32(state),
            sp_tokens, sp_next, jnp.int32(pad_id),
            rng, jnp.float32(temperature), k, constrained,
        )
        return toks, states, idxs, step_logits
