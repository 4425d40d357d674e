"""models/mla_scmoe.py at a toy size of the LongCat-Flash family on the CPU:
the shortcut-connected double layer (two latent attentions with both scale
factors, two dense feed-forwards, one routed feed-forward beside them whose
output joins a sublayer later), the softmax router over feed-forward and
identity experts, and an expert-parallel share, against the plain reference
the benchmark keeps (benchmark/reference/mla_scmoe.py: written-out
attention, a loop over experts, float32 at `highest`, nothing of the program
imported). Seeded random weights; every mechanism present, every width
shrunk.
"""

from __future__ import annotations

import asyncio
import dataclasses
import importlib.util
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.models import family, mla_moe, mla_scmoe
from k8s_llm_scheduler_tpu.models.configs import MlaScmoeConfig, get_config

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"t_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(BENCH / "reference" / "mla_scmoe.py")

# The toy, in the published key names (what a configuration file holds): a
# share of 4 of 8 feed-forward experts, 4 identity experts, top 3.
TOY = {
    "name": "toy-mla-scmoe", "hidden_size": 64, "num_layers": 2, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
    "n_routed_experts": 8, "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
    "routed_scaling_factor": 6, "norm_topk_prob": False, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "attention_method": "MLA", "vocab_size": 512,
    "max_position_embeddings": 2048, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "experts_held": 4, "expert_first": 2,
}


def toy_cfg(dtype=jnp.float32, conf=TOY, **overrides) -> MlaScmoeConfig:
    kw = dict(dtype=dtype, expert_first=conf["expert_first"], expert_count=conf["experts_held"])
    return MlaScmoeConfig.from_hf(conf["name"], conf, **{**kw, **overrides})


def toy_params(cfg, seed=0):
    return jax.jit(lambda k: mla_scmoe.init_params(k, cfg))(jax.random.PRNGKey(seed))


def as_f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------- the wave, by hand
PREFIX_LEN, PREFIX_CAP = 37, 64
SUFFIX_LENS = (9, 16, 0, 5)          # row 2 is a padding row
BLOCKS = ((1, 3, 0, 2), (4, 1, 0, 4), (2, 2, 0, 1))  # valid tokens a row, per block iteration
F, SS, CAP = 4, 16, 12


def _wave_tokens(rng):
    prefix = rng.integers(1, 500, PREFIX_LEN).tolist()
    suffixes = [rng.integers(1, 500, n).tolist() for n in SUFFIX_LENS]
    served = [rng.integers(1, 500, sum(b[r] for b in BLOCKS)).tolist() for r in range(len(SUFFIX_LENS))]
    return prefix, suffixes, served


def program_wave_logits(cfg, params, prefix, suffixes, served, junk=0):
    """Prefix prefill -> suffix prefill -> block decode through the latent
    caches (2 x n_layers deep), as engine._wave_impl strings them. Returns
    ([logits at every position the program computed one for, per row],
    counters summed). `junk` is written to every padding position."""
    R = len(suffixes)
    toks = np.full((1, PREFIX_CAP), junk, np.int32)
    toks[0, :PREFIX_LEN] = prefix
    _, pc, pr = mla_scmoe.forward_prefill_kv(params, cfg, jnp.asarray(toks), jnp.asarray([PREFIX_LEN]))
    assert pc.shape[0] == pr.shape[0] == mla_scmoe.cache_layers(cfg) == 2 * cfg.n_layers
    pc, pr = pc[:, 0], pr[:, 0]
    sfx = np.full((R, SS), junk, np.int32)
    for r, s in enumerate(suffixes):
        sfx[r, :len(s)] = s
    lens = jnp.asarray([len(s) for s in suffixes], jnp.int32)
    logits, sc, sr, counters = mla_scmoe.forward_prefill_suffix_dense(
        params, cfg, jnp.asarray(sfx), lens, pc, pr, jnp.int32(PREFIX_LEN))
    out = [[np.asarray(logits[r])] if len(suffixes[r]) else [] for r in range(R)]
    gc, gr = (jnp.zeros((mla_scmoe.cache_layers(cfg), R, CAP + F, *shape), cfg.dtype)
              for shape in mla_scmoe.cache_token_shapes(cfg))
    tail = np.zeros(R, np.int32)
    for blk in BLOCKS:
        tok = np.full((R, F), junk, np.int32)
        for r in range(R):
            tok[r, :blk[r]] = served[r][tail[r]:tail[r] + blk[r]]
        blk_len = jnp.asarray(blk, jnp.int32)
        valid = jnp.arange(F)[None, :] < blk_len[:, None]
        pos = (PREFIX_LEN + lens + tail)[:, None] + jnp.arange(F)[None, :]
        logits, gc, gr, c = mla_scmoe.forward_block_decode(
            params, cfg, jnp.asarray(tok), valid, blk_len, pos, sc, sr, lens,
            gc, gr, jnp.asarray(tail), pc, pr, jnp.int32(PREFIX_LEN))
        counters = counters + c
        tail = tail + np.asarray(blk)
        for r in range(R):
            if blk[r]:
                out[r].append(np.asarray(logits[r]))
    return out, np.asarray(counters)


def reference_wave_logits(conf, weights, prefix, suffixes, served, mode="f32"):
    """The reference's one full forward over the same tree; the rows the
    program computed logits for: the last suffix token and the last valid
    token of every block."""
    rows = [r for r, s in enumerate(suffixes) if s]
    tails = [suffixes[r] + served[r] for r in rows]
    spans = [(len(suffixes[r]) - 1, len(served[r]) + 1) for r in rows]
    full = REF.wave_logits(conf, weights, prefix, tails, spans, mode, conf["vocab_size"])
    out, at = {}, 0
    for r, (_first, count) in zip(rows, spans):
        mine = full[at:at + count]
        at += count
        ends = np.cumsum([0] + [b[r] for b in BLOCKS])
        out[r] = [mine[e] for e in ends]
    return out


class TestWaveAgainstReference:
    """Prefix prefill -> suffix prefill -> block decode through the latent
    caches against the reference's one full forward: logits, not tokens."""

    # float32 on the CPU: the two sides differ in the ORDER of float32 sums
    # (absorbed products, merged softmax parts, grouped experts against
    # written-out attention and a loop over experts) and in where the two
    # fixed factors are multiplied in, a few 1e-6 of logits of order 1; the
    # same toy in bfloat16 lands near 1e-1. 2e-4 sits well over the one and
    # far under the other (models/mla_moe.py's toy holds the same number).
    TOL = 2e-4

    @pytest.fixture(scope="class")
    def wave(self):
        return _wave_tokens(np.random.default_rng(7))

    def _gaps(self, dtype, wave):
        """Worst gap after the suffix prefill (forwards 1 + 2) and after each
        block decode (forward 3), and the counters."""
        cfg = toy_cfg(dtype)
        params = toy_params(cfg)
        got, counters = program_wave_logits(cfg, params, *wave)
        want = reference_wave_logits(TOY, as_f32(params), *wave)
        worst = np.zeros(len(BLOCKS) + 1)
        for r, rows in want.items():
            assert len(rows) == len(got[r]) == len(BLOCKS) + 1
            for i, (a, b) in enumerate(zip(got[r], rows)):
                worst[i] = max(worst[i], float(np.max(np.abs(a.astype(np.float32) - b))))
        return worst, counters

    def test_float32_agrees_with_the_reference_in_every_forward(self, wave):
        worst, counters = self._gaps(jnp.float32, wave)
        assert worst[0] < self.TOL, ("prefix prefill + suffix prefill", worst)
        assert worst[1:].max() < self.TOL, ("block decode through the caches", worst)
        valid = sum(SUFFIX_LENS) + sum(map(sum, BLOCKS))
        calls = 1 + len(BLOCKS)
        c = dict(zip(mla_scmoe.COUNTERS, counters))
        # every pick of a valid token is an identity or a feed-forward expert's
        assert c["moe_zero_assignments"] + c["moe_ffn_assignments"] == valid * TOY["moe_topk"] * TOY["num_layers"]
        assert 0 < c["moe_assignments"] < c["moe_ffn_assignments"]  # 4 of 8 are held here
        assert c["moe_zero_assignments"] > 0
        assert c["moe_layer_calls"] == calls * TOY["num_layers"]
        assert 0 < c["moe_experts_hit"] <= TOY["experts_held"] * c["moe_layer_calls"]

    def test_bfloat16_fails_the_same_tolerance(self, wave):
        """The tolerance is tight enough to tell the precisions apart."""
        worst, _ = self._gaps(jnp.bfloat16, wave)
        assert worst.max() > 10 * self.TOL, worst

    def test_padding_changes_neither_logits_nor_assignments(self, wave):
        cfg = toy_cfg()
        params = toy_params(cfg)
        a, ca = program_wave_logits(cfg, params, *wave, junk=0)
        b, cb = program_wave_logits(cfg, params, *wave, junk=311)
        for rows_a, rows_b in zip(a, b):
            for x, y in zip(rows_a, rows_b):
                np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(ca, cb)


def test_a_wave_through_the_engine_serves_the_references_best_tokens():
    """set_prefix (prefix prefill) -> submit_wave / harvest_wave (suffix
    prefill, block decode through the latent caches) in float32 at greedy
    decode, against the reference's full forward over prefix + suffix +
    served tokens: every served token lies within TOL, in logits, of the
    best token the reference sees at its place (tokens themselves may differ
    where two logits tie closer than the sums' order resolves)."""
    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine

    cfg = toy_cfg()
    params = toy_params(cfg)
    eng = InferenceEngine(params, cfg, num_pages=8, page_size=64, max_slots=4, max_pages_per_seq=8,
                          prefill_buckets=(128, 256), chunk_steps=4, temperature=0.0)
    tok = eng.tokenizer
    prefix = tok.encode("cluster state: " + "node cpu mem " * 6)
    suffixes = [tok.encode(f"pod-{i} wants " + "cpu " * (2 + 3 * i)) for i in range(3)]
    eng.set_prefix(prefix)
    assert eng._prefix.k.shape[0] == 2 * cfg.n_layers
    fins = eng.harvest_wave(eng.submit_wave(suffixes, max_new_tokens=6))
    served = [f.token_ids for f in fins]
    assert all(1 <= len(s) <= 6 for s in served)
    spans = [(len(s) - 1, len(t)) for s, t in zip(suffixes, served)]
    tails = [s + t for s, t in zip(suffixes, served)]
    logits = np.array(REF.wave_logits(TOY, as_f32(params), prefix, tails, spans, "f32", tok.vocab_size))
    logits[:, tok.pad_id] = -np.inf  # the engine never samples the pad
    flat = [t for s in served for t in s]
    assert len(flat) == logits.shape[0]
    gaps = [float(row.max() - row[t]) for row, t in zip(logits, flat)]
    assert max(gaps) < TestWaveAgainstReference.TOL, gaps
    c = eng.stats
    assert c["moe_zero_assignments"] > 0 and 0 < c["moe_assignments"] < c["moe_ffn_assignments"]


def test_init_is_the_references_leaf_for_leaf():
    """benchmark/tests/check_init.py's comparison, at the toy size."""
    cfg = toy_cfg(jnp.bfloat16)
    mine = toy_params(cfg, seed=5)
    theirs = REF.init_weights(TOY, 5)
    flat = dict(jax.tree_util.tree_leaves_with_path(theirs))
    leaves = jax.tree_util.tree_leaves_with_path(mine)
    assert len(leaves) == len(flat)
    for path, leaf in leaves:
        assert leaf.dtype == flat[path].dtype and leaf.shape == flat[path].shape, path
        np.testing.assert_array_equal(np.asarray(leaf, np.float32), np.asarray(flat[path], np.float32))
    bias = mine["layers"]["router_bias"]
    assert bias.shape == (2, 12) and float(jnp.min(jnp.abs(bias))) > 0  # drawn, not zero


# ------------------------------------------------------------- the sublayers
def _one_sequence(rng, cfg, S=12):
    x = jnp.asarray(rng.normal(size=(1, S, cfg.d_model)), jnp.float32)
    positions = jnp.arange(S)[None, :] + 5
    return x, positions


def test_absorbed_attention_with_both_scale_factors_is_the_written_out_one():
    """The program's sublayer (absorbed, the factors on the normed latents,
    the cached latent carrying sqrt(D / dc)) against the reference's
    (written out, the query's factor behind W_uq): equal with both factors
    on, and the factors matter."""
    cfg = toy_cfg()
    assert cfg.q_lora_scale == 2 ** 0.5 and cfg.kv_lora_scale == 2.0
    layers = toy_params(cfg)["layers"]
    lp = {k: layers[k][1, 1] for k in mla_scmoe.ATTENTION_LEAVES}
    x, positions = _one_sequence(np.random.default_rng(3), cfg)
    S = x.shape[1]
    mask = (jnp.arange(S)[:, None] >= jnp.arange(S)[None, :])[None, None]

    def attend(lp, q_nope, q_rope, c_kv, k_r):
        return mla_moe.attend_absorbed(lp, cfg, q_nope, q_rope, [(c_kv, k_r, mask)])

    got, (c_kv, _k_r) = mla_moe.attention_sublayer(lp, cfg, x, positions, mla_moe._inv_freq(cfg), attend)
    pad = REF.BLOCK - S  # the reference attends in blocks of BLOCK query rows
    xs = jnp.concatenate([x[0], jnp.zeros((pad, cfg.d_model))])
    pos = jnp.concatenate([positions[0], jnp.zeros((pad,), positions.dtype)])
    seg = jnp.concatenate([jnp.zeros((S,), jnp.int32), -jnp.ones((pad,), jnp.int32)])
    want = REF._attention(xs, lp, pos, seg, REF._dims(TOY), "f32")[:S]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-4, atol=1e-5)
    # the cached latent has the factor's size: RMS sqrt(D / dc) = 2, not 1
    assert abs(float(jnp.sqrt(jnp.mean(c_kv**2))) - 2.0) < 0.05
    for off in ({"mla_scale_q_lora": False}, {"mla_scale_kv_lora": False}):
        other = REF._attention(xs, lp, pos, seg, REF._dims({**TOY, **off}), "f32")[:S]
        assert float(jnp.max(jnp.abs(other - want))) > 1e-2, off


class TestLayerOrder:
    """x1 = x + A0(x); m = M(x1); x2 = x1 + F0(x1); x3 = x2 + A1(x2);
    x4 = x3 + F1(x3) + m: the program's layer is the reference's, and is NOT
    the layer that adds m a sublayer early (where the second attention and
    F1 would see it)."""

    def _both(self, early: bool):
        cfg = toy_cfg(expert_first=0, expert_count=8)
        conf = {**TOY, "expert_first": 0, "experts_held": 8}
        layers = toy_params(cfg)["layers"]
        x, positions = _one_sequence(np.random.default_rng(4), cfg, S=REF.BLOCK)
        S = x.shape[1]
        mask = (jnp.arange(S)[:, None] >= jnp.arange(S)[None, :])[None, None]

        def attend(_j, lp, q_nope, q_rope, c_kv, k_r):
            return mla_moe.attend_absorbed(lp, cfg, q_nope, q_rope, [(c_kv, k_r, mask)])

        got, _, _ = mla_scmoe._layer(layers, jnp.int32(0), cfg, x, positions, jnp.ones((1, S), bool),
                                     mla_moe._inv_freq(cfg), attend)
        pos, seg = positions[0], jnp.zeros((S,), jnp.int32)
        if not early:
            want = REF.layer(conf, layers, 0, x[0], pos, seg, "f32")
        else:  # the reference's own pieces, m joined after the FIRST sublayer
            dims, eps = REF._dims(conf), conf["rms_norm_eps"]
            sub = lambda names, j: {k: layers[k][0, j] for k in names}  # noqa: E731
            x1 = REF._attention(x[0], sub(REF.ATTENTION, 0), pos, seg, dims, "f32")
            m = REF._routed_ffn(x1, layers["mlp_norm"][0, 0], layers["router"][0], layers["router_bias"][0],
                                layers["we_gate"], layers["we_up"], layers["we_down"], jnp.int32(0),
                                REF._route_dims(conf), "f32")
            x2 = x1 + REF._dense_ffn(x1, sub(REF.DENSE, 0), eps, "f32") + m
            x3 = REF._attention(x2, sub(REF.ATTENTION, 1), pos, seg, dims, "f32")
            want = x3 + REF._dense_ffn(x3, sub(REF.DENSE, 1), eps, "f32")
        return float(jnp.max(jnp.abs(got[0] - want)))

    def test_m_joins_after_the_second_sublayer(self):
        assert self._both(early=False) < 1e-4

    def test_a_layer_that_adds_m_after_the_first_sublayer_is_told_apart(self):
        assert self._both(early=True) > 1e-2


# ---------------------------------------------------------------- the router
class TestRouter:
    """Softmax over ALL outputs, selection by score + bias, weights from the
    scores alone, NOT renormalised, times the scaling factor: a case in
    which changing any one of them changes the answer, for the program's
    router and for the reference's."""

    LOGITS = np.array([[2.0, 1.0, 0.5, -1.0, 0.0]], np.float32)
    BIAS = np.array([0.0, -0.3, 0.0, 0.35, 0.0], np.float32)
    K, SCALE = 2, 6.0

    def _expected(self, sigmoid=False, use_bias=True, bias_in_weights=False, renorm=False, scale=True):
        z = self.LOGITS[0]
        s = 1.0 / (1.0 + np.exp(-z)) if sigmoid else np.exp(z) / np.exp(z).sum()
        pick = np.argsort(-(s + self.BIAS if use_bias else s))[: self.K]
        w = (s + self.BIAS if bias_in_weights else s)[pick]
        if renorm:
            w = w / w.sum()
        out = np.zeros(5, np.float32)
        out[pick] = w * (self.SCALE if scale else 1.0)
        return out

    def _inputs(self):
        h = np.zeros((1, 8), np.float32)
        h[0, 0] = 1.0
        router = np.zeros((8, 5), np.float32)
        router[0] = self.LOGITS[0]
        return h, router

    def test_the_case_tells_them_apart(self):
        want = self._expected()
        assert set(np.nonzero(want)[0]) == {0, 3}  # by bias: output 3 displaces output 1
        for variant in (dict(sigmoid=True), dict(use_bias=False), dict(bias_in_weights=True),
                        dict(renorm=True), dict(scale=False)):
            assert np.max(np.abs(self._expected(**variant) - want)) > 0.05, variant

    def test_program_router(self):
        h, router = self._inputs()
        cfg = toy_cfg(n_routed_experts=3, n_zero_experts=2, n_experts_per_tok=self.K,
                      routed_scaling_factor=self.SCALE, expert_first=0, expert_count=3)
        sel, w = mla_moe.route({"router": jnp.asarray(router), "router_bias": jnp.asarray(self.BIAS)},
                               cfg, jnp.asarray(h))
        got = np.zeros(5, np.float32)
        got[np.asarray(sel[0])] = np.asarray(w[0])
        np.testing.assert_allclose(got, self._expected(), rtol=1e-5)

    def test_reference_router(self):
        h, router = self._inputs()
        got = REF.route(jnp.asarray(h), jnp.asarray(router), jnp.asarray(self.BIAS),
                        self.K, False, self.SCALE)
        np.testing.assert_allclose(np.asarray(got[0]), self._expected(), rtol=1e-5)


# ---------------------------------------------------------------- the shares
def test_four_shares_of_four_experts_add_up_to_the_uncut_layer():
    """An expert-parallel share holds 4 of 16 feed-forward experts, routes
    over all 16 + 8 identity outputs and computes its own experts' part and,
    like every share, the identity experts'. The four shares' parts with the
    identity experts COUNTED ONCE are the uncut routed layer, the program's
    and the reference's; every pick on a feed-forward expert is computed by
    exactly one share (sum of `moe_assignments` = `moe_ffn_assignments`)."""
    conf = {**TOY, "n_routed_experts": 16, "zero_expert_num": 8, "moe_topk": 5,
            "experts_held": 16, "expert_first": 0}
    cfg = toy_cfg(conf=conf)
    layers = toy_params(cfg)["layers"]
    lp = {k: v[0] for k, v in layers.items()}
    norm_w = lp["mlp_norm"][0]
    rng = np.random.default_rng(11)
    T = 24
    x = jnp.asarray(rng.normal(size=(T, cfg.d_model)), jnp.float32)
    h = mla_moe.rms_norm(x, norm_w, cfg.rms_eps)
    valid = jnp.ones((T,), bool)
    whole, counters = mla_moe.routed_experts(lp, cfg, h, valid)
    c = dict(zip(mla_scmoe.COUNTERS, map(int, counters)))
    assert c["moe_zero_assignments"] + c["moe_ffn_assignments"] == T * 5
    assert c["moe_assignments"] == c["moe_ffn_assignments"] and c["moe_zero_assignments"] > 0
    sel, w = mla_moe.route(lp, cfg, h)
    zero, _ = mla_moe.zero_experts(cfg, h, sel, w, valid)
    assert float(jnp.max(jnp.abs(zero))) > 0.1

    experts = lambda s: {k: lp[k][4 * s: 4 * s + 4] for k in mla_moe.EXPERT_LEAVES}  # noqa: E731
    parts, assigned = 0.0, 0
    for share in range(4):
        cut = dataclasses.replace(cfg, expert_first=4 * share, expert_count=4)
        part, cs = mla_moe.routed_experts({**lp, **experts(share)}, cut, h, valid)
        parts = parts + part
        cs = dict(zip(mla_scmoe.COUNTERS, map(int, cs)))
        assigned += cs["moe_assignments"]
        assert (cs["moe_zero_assignments"], cs["moe_ffn_assignments"]) == (
            c["moe_zero_assignments"], c["moe_ffn_assignments"])  # every share routes every token
    assert assigned == c["moe_ffn_assignments"]
    # each share added the identity experts' part: counted once, three go
    np.testing.assert_allclose(np.asarray(parts - 3 * zero), np.asarray(whole), rtol=1e-4, atol=1e-5)

    def ref_part(share: int, zero: bool):
        stacks = {k: (layers[k][:, 4 * share: 4 * share + 4] if share >= 0 else layers[k])
                  for k in mla_moe.EXPERT_LEAVES}
        return REF._routed_ffn(x, norm_w, lp["router"], lp["router_bias"], stacks["we_gate"],
                               stacks["we_up"], stacks["we_down"], jnp.int32(0),
                               REF._route_dims(conf), "f32", first=max(4 * share, 0), zero=zero)

    want = ref_part(-1, True)  # the uncut layer
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), rtol=1e-4, atol=2e-5)
    ref_parts = sum(ref_part(s, zero=(s == 0)) for s in range(4))
    np.testing.assert_allclose(np.asarray(ref_parts), np.asarray(want), rtol=1e-4, atol=2e-5)


# ------------------------------------------------------- the share's short path
def routed_experts_every_row(lp, cfg, h, valid):
    """`models/mla_moe.py` `routed_experts` without a short path (COUNTERS and
    the identity experts' part alone): every token x pick row ordered by
    expert, gathered, multiplied and put back. Kept here as what the short
    path has to equal, and as the text a layer that holds every output
    still has to lower to. (The order as a stable sort, as it stood before
    the counting sort, is tests/test_routed_order.py's.)"""
    T, D = h.shape
    k, held_n = cfg.n_experts_per_tok, cfg.experts_held
    with jax.named_scope("moe_router"):
        sel, w = mla_moe.route(lp, cfg, h)
    with jax.named_scope("moe_dispatch"):
        local = sel - cfg.expert_first
        held = valid[:, None] & (local >= 0) & (local < held_n)
        group = jnp.where(held, local, held_n).reshape(T * k)
        sizes, position = mla_moe.group_positions(group, held_n)
    with jax.named_scope("moe_dispatch"):
        order = mla_moe.order_head(position, T * k)
        rows = h.astype(lp["we_gate"].dtype)[order // k]
    with jax.named_scope("moe_experts"):
        gate, up, down = (
            w if w.ndim == 4 else w[None] for w in (lp["we_gate"], lp["we_up"], lp["we_down"])
        )
        layer = lp.get("layer", 0)
        mid = mla_moe.grouped_matmul(rows, (gate, up), sizes, layer, swiglu=True)
        out = mla_moe.grouped_matmul(mid, (down,), sizes, layer, out_dtype=jnp.float32)
    with jax.named_scope("moe_combine"):
        back = out[position].reshape(T, k, D)
        y = jnp.sum(jnp.where(held[..., None], back * w[..., None], 0.0), axis=1)
    counters = jnp.stack([
        jnp.sum(held), jnp.sum(sizes > 0), jnp.int32(1), jnp.max(sizes),
    ]).astype(jnp.int32)
    if cfg.n_zero_experts is not None:
        y_zero, zero_counters = mla_moe.zero_experts(cfg, h, sel, w, valid)
        return y + y_zero, jnp.concatenate([counters, zero_counters])
    return y, counters


@pytest.mark.parametrize("n_rows, held_n, n_outputs", [
    (2304, 16, 768), (12288, 16, 768), (24576, 16, 768),  # the third cell's decode, suffix and prefix calls
    (4096, 64, 64), (96, 8, 64), (384, 4, 24), (3, 1, 1000), (1 << 20, 1, 3),
])
def test_the_bound_is_slack_times_a_level_share_in_whole_tiles_and_never_more_than_all(n_rows, held_n, n_outputs):
    from k8s_llm_scheduler_tpu.ops.grouped_matmul import ROW_TILE

    bound = mla_moe.held_bound(n_rows, held_n, n_outputs)
    level = n_rows * held_n / n_outputs
    assert 0 < bound <= n_rows
    if bound < n_rows:
        assert bound % ROW_TILE == 0
        assert mla_moe.HELD_SLACK * level <= bound < mla_moe.HELD_SLACK * level + ROW_TILE
    else:
        assert mla_moe.HELD_SLACK * level > n_rows - ROW_TILE
    if held_n == n_outputs:
        assert bound == n_rows  # a layer that holds every output has no short path


class TestShortPath:
    """A share of 4 of 16 feed-forward experts among 24 router outputs, top 3,
    128 tokens: 384 assignment rows, of which the short path handles a head
    of `held_bound`. The router's SELECTION is forced (`route(sel=)`, the way
    benchmark/tests/read_flips.py forces it), so that the number of held
    assignments is the case's; weights, experts and identity picks are the
    program's own."""

    T, FIRST, HELD = 128, 5, 4
    CONF = {**TOY, "n_routed_experts": 16, "zero_expert_num": 8, "moe_topk": 3,
            "experts_held": HELD, "expert_first": FIRST}

    @pytest.fixture(scope="class")
    def layer(self):
        cfg = toy_cfg(conf=self.CONF)
        lp = {k: v[0] for k, v in toy_params(cfg)["layers"].items()}
        rng = np.random.default_rng(23)
        h = mla_moe.rms_norm(jnp.asarray(rng.normal(size=(self.T, cfg.d_model)), jnp.float32),
                             lp["mlp_norm"][0], cfg.rms_eps)
        valid = np.ones(self.T, bool)
        valid[rng.choice(self.T, 16, replace=False)] = False  # padding tokens
        bound = mla_moe.held_bound(self.T * 3, self.HELD, 24)
        assert bound < valid.sum() * 3 < self.T * 3  # there is a short path, and it can overflow
        return cfg, lp, h, valid, bound

    def _selection(self, valid, n_held: int):
        """[T, 3] router outputs, distinct within a token: `n_held` slots of
        valid tokens on held experts, every other slot of a valid token on an
        absent expert or an identity expert; padding tokens pick held
        experts alone (and must not count)."""
        t = np.arange(self.T)
        sel = np.stack([t % self.FIRST, self.FIRST + self.HELD + t % 7, 16 + t % 8], axis=1)
        on_held = self.FIRST + (t[:, None] + np.arange(3)[None, :]) % self.HELD
        slots = np.argwhere(np.broadcast_to(valid[:, None], sel.shape))
        slots = slots[np.random.default_rng(n_held).permutation(len(slots))[:n_held]]
        sel[slots[:, 0], slots[:, 1]] = on_held[slots[:, 0], slots[:, 1]]
        sel[~valid] = on_held[~valid]
        return jnp.asarray(sel, jnp.int32)

    def _run(self, monkeypatch, fn, layer, n_held):
        cfg, lp, h, valid, _ = layer
        forced, real = self._selection(valid, n_held), mla_moe.route
        monkeypatch.setattr(mla_moe, "route", lambda lp_, cfg_, h_, sel=None: real(lp_, cfg_, h_, sel=forced))
        y, counters = jax.jit(lambda lp_, h_, v: fn(lp_, cfg, h_, v))(lp, h, jnp.asarray(valid))
        _, w = real(lp, cfg, h, sel=forced)
        return np.asarray(y), np.asarray(counters), np.asarray(forced), np.asarray(w)

    @pytest.mark.parametrize("case", ["none", "under", "at", "one_over", "every_pick"])
    def test_it_equals_a_loop_over_experts_and_the_every_row_path(self, monkeypatch, layer, case):
        cfg, lp, h, valid, bound = layer
        n_held = {"none": 0, "under": bound - 37, "at": bound, "one_over": bound + 1,
                  "every_pick": int(valid.sum()) * 3}[case]
        y, counters, sel, w = self._run(monkeypatch, mla_moe.routed_experts, layer, n_held)
        c = dict(zip(mla_scmoe.COUNTERS, map(int, counters)))
        assert c["moe_assignments"] == n_held and c["moe_layer_calls"] == 1
        assert c["moe_bounded_calls"] == (1 if n_held <= bound else 0)
        assert c["moe_zero_assignments"] + c["moe_ffn_assignments"] == int(valid.sum()) * 3

        want = np.zeros_like(y)
        x = np.asarray(h)
        for e in range(self.HELD):
            mine = valid[:, None] & (sel == self.FIRST + e)
            gate, up, down = (np.asarray(lp[k][e]) for k in mla_moe.EXPERT_LEAVES)
            g = x @ gate
            out = ((g / (1.0 + np.exp(-g))) * (x @ up)) @ down
            want += out * (w * mine).sum(axis=1, keepdims=True)
        want += x * (w * (valid[:, None] & (sel >= 16))).sum(axis=1, keepdims=True)
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=2e-5)
        assert not y[~valid].any()  # padding is not routed, whatever it picked

        before, counters_before, _, _ = self._run(monkeypatch, routed_experts_every_row, layer, n_held)
        np.testing.assert_allclose(y, before, rtol=1e-5, atol=1e-6)  # float32 summation order
        np.testing.assert_array_equal(counters[:6], counters_before)

    def test_the_short_path_holds_no_operand_of_every_row_and_the_long_one_does(self, layer):
        cfg, lp, h, valid, bound = layer
        text = jax.jit(lambda lp_, h_, v: mla_moe.routed_experts(lp_, cfg, h_, v)).lower(
            lp, h, jnp.asarray(valid)).as_text()
        D, rows = cfg.d_model, self.T * 3
        # the head's weighted rows against the one-hot, and the un-sort's gather
        assert f"tensor<{self.T}x{bound}xf32>" in text and f"tensor<{bound}x{D}xf32>" in text
        assert f"tensor<{rows}x{D}xf32>" in text


@pytest.mark.parametrize("name, tokens", [("tiny-mla-moe", 4), ("tiny-mla-moe", 192), ("tiny-mla-moe", 2048)])
def test_a_layer_that_holds_every_expert_lowers_as_it_did(name, tokens):
    """`glm-4_7-flash` holds 64 of 64: its bound is every row, and the text
    `routed_experts` lowers to is the text of the function as it stood."""
    cfg = get_config(name)
    assert mla_moe.held_bound(tokens * cfg.n_experts_per_tok, cfg.experts_held, cfg.n_routed_experts) \
        == tokens * cfg.n_experts_per_tok
    lp = jax.tree_util.tree_map(lambda a: a[0], jax.jit(
        lambda k: mla_moe.init_params(k, cfg))(jax.random.PRNGKey(0))["moe_layers"])
    h, valid = jnp.ones((tokens, cfg.d_model), jnp.float32), jnp.ones((tokens,), bool)
    now, before = (jax.jit(lambda lp, h, v, fn=fn: fn(lp, cfg, h, v)).lower(lp, h, valid).as_text()
                   for fn in (mla_moe.routed_experts, routed_experts_every_row))
    assert now == before


# ------------------------------------------------------------------ the names
def test_the_lowered_forwards_hold_the_scopes_and_kernel_names():
    """The scopes a device trace reads this model's time by (benchmark/
    metrics/dense_ffn_*, moe_zero_*, and the accepted moe_* / mla_proj_* /
    attn_ / mlp_ readers) and the grouped kernels' names."""
    cfg = toy_cfg()
    params = toy_params(cfg)
    R, L = 2, mla_scmoe.cache_layers(cfg)
    c_tok, r_tok = mla_scmoe.cache_token_shapes(cfg)
    z = lambda *shape: jnp.zeros(shape, cfg.dtype)  # noqa: E731
    text = jax.jit(mla_scmoe.forward_block_decode, static_argnums=1).lower(
        params, cfg, jnp.zeros((R, F), jnp.int32), jnp.ones((R, F), bool), jnp.full((R,), F, jnp.int32),
        jnp.zeros((R, F), jnp.int32), z(L, R, SS, *c_tok), z(L, R, SS, *r_tok), jnp.ones((R,), jnp.int32),
        z(L, R, CAP + F, *c_tok), z(L, R, CAP + F, *r_tok), jnp.zeros((R,), jnp.int32),
        z(L, PREFIX_CAP, *c_tok), z(L, PREFIX_CAP, *r_tok), jnp.int32(PREFIX_LEN),
    ).as_text(debug_info=True)
    for path in ("attn/mla_down", "attn/mla_up", "attn/latent_attention", "attn/wo", "kv_writeback",
                 "mlp/dense_ffn", "mlp/moe_router", "mlp/moe_dispatch", "mlp/moe_experts",
                 "mlp/moe_combine", "mlp/moe_zero", "lm_head", "embed"):
        assert f"{path}/" in text, path
    assert "moe_shared" not in text  # no shared expert in this family
    assert "kv_writeback/dynamic_update_slice" in text and "kv_writeback/scatter" not in text
    for kernel in ("moe_grouped_swiglu", "moe_grouped_matmul"):
        assert kernel in text, kernel
    prefill = jax.jit(mla_scmoe.forward_prefill_kv, static_argnums=1).lower(
        params, cfg, jnp.zeros((1, PREFIX_CAP), jnp.int32), jnp.asarray([PREFIX_LEN])).as_text(debug_info=True)
    assert "/prefix_prefill/" in prefill and "attn/latent_attention/" in prefill
    assert "lm_head/" not in prefill  # the cache alone


def test_the_second_family_still_counts_four_and_holds_no_identity_scope():
    """What models/mla_moe.py shares is chosen by the config: its own
    forwards keep their four counters, sigmoid scores and no `moe_zero`."""
    cfg = get_config("tiny-mla-moe")
    assert mla_moe.COUNTERS == ("moe_assignments", "moe_experts_hit", "moe_layer_calls", "moe_max_load")
    assert (cfg.router_score, cfg.n_zero_experts, cfg.q_lora_scale, cfg.kv_lora_scale) == ("sigmoid", None, 1.0, 1.0)
    lp = jax.tree_util.tree_map(lambda a: a[0], jax.jit(
        lambda k: mla_moe.init_params(k, cfg))(jax.random.PRNGKey(0))["moe_layers"])
    h = jnp.ones((4, cfg.d_model), jnp.float32)
    text = jax.jit(lambda lp, h: mla_moe.routed_experts(lp, cfg, h, jnp.ones((4,), bool))).lower(
        lp, h).as_text(debug_info=True)
    assert "moe_zero" not in text and "moe_router/" in text
    _, counters = mla_moe.routed_experts(lp, cfg, h, jnp.ones((4,), bool))
    assert counters.shape == (4,)


# --------------------------------------------------------- a whole decision
@pytest.fixture(scope="module")
def stack():
    import chip_smoke
    from k8s_llm_scheduler_tpu.cli import _build_stack
    from k8s_llm_scheduler_tpu.testing import synthetic_cluster

    cfg = chip_smoke.smoke_config(model="tiny-mla-scmoe", bpe_fixture=False)
    cluster = synthetic_cluster(3)
    scheduler, backend = _build_stack(cfg, cluster)
    yield scheduler, backend, cluster
    backend.close()


def test_scheduler_run_binds_pods_from_the_model(stack):
    """`cli._build_stack` -> `Scheduler.run()` -> LocalLLMBackend ->
    submit_wave / harvest_wave on the toy: pods are bound by the model's
    decisions, the prefix the engine holds is the latent pair, two sublayers
    a layer deep, and all six counters came back with the harvest."""
    import chip_smoke
    from k8s_llm_scheduler_tpu.testing import pod_burst

    scheduler, backend, cluster = stack
    engine = backend.engine
    assert family(engine.cfg) is mla_scmoe and not engine.paged
    burst = pod_burst(6, distinct_shapes=6)
    asyncio.run(chip_smoke._serve(scheduler, cluster, burst, timeout_s=100.0))
    stats = scheduler.get_stats()
    assert cluster.bind_count == 6
    assert stats["llm_decisions"] == 6 and stats["fallback_decisions"] == 0
    cfg = engine.cfg
    cap = engine._prefix.k.shape[1]
    assert engine._prefix.k.shape == (2 * cfg.n_layers, cap, cfg.kv_lora_rank)
    assert engine._prefix.v.shape == (2 * cfg.n_layers, cap, cfg.qk_rope_head_dim)
    assert cap >= engine.prefix_len > 0
    assert engine.kv.k.shape[:2] == (2 * cfg.n_layers, 1)  # no paged pool: the scratch page alone
    es = backend.get_stats()
    assert es["waves"] >= 1 and es["moe_layer_calls"] == cfg.n_layers * (
        es["wave_model_calls"] + es["waves"])
    assert es["moe_zero_assignments"] > 0
    assert 0 < es["moe_assignments"] < es["moe_ffn_assignments"]
    picks = es["moe_zero_assignments"] + es["moe_ffn_assignments"]
    assert picks % (cfg.n_experts_per_tok * cfg.n_layers) == 0  # whole tokens, every layer


# -------------------------------------------------------- what is refused
class TestRefusedPaths:
    """What this family does not serve refuses at build time (or at the
    call, for an entry point), naming the model, its module and the path,
    before anything is traced."""

    @pytest.mark.parametrize("kwargs, path", [
        (dict(mesh_axes={"tp": 2}), "llm.mesh"),
        (dict(quantize="int8"), "llm.quantization"),
        (dict(checkpoint_path="/nonexistent"), "llm.checkpoint_path"),
        (dict(spec_enabled=True), "llm.spec_enabled"),
        (dict(decode_matmul="ragged"), "llm.decode_matmul"),
    ])
    def test_build_refuses(self, kwargs, path):
        from k8s_llm_scheduler_tpu.engine.local import build_local_backend

        t0 = time.perf_counter()
        with pytest.raises(ValueError, match="tiny-mla-scmoe") as err:
            build_local_backend("tiny-mla-scmoe", compile_cache_dir=None, **kwargs)
        assert path in str(err.value) and "not served" in str(err.value)
        assert time.perf_counter() - t0 < 30

    def test_ragged_decode_refuses_in_the_forward_too(self):
        cfg = toy_cfg()
        with pytest.raises(ValueError, match="mla_scmoe.py"):
            mla_scmoe.forward_block_decode(None, cfg, *([jnp.zeros((1, 1), jnp.int32)] * 13), ragged=True)

    @pytest.mark.parametrize("call, path", [
        (lambda e: e.generate("hello"), "generate()"),
        (lambda e: e.add_requests([[1, 2, 3]]), "add_requests()"),
        (lambda e: e.admit_packed([[1, 2, 3]]), "admit_packed()"),
        (lambda e: e.step(), "step()"),
        (lambda e: e.step_fused(), "step_fused()"),
        (lambda e: e.decode_fused(), "decode_fused()"),
        (lambda e: e.attach_spec(object()), "attach_spec()"),
    ])
    def test_paged_entry_points_refuse(self, stack, call, path):
        engine = stack[1].engine
        with pytest.raises(ValueError, match="tiny-mla-scmoe") as err:
            call(engine)
        assert path in str(err.value) and "paged" in str(err.value)
        assert "models/mla_scmoe.py" in str(err.value)

    def test_the_profiler_books_ask_the_config(self):
        from k8s_llm_scheduler_tpu.observability.profiler import (
            attn_flops_per_token,
            matmul_flops_per_token,
        )

        cfg = get_config("tiny-mla-scmoe")
        d = cfg.d_model
        held_picks = 3 * 4 / 12  # top 3, 4 of the 8 + 4 router outputs held here
        layer = (2 * cfg.attn_params() + 2 * 3 * d * cfg.d_ff + d * 12
                 + held_picks * 3 * d * cfg.d_ff_expert)
        assert matmul_flops_per_token(cfg) == 2.0 * (cfg.n_layers * layer + d * cfg.vocab_size)
        assert attn_flops_per_token(cfg, 10) == 10 * 2.0 * (2 * cfg.n_layers) * cfg.n_heads * (2 * 16 + 8)
