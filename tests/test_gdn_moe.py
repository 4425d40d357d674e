"""models/gdn_moe.py at a toy size of the Qwen3-Next family on the CPU: two
periods of three gated-delta-rule layers and one gated attention, a
convolution whose window crosses every join, a per-sequence state beside the
per-token cache, a share of the routed experts and a gated shared expert,
against the plain reference the benchmark keeps
(benchmark/reference/gdn_moe.py: the recurrence token by token, float32 at
`highest`, nothing of the program imported). Seeded random weights; every
mechanism present, every width shrunk.
"""

from __future__ import annotations

import asyncio
import dataclasses
import importlib.util
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.models import family, gdn_moe, mla_moe
from k8s_llm_scheduler_tpu.models.configs import GdnMoeConfig, get_config

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"t_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(BENCH / "reference" / "gdn_moe.py")

# The toy, in the published key names (what a configuration file holds): two
# periods, a share of 4 of 16 experts, top 3.
TOY = {
    "name": "toy-gdn-moe", "hidden_size": 64, "num_hidden_layers": 8, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 16, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_scaling": None,
    "vocab_size": 512, "max_position_embeddings": 2048, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "experts_held": 4, "expert_first": 4,
}

# Program and reference both compute in float32 here and differ in the ORDER
# of their sums alone: the chunked form's solve and products against the
# recurrence's token-by-token updates, grouped experts against a loop. Read
# at this size: 1e-5 in logits of unit scale. A state that is lost, stale or
# seeded wrong moves them by tenths (the last test of this file).
TOL = 1e-3


def toy_cfg(dtype=jnp.float32, conf=TOY, **overrides) -> GdnMoeConfig:
    kw = dict(dtype=dtype, expert_first=conf["expert_first"], expert_count=conf["experts_held"])
    return GdnMoeConfig.from_hf(conf["name"], conf, **{**kw, **overrides})


def toy_params(cfg, seed=0):
    return jax.jit(lambda k: gdn_moe.init_params(k, cfg))(jax.random.PRNGKey(seed))


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------- the chunked delta rule
def _delta_rule_inputs(rng, lens, chunk, Hk, H, dk, dv, periods):
    B = len(lens)
    T = -(-max(max(lens), 1) // chunk) * chunk
    q, k = (gdn_moe._l2(jnp.asarray(rng.normal(size=(B, Hk, T, dk)), jnp.float32)) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(B, H, T, dv)), jnp.float32)
    ok = np.arange(T)[None, :] < np.asarray(lens)[:, None]
    g = jnp.where(ok[:, None], -jnp.asarray(rng.uniform(0.001, 0.5, size=(B, H, T)), jnp.float32), 0.0)
    beta = jnp.where(ok[:, None], jnp.asarray(rng.uniform(0, 1, size=(B, H, T)), jnp.float32), 0.0)
    member = jnp.asarray(rng.normal(size=(periods, B, H, dk, dv)), jnp.float32)
    return q, k, v, g, beta, ok, member


def _assert_is_the_recurrence(q, k, v, g, beta, ok, lens, member, period, chunk, atol=1e-5):
    """`gated_delta_chunks` (the XLA preamble and ops/gdn_scan.py's kernel,
    interpreted) against the reference's token-by-token scan, row by row."""
    o, new = gdn_moe.gated_delta_chunks(q, k, v, g, beta, jnp.asarray(lens, jnp.int32), member, period, chunk)
    q, k = (jnp.repeat(a, v.shape[1] // k.shape[1], axis=1) for a in (q, k))  # value head h reads key head h // rep
    for b, n in enumerate(lens):
        t = lambda a: jnp.moveaxis(a[b], 0, 1)  # noqa: E731  [H, T, ..] -> [T, H, ..]
        want_o, want_s = REF.delta_rule(t(q), t(k), t(v), t(g), t(beta), jnp.asarray(ok[b]), member[period, b])
        np.testing.assert_allclose(np.asarray(t(o))[:n], np.asarray(want_o)[:n], rtol=1e-4, atol=atol)
        np.testing.assert_allclose(np.asarray(new[period, b]), np.asarray(want_s), rtol=1e-4, atol=atol)
        if n == 0:  # no valid position: the row's state comes back to the bit
            np.testing.assert_array_equal(np.asarray(new[period, b]), np.asarray(member[period, b]))
    for p in range(member.shape[0]):  # the other periods' entries are not this call's to touch
        if p != period:
            np.testing.assert_array_equal(np.asarray(new[p]), np.asarray(member[p]))


# the last three: the cell's call shapes in small (a decode block as one chunk
# of 24 with ragged rows, one of them empty; a suffix call as two chunks of 64;
# a prefix prefill as many chunks for one row), on the middle entry of a member
@pytest.mark.parametrize("lens, chunk, key_heads, periods, period", [
    ((48, 48), 16, 4, 1, 0), ((48, 17), 16, 4, 1, 0), ((0, 5), 24, 4, 1, 0), ((24, 1), 24, 4, 1, 0),
    ((3, 0, 24, 1, 8), 24, 2, 3, 1), ((70, 128, 83), 64, 2, 3, 1), ((300,), 64, 2, 3, 1)])
def test_the_chunked_delta_rule_is_the_recurrence(lens, chunk, key_heads, periods, period):
    """`gated_delta_chunks` over whole chunks, against the reference's
    token-by-token scan: outputs at the valid positions and the state after
    them; a position that is not valid (g = 0, beta = 0) leaves the state as
    it was, so a row of length 0 keeps the state it came with; of a member
    of several periods only the entry named is advanced; a key head
    serves one value head or two."""
    rng = np.random.default_rng(3)
    q, k, v, g, beta, ok, member = _delta_rule_inputs(rng, lens, chunk, key_heads, 4, 16, 8, periods)
    _assert_is_the_recurrence(q, k, v, g, beta, ok, lens, member, period, chunk)


@pytest.mark.parametrize("lens, chunk", [((5, 2), 5), ((8, 1), 8), ((24, 9), 24), ((64, 33), 32), ((128,), 64)])
def test_the_kernel_is_the_recurrence_where_every_key_is_the_same(lens, chunk):
    """THE HARD CASE of the solve, through the whole chunked form and its
    kernel: every key of a row equal and beta one (a prompt that repeats
    itself), no decay: (I + A) is the all-ones lower triangle, its inverse
    is bounded by one, and the powers of A, which a Neumann series over the
    chunk would sum, reach 1e17 at 64 positions. The kernel's forward
    substitution, a column at a time in groups of 8 (chunks below, at and
    over a group; rows that end inside one), holds it. Equal keys make every
    product with the state a coherent sum, so the 16 bits that its three
    bfloat16 passes carry show: 9e-5 on entries of unit scale, where random
    keys read 1e-5."""
    rng = np.random.default_rng(4)
    q, k, v, g, beta, ok, member = _delta_rule_inputs(rng, lens, chunk, 2, 4, 16, 8, 1)
    k = jnp.broadcast_to(k[:, :, :1], k.shape)
    beta = jnp.where(ok[:, None], 1.0, 0.0).astype(jnp.float32) * jnp.ones_like(beta)
    _assert_is_the_recurrence(q, k, v, g * 0.0, beta, ok, lens, member, 0, chunk, atol=1e-4)


def test_the_convolution_and_the_state_cross_every_join():
    """One delta-rule mixer over a sequence in ONE call, against the same
    sequence in three calls (prefix | suffix | a decode block with padding
    behind its valid tokens), each seeded with the state and the window the
    call before it returned: the first tokens after a join see the last
    tokens before it through the convolution, and the pieces' outputs are
    the whole's."""
    cfg = toy_cfg()
    params = toy_params(cfg)
    lp = {k: a[1] for k, a in params["gdn"].items()}
    rng = np.random.default_rng(5)
    cuts = (37, 9, 5)   # prefix, suffix, decode block (8 wide, 5 valid)
    T = sum(cuts)
    u = jnp.asarray(rng.normal(size=(1, T, cfg.d_model)), jnp.float32)
    zero = gdn_moe.zero_state(cfg, 1)
    s0, w0 = zero[0][:1], zero[-1][0]  # one period's entry of a member; its window
    ones = lambda n: jnp.ones((1, n), bool)  # noqa: E731
    whole, s_whole, w_whole = gdn_moe.gdn_mixer(lp, cfg, u, ones(T), jnp.asarray([T]), s0, 0, w0)
    s, w, at, pieces = s0, w0, 0, []
    for n, width in zip(cuts, (64, 16, 8)):
        piece = jnp.zeros((1, width, cfg.d_model), jnp.float32).at[:, :n].set(u[:, at: at + n])
        y, s, w = gdn_moe.gdn_mixer(lp, cfg, piece, jnp.arange(width)[None] < n, jnp.asarray([n]), s, 0, w)
        pieces.append(y[:, :n])
        at += n
    np.testing.assert_allclose(np.asarray(jnp.concatenate(pieces, axis=1)), np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_whole), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_whole))  # the last three inputs, as they were
    # and without the window the first tokens behind a join differ
    y, _, _ = gdn_moe.gdn_mixer(lp, cfg, u[:, cuts[0]: cuts[0] + 8], ones(8), jnp.asarray([8]), s_whole * 0, 0, w0)
    assert float(jnp.max(jnp.abs(y[:, :3] - whole[:, cuts[0]: cuts[0] + 3]))) > 1e-2


# ---------------------------------------------------------- the wave, by hand
P, P_BUCKET, R, SS, F, CAP = 150, 256, 4, 128, 8, 32
SUFFIX_LENS = (70, 65, 83, 3)
BLOCKS = ((3, 1, 8, 0), (1, 8, 2, 5), (8, 8, 8, 8), (0, 3, 2, 7))  # valid tokens a row, per model call


class Wave:
    """Prefix prefill -> suffix prefill -> block decode in uneven steps, by
    hand through the three forwards; `logits[r]` holds row r's logits after
    its suffix and after every call that advanced it."""

    def __init__(self, cfg, params, lose_state: bool = False):
        rng = np.random.default_rng(0)
        self.prefix = rng.integers(1, 500, P).tolist()
        self.suffixes = [rng.integers(1, 500, n).tolist() for n in SUFFIX_LENS]
        self.served = [rng.integers(1, 500, sum(b[r] for b in BLOCKS)).tolist() for r in range(R)]
        tok = np.zeros((1, P_BUCKET), np.int32)
        tok[0, :P] = self.prefix
        _, pk, pv, state = jax.jit(gdn_moe.forward_prefill_kv, static_argnums=1)(
            params, cfg, jnp.asarray(tok), jnp.asarray([P]))
        pk, pv, self.prefix_state = pk[:, 0], pv[:, 0], tuple(a[:, 0] for a in state)
        if lose_state:
            state = tuple(jnp.zeros_like(a) for a in self.prefix_state)
        else:
            state = self.prefix_state
        stok = np.zeros((R, SS), np.int32)
        lens = np.asarray(SUFFIX_LENS, np.int32)
        for r, s in enumerate(self.suffixes):
            stok[r, : len(s)] = s
        logits, ks, vs, rows, self.suffix_counters = jax.jit(
            gdn_moe.forward_prefill_suffix_dense, static_argnums=1)(
            params, cfg, jnp.asarray(stok), jnp.asarray(lens), pk, pv, jnp.int32(P), state=state)
        self.logits = [[np.asarray(logits[r])] for r in range(R)]
        gk, gv = (jnp.zeros((cfg.n_attn_layers, R, CAP + F, *shape), cfg.dtype)
                  for shape in gdn_moe.cache_token_shapes(cfg))
        done = np.zeros(R, np.int32)
        decode = jax.jit(gdn_moe.forward_block_decode, static_argnums=1)
        self.decode_counters = []
        for blk in BLOCKS:
            blk = np.asarray(blk, np.int32)
            bt = np.zeros((R, F), np.int32)
            for r in range(R):
                bt[r, : blk[r]] = self.served[r][done[r]: done[r] + blk[r]]
            before = rows
            lg, gk, gv, rows, c = decode(
                params, cfg, jnp.asarray(bt), jnp.asarray(np.arange(F)[None, :] < blk[:, None]),
                jnp.asarray(blk), jnp.asarray((P + lens + done)[:, None] + np.arange(F)[None, :], jnp.int32),
                ks, vs, jnp.asarray(lens), gk, gv, jnp.asarray(done), pk, pv, jnp.int32(P), state=rows)
            for r in range(R):
                if blk[r]:
                    self.logits[r].append(np.asarray(lg[r]))
                else:  # a row the call held nothing for keeps its state, to the bit
                    for a, b in zip(before, rows):
                        np.testing.assert_array_equal(np.asarray(a[:, r]), np.asarray(b[:, r]))
            done += blk
            self.decode_counters.append(np.asarray(c))


def _gaps(wave, ref_logits):
    """Largest |program - reference| over the logits the wave kept."""
    worst, row = 0.0, 0
    for r in range(R):
        ends = np.concatenate([[0], np.cumsum([b[r] for b in BLOCKS])])
        for n, i in enumerate(sorted(set(ends.tolist()))):
            worst = max(worst, float(np.abs(ref_logits[row + i] - wave.logits[r][n]).max()))
        row += len(wave.served[r]) + 1
    return worst


@pytest.fixture(scope="module")
def toy():
    with jax.default_matmul_precision("highest"):
        cfg = toy_cfg()
        params = toy_params(cfg)
        wave = Wave(cfg, params)
        tails = [s + t for s, t in zip(wave.suffixes, wave.served)]
        spans = [(len(s) - 1, len(t) + 1) for s, t in zip(wave.suffixes, wave.served)]
        ref = REF.wave_logits(TOY, params, wave.prefix, tails, spans, "f32", 512)
    return cfg, params, wave, ref


def test_prefill_then_block_decode_is_the_references_full_forward(toy):
    """Prefix prefill, suffix prefill seeded from the prefix's state, then
    block decode in uneven `blk_len` steps (rows that sit a call out, rows
    that fill the block) through cache AND state, against the reference's
    one forward over prefix + suffix + served tokens."""
    cfg, _, wave, ref = toy
    assert float(np.std(ref)) > 0.5  # logits of unit scale: the tolerance means something
    assert _gaps(wave, ref) < TOL
    # the counters: every valid token once a call, the scan's width with padding
    names = gdn_moe.COUNTERS
    c = dict(zip(names, wave.suffix_counters))
    assert c["state_tokens_valid"] == sum(SUFFIX_LENS) and c["state_tokens_computed"] == R * SS
    assert c["moe_layer_calls"] == cfg.n_layers == c["moe_bounded_calls"]
    assert 0 < c["moe_assignments"] < sum(SUFFIX_LENS) * cfg.n_experts_per_tok * cfg.n_layers
    for blk, counters in zip(BLOCKS, wave.decode_counters):
        c = dict(zip(names, counters))
        assert c["state_tokens_valid"] == sum(blk) and c["state_tokens_computed"] == R * F


def test_a_lost_pin_state_fails_the_tolerance(toy):
    """The same wave with the prefix's state zeroed before the rows are
    seeded (what a pin that held the cache alone would serve): the logits
    leave the reference's by hundreds of tolerances, at the end of 65-83
    token suffixes and after them, so the comparison sees a state that is
    lost. (With the published init's decay a state forgets within a few
    tokens and this reads as sound: models/gdn_moe.py `init_params`.)"""
    cfg, params, _, ref = toy
    lost = Wave(cfg, params, lose_state=True)
    assert _gaps(lost, ref) > 100 * TOL


def test_init_is_the_references_leaf_for_leaf():
    """benchmark/tests/check_init.py's comparison, at the toy size."""
    cfg = toy_cfg(jnp.bfloat16)
    ours = toy_params(cfg, seed=7)
    theirs = REF.init_weights(TOY, 7)
    flat = dict(jax.tree_util.tree_leaves_with_path(theirs))
    for path, leaf in jax.tree_util.tree_leaves_with_path(ours):
        assert leaf.dtype == flat[path].dtype and leaf.shape == flat[path].shape, path
        np.testing.assert_array_equal(np.asarray(leaf, np.float32), np.asarray(flat[path], np.float32))
    decay = np.exp(np.asarray(ours["gdn"]["A_log"])) * np.log1p(np.exp(np.asarray(ours["gdn"]["dt_bias"])))
    assert 1e-6 < decay.min() and decay.max() < 1.6  # A dt: a step of 1e-3..1e-1 times (0, 16)


# ---------------------------------------------------------------- the shares
def test_four_shares_of_four_experts_and_the_shared_expert_once_are_the_uncut_layer():
    """An expert-parallel share holds 4 of 16 experts, routes over all 16
    and computes its own experts' part; the four shares' routed parts with
    the gated shared expert counted ONCE are the uncut layer, the program's
    and the reference's, and every assignment is computed by exactly one
    share."""
    conf = {**TOY, "experts_held": 16, "expert_first": 0}
    cfg = toy_cfg(conf=conf)
    layers = toy_params(cfg)["layers"]
    idx = jnp.int32(1)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(1, 24, cfg.d_model)), jnp.float32)
    valid = jnp.ones((1, 24), bool)
    whole, counters = gdn_moe._sparse_block(layers, idx, cfg, x, valid)
    assert int(counters[0]) == 24 * 3 and int(counters[4]) == 1  # holds all: every call within its bound
    want = REF._sparse_block(x[0], layers, idx, 3, True, cfg.rms_eps, "f32")
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(want), rtol=1e-4, atol=2e-5)

    h = gdn_moe._norm(x, layers["mlp_norm"][1], cfg.rms_eps)[0]
    parts, assigned = 0.0, 0
    for share in range(4):
        cut = dataclasses.replace(cfg, expert_first=4 * share, expert_count=4)
        lp = {"router": layers["router"][1], "layer": idx,
              **{k: layers[k][:, 4 * share: 4 * share + 4] for k in mla_moe.EXPERT_LEAVES}}
        part, c = mla_moe.routed_experts(lp, cut, h, valid[0])
        parts, assigned = parts + part, assigned + int(c[0])
        assert c.shape == (5,)  # a share has the short path: BOUND_COUNTERS behind the four
        ref_part = REF._sparse_block(
            x[0], {**layers, **{k: lp[k] for k in mla_moe.EXPERT_LEAVES}}, idx, 3, True, cfg.rms_eps,
            "f32", first=4 * share, shared=False)
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part), rtol=1e-4, atol=2e-5)
    assert assigned == 24 * 3
    shared_once = want - REF._sparse_block(x[0], layers, idx, 3, True, cfg.rms_eps, "f32", shared=False)
    np.testing.assert_allclose(np.asarray(parts + shared_once), np.asarray(whole[0]), rtol=1e-4, atol=2e-5)


def test_the_registered_toy_is_the_hand_written_one():
    cfg = get_config("tiny-gdn-moe")
    assert family(cfg) is gdn_moe
    assert (cfg.n_periods, cfg.n_gdn_layers, cfg.n_attn_layers, cfg.rotary_dim) == (2, 6, 2, 8)
    assert gdn_moe.cache_layers(cfg) == 2 and gdn_moe.state_layers(cfg) == 2
    members = gdn_moe.state_shapes(cfg)  # S of the period's three delta-rule layers, then their windows
    assert [m[0] for m in members] == [(4, 16, 16)] * 3 + [(3, 2 * 32 + 64)] * 3
    assert all(m[1] == jnp.float32 for m in members)
    for other in ("tiny", "tiny-mla-moe", "tiny-mla-scmoe"):  # nothing to carry: their programs do not change
        assert family(get_config(other)).state_shapes(get_config(other)) == ()


def test_the_lowered_forwards_hold_the_scopes_and_kernel_names():
    """What benchmark/metrics/ reads by name is in the program text."""
    cfg = toy_cfg(jnp.bfloat16)
    params = jax.eval_shape(lambda k: gdn_moe.init_params(k, cfg), jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    cache = lambda *lead: tuple(  # noqa: E731
        jax.ShapeDtypeStruct((cfg.n_attn_layers, *lead, *s), cfg.dtype) for s in gdn_moe.cache_token_shapes(cfg))
    state = lambda *lead: tuple(  # noqa: E731
        jax.ShapeDtypeStruct((gdn_moe.state_layers(cfg), *lead, *s), d) for s, d in gdn_moe.state_shapes(cfg))
    prefix = jax.jit(gdn_moe.forward_prefill_kv, static_argnums=1).lower(
        params, cfg, i32(1, 256), i32(1)).as_text(debug_info=True)
    suffix = jax.jit(gdn_moe.forward_prefill_suffix_dense, static_argnums=1).lower(
        params, cfg, i32(R, SS), i32(R), *cache(256), i32(), state=state()).as_text(debug_info=True)
    decode = jax.jit(gdn_moe.forward_block_decode, static_argnums=1).lower(
        params, cfg, i32(R, F), jax.ShapeDtypeStruct((R, F), jnp.bool_), i32(R), i32(R, F),
        *cache(R, SS), i32(R), *cache(R, CAP + F), i32(R), *cache(256), i32(), state=state(R)
    ).as_text(debug_info=True)
    # (a share's experts and combine lie inside the short path's `cond`: mlp/cond/branch_*/moe_experts)
    for scope in ("attn/gdn/gdn_proj/", "attn/gdn/gdn_conv/", "attn/gdn/gdn_scan/", "attn/gdn/gdn_out/",
                  "attn/gdn/state_writeback/", "attn/full_attn/", "mlp/moe_router/", "mlp/moe_dispatch/",
                  "/moe_experts/", "/moe_combine/", "mlp/moe_shared/", "lm_head/", "embed/"):
        assert scope in suffix and scope in decode, scope
    assert "state_seed" in suffix and "state_seed" not in decode
    assert "kv_writeback" in decode
    for kernel in ("moe_grouped_swiglu", "moe_grouped_matmul"):
        assert kernel in decode, kernel
    # the delta rule's kernel, under the scope gdn_scan_device_ms_per_bind.tput reads, in all three forwards
    for name, text in (("prefix", prefix), ("suffix", suffix), ("decode", decode)):
        assert re.search(r"attn/gdn/gdn_scan/[^\"]*gdn_chunk_scan", text), name
        assert "prefix_prefill/" in text if name == "prefix" else "prefix_prefill/" not in text


def test_a_suffix_call_leaves_the_pins_state_bit_identical(toy):
    """The kernel updates a state where it lies, and the rows' state it is
    handed in the suffix call is their own copy (`state_seed`): the prefix's
    arrays, which a pin holds for every later wave, still hold the same
    bits after a wave has been seeded from them, and are still alive."""
    cfg, params, wave, _ = toy
    held = wave.prefix_state
    before = [np.asarray(a).copy() for a in held]
    pk = jnp.zeros((cfg.n_attn_layers, P_BUCKET, *gdn_moe.cache_token_shapes(cfg)[0]), cfg.dtype)
    tokens = jnp.ones((R, SS), jnp.int32)
    out = jax.jit(gdn_moe.forward_prefill_suffix_dense, static_argnums=1)(
        params, cfg, tokens, jnp.asarray(SUFFIX_LENS, jnp.int32), pk, pk, jnp.int32(P), state=held)
    rows = out[3]
    assert any(float(jnp.max(jnp.abs(r[:, 0] - h))) > 1e-3 for r, h in zip(rows, held))  # the rows moved on
    for mine, theirs in zip(before, held):
        assert not theirs.is_deleted()
        np.testing.assert_array_equal(mine, np.asarray(theirs))


# --------------------------------------------------------- a whole decision
@pytest.fixture(scope="module")
def stack():
    import chip_smoke
    from k8s_llm_scheduler_tpu.cli import _build_stack
    from k8s_llm_scheduler_tpu.testing import synthetic_cluster

    cfg = chip_smoke.smoke_config(model="tiny-gdn-moe", bpe_fixture=False)
    cluster = synthetic_cluster(3)
    scheduler, backend = _build_stack(cfg, cluster)
    yield scheduler, backend, cluster
    backend.close()


def test_scheduler_run_binds_pods_from_the_model(stack):
    """`cli._build_stack` -> `Scheduler.run()` -> LocalLLMBackend ->
    submit_wave / harvest_wave on the toy, the path the other three families
    take: pods are bound by the model's decisions, the prefix the engine
    holds is a cache of the attention layers AND the state of the delta-rule
    layers, and every counter came back with the harvest."""
    import chip_smoke
    from k8s_llm_scheduler_tpu.testing import pod_burst

    scheduler, backend, cluster = stack
    engine = backend.engine
    assert family(engine.cfg) is gdn_moe and not engine.paged
    burst = pod_burst(6, distinct_shapes=6)
    asyncio.run(chip_smoke._serve(scheduler, cluster, burst, timeout_s=100.0))
    stats = scheduler.get_stats()
    assert cluster.bind_count == 6
    assert stats["llm_decisions"] == 6 and stats["fallback_decisions"] == 0
    cfg = engine.cfg
    pfx = engine._prefix
    assert pfx.k.shape == (cfg.n_attn_layers, pfx.k.shape[1], cfg.n_kv_heads, cfg.head_dim)
    assert [a.shape for a in pfx.state] == [(cfg.n_periods, *s) for s, _ in gdn_moe.state_shapes(cfg)]
    assert pfx.nbytes == sum(a.nbytes for a in (*pfx.kv, *pfx.state))
    assert float(jnp.max(jnp.abs(pfx.state[0]))) > 0
    es = backend.get_stats()
    assert es["waves"] >= 1 and es["state_seeds"] >= 6
    assert es["moe_layer_calls"] == cfg.n_layers * (es["wave_model_calls"] + es["waves"])
    assert es["moe_bounded_calls"] == es["moe_layer_calls"]
    assert 0 < es["state_tokens_valid"] <= es["state_tokens_computed"]
    assert es["state_tokens_valid"] >= es["decode_tokens"]


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
        with pytest.raises(ValueError, match="tiny-gdn-moe") as err:
            build_local_backend("tiny-gdn-moe", compile_cache_dir=None, **kwargs)
        assert path in str(err.value) and "not served" in str(err.value)
        assert time.perf_counter() - t0 < 30

    def test_ragged_decode_refuses_in_the_forward_too(self):
        cfg = toy_cfg()
        with pytest.raises(ValueError, match="gdn_moe.py"):
            gdn_moe.forward_block_decode(None, cfg, *([jnp.zeros((1, 1), jnp.int32)] * 13),
                                         ragged=True, state=())

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
        with pytest.raises(ValueError, match="tiny-gdn-moe") as err:
            call(engine)
        assert path in str(err.value) and "per-sequence state" in str(err.value)
        assert "models/gdn_moe.py" in str(err.value)

    @pytest.mark.parametrize("call, path", [
        (lambda e: e.export_prefix_kv((1, 2, 3)), "export_prefix_kv()"),
        (lambda e: e.adopt_prefix_pages([1, 2, 3], jnp.zeros((1,)), jnp.zeros((1,))), "adopt_prefix_pages()"),
    ])
    def test_the_prefix_plane_refuses_a_pin_that_is_not_k_and_v(self, stack, call, path):
        engine = stack[1].engine
        with pytest.raises(ValueError, match="tiny-gdn-moe") as err:
            call(engine)
        assert path in str(err.value) and "state" in str(err.value) and "models/gdn_moe.py" in str(err.value)

    def test_the_profiler_books_ask_the_config(self):
        from k8s_llm_scheduler_tpu.observability.profiler import (
            attn_flops_per_token,
            matmul_flops_per_token,
        )

        cfg = get_config("tiny-gdn-moe")
        d = cfg.d_model
        gdn = d * (2 * 32 + 2 * 64) + d * 8 + 64 * d
        attn = d * 4 * 64 + 2 * d * 2 * 32 + 4 * 32 * d
        moe = d * 16 + (3 * 4 / 16) * 3 * d * 32 + 3 * d * 32 + d  # top 3, 4 of 16 held here
        state = 3 * 2.0 * 4 * 16 * 16
        assert matmul_flops_per_token(cfg) == (
            2.0 * (6 * gdn + 2 * attn + 8 * moe + d * cfg.vocab_size) + 6 * state)
        assert attn_flops_per_token(cfg, 10) == 10 * 4.0 * 2 * 4 * 32  # the two layers that attend
