"""models/cohere2_moe.py at a toy size of the Command A+ family on the CPU:
two periods of three window layers and one global layer without position
encoding, a window of 32 that the prompts outrun (prefix, suffix and decode
all reach past it), parallel blocks, a share of the routed experts (4 of
16), four averaged shared experts and the tied head, against the plain
reference the benchmark keeps (benchmark/reference/cohere2_moe.py: one
softmax over the whole sequence with the window in its mask, float32 at
`highest`, nothing of the program imported). Seeded random weights; every
mechanism present, every width shrunk.
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

from k8s_llm_scheduler_tpu.models import cohere2_moe, family
from k8s_llm_scheduler_tpu.models.configs import Cohere2MoeConfig, get_config
from k8s_llm_scheduler_tpu.ops.attention import (
    attend_part,
    block_prefix_widths,
    merge_attention_parts,
    prefix_attend_parts,
    window_prefix_keys_read,
)
from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import window_key_blocks, window_prefix_attention

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"t_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(BENCH / "reference" / "cohere2_moe.py")

# The toy, in the published key names (what a configuration file holds).
TOY = {
    "name": "toy-cohere2-moe", "hidden_size": 64, "num_hidden_layers": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2, "sliding_window": 32,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 32,
    "num_experts": 16, "num_shared_experts": 4, "num_experts_per_tok": 4, "experts_held": 4, "expert_first": 4,
    "norm_topk_prob": True, "logit_scale": 0.5, "vocab_size": 512, "max_position_embeddings": 2048,
    "rope_theta": 10000, "layer_norm_eps": 1e-5, "tie_word_embeddings": True, "use_parallel_block": True,
    "shared_expert_combination_strategy": "average", "expert_selection_fn": "sigmoid", "use_qk_norm": False,
    "first_k_dense_replace": 0, "attention_bias": False, "rotary_pct": 1, "hidden_act": "silu",
    "use_gated_activation": True, "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
}

# Program and reference both compute in float32 here and differ in the ORDER
# of their sums alone (flash parts merged against one softmax, the grouped
# experts against a loop over them): read at this size, 5e-6 of the logits'
# scale. A window that did not bind (every key seen) moves them by seven
# times the scale (test_a_window_that_does_not_bind_fails_the_tolerance).
TOL = 1e-3


def toy_cfg(dtype=jnp.float32, conf=TOY, **overrides) -> Cohere2MoeConfig:
    return Cohere2MoeConfig.from_hf(conf["name"], conf, **{
        "dtype": dtype, "expert_first": conf["expert_first"], "expert_count": conf["experts_held"], **overrides})


def toy_params(cfg, seed=0):
    return jax.jit(lambda k: cohere2_moe.init_params(k, cfg))(jax.random.PRNGKey(seed))


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ---------------------------------------------------------- the wave, by hand
P, P_BUCKET, R, SS, F, CAP = 150, 256, 4, 128, 8, 32
SUFFIX_LENS = (70, 45, 20, 3)
BLOCKS = ((3, 1, 8, 0), (1, 8, 2, 5), (8, 8, 8, 8), (0, 3, 2, 7))  # valid tokens a row, per model call


class Wave:
    """Prefix prefill -> suffix prefill -> block decode in uneven steps, by
    hand through the three forwards; `logits[r]` holds row r's logits after
    its suffix and after every call that advanced it."""

    def __init__(self, cfg, params, impl=None):
        rng = np.random.default_rng(0)
        self.prefix = rng.integers(1, 500, P).tolist()
        self.suffixes = [rng.integers(1, 500, n).tolist() for n in SUFFIX_LENS]
        self.served = [rng.integers(1, 500, sum(b[r] for b in BLOCKS)).tolist() for r in range(R)]
        tok = np.zeros((1, P_BUCKET), np.int32)
        tok[0, :P] = self.prefix
        _, pk, pv = jax.jit(cohere2_moe.forward_prefill_kv, static_argnums=1)(
            params, cfg, jnp.asarray(tok), jnp.asarray([P]))
        pk, pv = pk[:, 0], pv[:, 0]
        stok = np.zeros((R, SS), np.int32)
        lens = np.asarray(SUFFIX_LENS, np.int32)
        for r, s in enumerate(self.suffixes):
            stok[r, : len(s)] = s
        logits, ks, vs, self.suffix_counters = jax.jit(
            cohere2_moe.forward_prefill_suffix_dense, static_argnums=(1, 7))(
            params, cfg, jnp.asarray(stok), jnp.asarray(lens), pk, pv, jnp.int32(P), impl)
        self.logits = [[np.asarray(logits[r])] for r in range(R)]
        gk, gv = (jnp.zeros((cfg.n_layers, R, CAP + F, *shape), cfg.dtype)
                  for shape in cohere2_moe.cache_token_shapes(cfg))
        done = np.zeros(R, np.int32)
        decode = jax.jit(cohere2_moe.forward_block_decode, static_argnums=(1, 15))
        self.decode_counters, self.positions = [], []
        for blk in BLOCKS:
            blk = np.asarray(blk, np.int32)
            bt = np.zeros((R, F), np.int32)
            for r in range(R):
                bt[r, : blk[r]] = self.served[r][done[r]: done[r] + blk[r]]
            pos = (P + lens + done)[:, None] + np.arange(F)[None, :]
            lg, gk, gv, c = decode(
                params, cfg, jnp.asarray(bt), jnp.asarray(np.arange(F)[None, :] < blk[:, None]),
                jnp.asarray(blk), jnp.asarray(pos, jnp.int32),
                ks, vs, jnp.asarray(lens), gk, gv, jnp.asarray(done), pk, pv, jnp.int32(P), impl)
            for r in range(R):
                if blk[r]:
                    self.logits[r].append(np.asarray(lg[r]))
            done += blk
            self.decode_counters.append(np.asarray(c))
            self.positions.append((pos, np.arange(F)[None, :] < blk[:, None]))


def _gaps(wave, ref_logits):
    """Largest |program - reference| over the logits the wave kept."""
    worst, row = 0.0, 0
    for r in range(R):
        ends = np.concatenate([[0], np.cumsum([b[r] for b in BLOCKS])])
        for n, i in enumerate(sorted(set(ends.tolist()))):
            worst = max(worst, float(np.abs(ref_logits[row + i] - wave.logits[r][n]).max()))
        row += len(wave.served[r]) + 1
    return worst


def _reference(wave, conf=TOY, params=None):
    tails = [s + t for s, t in zip(wave.suffixes, wave.served)]
    spans = [(len(s) - 1, len(t) + 1) for s, t in zip(wave.suffixes, wave.served)]
    return REF.wave_logits(conf, params, wave.prefix, tails, spans, "f32", 512)


@pytest.fixture(scope="module")
def toy():
    with jax.default_matmul_precision("highest"):
        cfg = toy_cfg()
        params = toy_params(cfg)
        wave = Wave(cfg, params)
        ref = _reference(wave, params=params)
    return cfg, params, wave, ref


def test_prefill_then_block_decode_is_the_references_full_forward(toy):
    """Prefix prefill, suffix prefill, then block decode in uneven `blk_len`
    steps (rows that sit a call out, rows that fill the block) through the
    caches, against the reference's one forward over prefix + suffix +
    served tokens. The window (32) binds in every piece: a 150-token prefix,
    suffixes of up to 70 tokens, decode 70-90 tokens behind the prefix."""
    cfg, _, wave, ref = toy
    scale = float(np.std(ref))
    assert scale > 0.05  # logits of the table's scale, not zeros: the tolerance means something
    assert _gaps(wave, ref) < TOL * scale
    # the counters: the routed layer's, then, over the call's valid queries, the prefix keys a
    # window layer's attention reads (here the einsum's whole 256-token buffer) and a causal one's
    names = cohere2_moe.COUNTERS
    c = dict(zip(names, wave.suffix_counters))
    assert (c["window_keys_read"], c["window_keys_causal"]) == (sum(SUFFIX_LENS) * P_BUCKET, sum(SUFFIX_LENS) * P)
    assert c["moe_layer_calls"] == cfg.n_layers and c["moe_bounded_calls"] == cfg.n_layers
    assert 0 < c["moe_assignments"] <= sum(SUFFIX_LENS) * cfg.n_experts_per_tok * cfg.n_layers
    assert (c["block_prefix_positions"], c["block_prefix_positions_full"]) == (0, 0)
    for (p, ok), counters, blk in zip(wave.positions, wave.decode_counters, BLOCKS):
        c = dict(zip(names, counters))
        assert (c["window_keys_read"], c["window_keys_causal"]) == (int(ok.sum()) * P_BUCKET, int(ok.sum()) * P)
        w = min(w for w in block_prefix_widths(F) if w >= max(blk))
        assert (c["block_prefix_positions"], c["block_prefix_positions_full"]) == (R * w, R * F)


def test_the_windowed_kernel_serves_the_same_wave(toy, monkeypatch):
    """The same wave with the prefix parts of suffix and decode calls through
    ops/pallas_prefix_attention.py `window_prefix_attention` (interpreted):
    the kernel's products take bf16 operands with float32 accumulation, so
    the tolerance is bf16's, 3% of the logits' scale (read: 0.4%). W_q is
    drawn at unit gain here: at the toy's 16-wide heads the sharpened draw
    (`Q_GAIN`, chosen at the published 128) turns bf16's rounding of the
    scores alone into ~20% of the logits' scale, in any kernel."""
    cfg = toy[0]
    monkeypatch.setattr(cohere2_moe, "Q_GAIN", 1.0)
    monkeypatch.setattr(REF, "Q_GAIN", 1.0)
    params = toy_params(cfg)
    wave = Wave(cfg, params, impl="pallas")
    ref = _reference(wave, params=params)
    assert _gaps(wave, ref) < 3e-2 * float(np.std(ref))


def test_a_window_that_does_not_bind_fails_the_tolerance(toy):
    """The reference with a window wider than every sequence (every layer
    sees every key) is another answer, by far more than the tolerance: the
    comparison sees whether the window is applied."""
    cfg, params, wave, ref = toy
    wide = _reference(wave, {**TOY, "sliding_window": 4096}, params)
    assert float(np.max(np.abs(wide - ref))) > 100 * TOL * float(np.std(ref))


# --------------------------------------------- the chunked prefix and its seed
def _engine(cfg, params, chunk):
    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine

    page = min(chunk, 64)
    return InferenceEngine(params, cfg, num_pages=4, page_size=page, max_slots=2, max_pages_per_seq=4,
                           prefill_buckets=(chunk,), prefix_chunk=chunk)


def _fresh(cfg, params, ids):
    n = len(ids)
    tok = np.zeros((1, -(-n // 64) * 64), np.int32)
    tok[0, :n] = ids
    _, k, v = jax.jit(cohere2_moe.forward_prefill_kv, static_argnums=1)(params, cfg, jnp.asarray(tok),
                                                                        jnp.asarray([n]))
    return k[:, 0, :n], v[:, 0, :n]


# chunks narrower and wider than the window (32): a chunk starting at s sees the
# prefix from s - 31 on, and a wide chunk's own tokens through the window too
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_prefix_with_and_without_an_lcp_seed_is_a_fresh_prefill(toy, chunk):
    """`_prefill_prefix_chunked` over 200 tokens, fresh and seeded from a
    cached prompt that shares its first 117 (a seed off the chunk grid, the
    resume's first chunk reaching past the prompt), against one prefill of
    the whole prompt: every layer's k and v at every position."""
    cfg, params, _, _ = toy
    rng = np.random.default_rng(9)
    a = rng.integers(1, 500, 200).tolist()
    b = a[:117] + rng.integers(1, 500, 83).tolist()
    eng = _engine(cfg, params, chunk)
    bufs_a, _ = eng._prefill_prefix_chunked(a)
    bufs_b, _ = eng._prefill_prefix_chunked(b, seed=(bufs_a, 117))
    for ids, bufs in ((a, bufs_a), (b, bufs_b)):
        want_k, want_v = _fresh(cfg, params, ids)
        np.testing.assert_allclose(np.asarray(bufs[0][:, : len(ids)]), np.asarray(want_k), atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(bufs[1][:, : len(ids)]), np.asarray(want_v), atol=2e-5, rtol=1e-4)
    # the seeded tokens are the cached prompt's, to the bit
    np.testing.assert_array_equal(np.asarray(bufs_b[0][:, :117]), np.asarray(bufs_a[0][:, :117]))


def test_the_lcp_seed_is_one_program_whatever_the_reuse(toy):
    """Two seeds of different reuse lengths at one buffer shape trace and
    compile one program (the copy's length is a traced scalar), and a
    chunked prefill seeded at a third length compiles nothing new."""
    cfg, params, _, _ = toy
    eng = _engine(cfg, params, 64)
    rng = np.random.default_rng(4)
    a = rng.integers(1, 500, 200).tolist()
    bufs_a, _ = eng._prefill_prefix_chunked(a)
    for reuse in (70, 133):
        eng._lcp_seed(eng._prefix_buffers(bufs_a[0].shape[1]), bufs_a, jnp.int32(reuse))
    assert eng._lcp_seed._cache_size() == 1

    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiled.append(kw.get("fun_name")) if name.endswith("backend_compile_duration")
        else None)
    eng._prefill_prefix_chunked(a[:99] + rng.integers(1, 500, 101).tolist(), seed=(bufs_a, 99))
    assert "lcp_seed" not in compiled and eng._lcp_seed._cache_size() == 1


# ------------------------------------------------------------ the window kernel
def _kernel_inputs(rng, B, S, plen, window, Sp=1152):
    H, Hkv, hd = 8, 2, 128
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(Sp, Hkv, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(Sp, Hkv, hd)), jnp.bfloat16)
    offsets = rng.integers(0, 40, size=(B, 1)) + np.arange(S)[None, :]   # rows at their own positions
    lo = jnp.asarray(plen + offsets - (window - 1), jnp.int32)
    qg = (q.astype(jnp.float32) * hd**-0.5).reshape(B, S, Hkv, H // Hkv, hd)
    return q, qg, k, v, lo


# the prefix is 1,152 keys in three blocks of 384; the window's lower edge
# (plen + offset - window + 1) falls mid-block (701 + ..), on a block's start
# (768: 1,000 - 233 + 1) and below the prefix's start (a prefix shorter than
# the window: every key seen)
@pytest.mark.parametrize("B, S, plen, window, offset", [
    (2, 16, 1000, 300, None), (3, 8, 1000, 233, 0), (2, 8, 200, 600, None)])
def test_the_window_kernel_is_the_masked_einsum(B, S, plen, window, offset):
    """`window_prefix_attention` (interpreted) against the plain einsum with
    the window in its mask, on (o, m, l) merged: bf16 operands in the
    kernel, float32 in the einsum, so bf16's tolerance."""
    rng = np.random.default_rng(B * 100 + S)
    q, qg, k, v, lo = _kernel_inputs(rng, B, S, plen, window)
    if offset is not None:
        lo = jnp.asarray(plen - (window - 1) + np.arange(S)[None, :].repeat(B, 0), jnp.int32)
    got = merge_attention_parts([window_prefix_attention(q, k, v, jnp.int32(plen), lo, window=window,
                                                         interpret=True)])
    j = jnp.arange(k.shape[0])
    mask = (j < plen) & (j >= lo[:, :, None])
    want = merge_attention_parts([attend_part(qg, k, v, mask[:, None, None], "bqkgh,skh->bkgqs")])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2, rtol=2e-2)
    # the dispatch: "pallas" takes the kernel, "xla" the einsum, with one answer
    via = merge_attention_parts([prefix_attend_parts(q, qg, k, v, jnp.int32(plen), impl="xla", window=(window, lo))])
    np.testing.assert_allclose(np.asarray(via), np.asarray(want), atol=1e-6)


def test_the_window_kernel_never_visits_a_block_below_the_window():
    """The kernel's grid walks `window_key_blocks` key blocks a query block,
    from the first its lowest row sees: 2 of the prefix's 3 blocks of 384
    for a window of 300, and its key blocks' index map starts there. Read
    from the lowered call, not from the mask."""
    rng = np.random.default_rng(1)
    q, _, k, v, lo = _kernel_inputs(rng, 2, 16, 1000, 300)
    jaxpr = jax.make_jaxpr(lambda *a: window_prefix_attention(*a, window=300, interpret=True))(
        q, k, v, jnp.int32(1000), lo)

    def calls(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    grid = call.params["grid_mapping"].grid
    assert grid[2] == window_key_blocks(300, 384, 3) == 2 and grid[2] < 1152 // 384
    assert call.params["name"] == "window_prefix_attention"
    assert not call.params["name"].startswith("flash_prefix_attention_parts")
    assert window_key_blocks(4096, 1024, 12) == 5   # the cell: 5 of a 12,288-token buffer's 12
    # the window counter counts the keys the grid visits
    assert window_prefix_keys_read(q.shape, k.shape, 300, "pallas") == grid[2] * 384


@pytest.mark.parametrize("impl, cap, read", [
    ("pallas", 12288, 5 * 1024), ("pallas", 10240, 5 * 1024), ("pallas", 2048, 2048),
    ("xla", 12288, 12288), ("xla", 10240, 10240)])
def test_the_window_counter_reads_what_the_attention_reads(impl, cap, read):
    """`window_keys_read` counts a query's prefix keys as the attention
    reads them: the kernel's key blocks (5 of 1,024 at the cell, whatever the
    buffer beyond them), or the whole buffer, which the einsum masks."""
    assert window_prefix_keys_read((8, 24, 128, 128), (cap, 8, 128), 4096, impl) == read


# ----------------------------------------------------------- the share test
def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts (4 experts each, through the program's
    `routed_experts` at expert_first 0, 4, 8, 12), plus what every share
    computes alike counted once (the attention, the shared experts' mean),
    add up to the uncut reference's layer over all 16 experts."""
    from k8s_llm_scheduler_tpu.models.mla_moe import routed_experts

    whole_conf = {**TOY, "experts_held": 16, "expert_first": 0}
    cfg = toy_cfg(conf=whole_conf)
    params = toy_params(cfg, seed=3)
    layers = params["layers"]
    rng = np.random.default_rng(2)
    T = 64   # a whole attention block of the reference's
    x = jnp.asarray(rng.normal(size=(T, cfg.d_model)), jnp.float32)
    pos = jnp.arange(T, dtype=jnp.int32)
    seg = jnp.zeros((T,), jnp.int32)
    l = 1   # a window layer
    want = REF.layer(whole_conf, layers, l, x, pos, seg, "f32")

    h = cohere2_moe.layer_norm(x, layers["attn_norm"][l], cfg.norm_eps)
    attn = REF.layer(whole_conf, layers, l, x, pos, seg, "f32", routed=False, shared=False) - x
    shared = REF.layer(whole_conf, layers, l, x, pos, seg, "f32", routed=False) - x - attn
    routed = 0.0
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, expert_first=first, expert_count=4)
        lp = {"router": layers["router"][l], "layer": jnp.int32(l),
              **{k: layers[k][:, first: first + 4] for k in ("we_gate", "we_up", "we_down")}}
        y, _ = routed_experts(lp, share, h, jnp.ones((T,), bool))
        routed = routed + y
    np.testing.assert_allclose(np.asarray(x + attn + shared + routed), np.asarray(want), atol=2e-5, rtol=1e-5)
    # a share alone is not the layer
    assert float(jnp.max(jnp.abs(x + attn + shared + y - want))) > 1e-3


# ------------------------------------------------------------ config, init
def test_init_is_the_references_leaf_for_leaf():
    """benchmark/tests/check_init.py's comparison, at the toy size."""
    cfg = toy_cfg(jnp.bfloat16)
    ours = toy_params(cfg, seed=7)
    theirs = REF.init_weights(TOY, 7)
    flat = dict(jax.tree_util.tree_leaves_with_path(theirs))
    assert len(flat) == len(jax.tree_util.tree_leaves(ours))
    for path, leaf in jax.tree_util.tree_leaves_with_path(ours):
        assert leaf.dtype == flat[path].dtype and leaf.shape == flat[path].shape, path
        np.testing.assert_array_equal(np.asarray(leaf, np.float32), np.asarray(flat[path], np.float32))


def test_the_registered_toy_is_the_hand_written_one():
    cfg = get_config("tiny-cohere2-moe")
    assert family(cfg) is cohere2_moe
    assert cfg == dataclasses.replace(toy_cfg(jnp.bfloat16), name="tiny-cohere2-moe", expert_first=0)
    assert (cfg.period, cfg.global_position, cfg.n_periods, cfg.n_window_layers) == (4, 3, 2, 6)
    assert (cfg.d_ff_shared, cfg.shared_scale, cfg.experts_held) == (128, 0.25, 4)
    assert cohere2_moe.cache_layers(cfg) == 8 and cohere2_moe.state_shapes(cfg) == ()


@pytest.mark.parametrize("change, what", [
    ({"shared_expert_combination_strategy": "sum"}, "shared_expert_combination_strategy"),
    ({"use_qk_norm": True}, "use_qk_norm"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"attention_bias": True}, "attention_bias"),
    ({"rotary_pct": 0.5}, "rotary_pct"),
    ({"use_parallel_block": False}, "use_parallel_block"),
    ({"expert_selection_fn": "softmax"}, "expert_selection_fn"),
    ({"rope_parameters": {"rope_theta": 10000, "rope_type": "yarn"}}, "rope scaling"),
    ({"layer_types": ["sliding_attention"] * 3 + ["full_attention"] + ["full_attention"] * 4}, "whole period"),
    ({"layer_types": ["sliding_attention", "chunked_attention"] * 4}, "layer_types"),
    ({"tie_word_embeddings": False}, "tied output head"),
])
def test_from_hf_refuses_what_it_does_not_run(change, what):
    with pytest.raises(ValueError, match="toy-cohere2-moe") as err:
        toy_cfg(conf={**TOY, **change})
    assert what in str(err.value)


def test_the_published_layer_types_are_read_to_the_depth_run():
    """The configuration keeps the published 32 entries; the first four,
    one period, are run."""
    conf = {**TOY, "num_hidden_layers": 4, "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8}
    cfg = toy_cfg(conf=conf)
    assert cfg.n_layers == 4 and cfg.global_layers == (3,) and cfg.n_periods == 1


def test_the_lowered_forwards_hold_the_scopes_the_readers_ask_for():
    """What benchmark/metrics/ reads by name is in the program text."""
    cfg = toy_cfg(jnp.bfloat16)
    model = cohere2_moe
    params = jax.eval_shape(lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    cache = lambda *lead: tuple(  # noqa: E731
        jax.ShapeDtypeStruct((cfg.n_layers, *lead, *s), cfg.dtype) for s in model.cache_token_shapes(cfg))
    suffix = jax.jit(model.forward_prefill_suffix_dense, static_argnums=1).lower(
        params, cfg, i32(R, SS), i32(R), *cache(256), i32()).as_text(debug_info=True)
    decode = jax.jit(model.forward_block_decode, static_argnums=1).lower(
        params, cfg, i32(R, F), jax.ShapeDtypeStruct((R, F), jnp.bool_), i32(R), i32(R, F),
        *cache(R, SS), i32(R), *cache(R, CAP + F), i32(R), *cache(256), i32()).as_text(debug_info=True)
    for scope in ("attn/swa_attn/", "attn/full_attn/", "mlp/moe_router/", "mlp/moe_dispatch/", "mlp/moe_shared/",
                  "lm_head/", "embed/"):
        assert scope in suffix and scope in decode, scope
    assert "kv_writeback" in decode and "kv_writeback" not in suffix


# --------------------------------------------------------- a whole decision
@pytest.fixture(scope="module")
def stack():
    import chip_smoke
    from k8s_llm_scheduler_tpu.cli import _build_stack
    from k8s_llm_scheduler_tpu.testing import synthetic_cluster

    cfg = chip_smoke.smoke_config(model="tiny-cohere2-moe", bpe_fixture=False)
    cluster = synthetic_cluster(3)
    scheduler, backend = _build_stack(cfg, cluster)
    yield scheduler, backend, cluster
    backend.close()


def test_scheduler_run_binds_pods_from_the_model(stack):
    """`cli._build_stack` -> `Scheduler.run()` -> LocalLLMBackend ->
    submit_wave / harvest_wave on the toy, the path the other families
    take: pods are bound by the model's decisions, and the routed layer's
    and the window's counters came back with the harvest."""
    import chip_smoke
    from k8s_llm_scheduler_tpu.testing import pod_burst

    scheduler, backend, cluster = stack
    engine = backend.engine
    assert family(engine.cfg) is cohere2_moe and not engine.paged
    burst = pod_burst(6, distinct_shapes=6)
    asyncio.run(chip_smoke._serve(scheduler, cluster, burst, timeout_s=100.0))
    stats = scheduler.get_stats()
    assert cluster.bind_count == 6
    assert stats["llm_decisions"] == 6 and stats["fallback_decisions"] == 0
    cfg = engine.cfg
    assert engine._prefix.k.shape[::2] == (cfg.n_layers, cfg.n_kv_heads)
    es = backend.get_stats()
    assert es["waves"] >= 1 and es["moe_layer_calls"] > 0
    # on the CPU the window's einsum reads the whole prefix buffer, more than a causal layer's keys
    assert es["window_keys_read"] >= es["window_keys_causal"] > 0


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
        with pytest.raises(ValueError, match="tiny-cohere2-moe") as err:
            build_local_backend("tiny-cohere2-moe", compile_cache_dir=None, **kwargs)
        assert path in str(err.value) and "not served" in str(err.value)
        assert time.perf_counter() - t0 < 30

    def test_ragged_decode_refuses_in_the_forward_too(self):
        with pytest.raises(ValueError, match="cohere2_moe.py"):
            cohere2_moe.forward_block_decode(None, toy_cfg(), *([jnp.zeros((1, 1), jnp.int32)] * 13), ragged=True)

    @pytest.mark.parametrize("call, path", [
        (lambda e: e.generate("hello"), "generate()"),
        (lambda e: e.add_requests([[1, 2, 3]]), "add_requests()"),
        (lambda e: e.step(), "step()"),
        (lambda e: e.attach_spec(object()), "attach_spec()"),
    ])
    def test_paged_entry_points_refuse(self, stack, call, path):
        engine = stack[1].engine
        with pytest.raises(ValueError, match="tiny-cohere2-moe") as err:
            call(engine)
        assert path in str(err.value) and "a window in PagedKVCache" in str(err.value)
        assert "models/cohere2_moe.py" in str(err.value)

    def test_the_profiler_books_ask_the_config(self):
        from k8s_llm_scheduler_tpu.observability.profiler import (
            attn_flops_per_token,
            matmul_flops_per_token,
        )

        cfg = get_config("tiny-cohere2-moe")
        d = cfg.d_model
        attn = 2 * d * 128 + 2 * d * 32          # W_q, W_o (8 heads of 16); W_k, W_v (2 KV heads)
        ffn = d * 16 + (4 * 4 / 16 + 4) * 3 * d * 32   # router; held picks and four shared experts
        assert matmul_flops_per_token(cfg) == 2.0 * (8 * (attn + ffn) + d * cfg.vocab_size)
        per_key = 4.0 * 8 * 16
        assert attn_flops_per_token(cfg, 10) == 10 * per_key * 8       # within the window: every layer
        assert attn_flops_per_token(cfg, 100) == per_key * (2 * 100 + 6 * 32)


def test_the_full_prefix_kernel_serves_the_global_layers_alone():
    """With the kernels chosen, a period's three window layers call
    `window_prefix_attention` and its global layer alone calls
    `flash_prefix_attention_parts`, in the suffix call and in block decode:
    a reader of the full kernel's events (metrics/prefix_attn_roofline.py,
    every prefix key a call) sees the global layer's calls and nothing of
    the window's. Block decode holds each layer's kernel once a width of
    its prefix part (ops/attention.py block_prefix_widths), one of which a
    call runs."""
    cfg = toy_cfg(jnp.bfloat16)
    model = cohere2_moe
    params = jax.eval_shape(lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    cache = lambda *lead: tuple(  # noqa: E731
        jax.ShapeDtypeStruct((cfg.n_layers, *lead, *s), cfg.dtype) for s in model.cache_token_shapes(cfg))

    def kernels(jaxpr):
        out = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out.extend(kernels(sub))
        return out

    suffix = jax.make_jaxpr(lambda p, *a: model.forward_prefill_suffix_dense(p, cfg, *a, prefix_impl="pallas"))(
        params, i32(R, 32), i32(R), *cache(256), i32())
    decode = jax.make_jaxpr(lambda p, *a: model.forward_block_decode(p, cfg, *a, prefix_impl="pallas"))(
        params, i32(R, F), jax.ShapeDtypeStruct((R, F), jnp.bool_), i32(R), i32(R, F), *cache(R, SS), i32(R),
        *cache(R, CAP + F), i32(R), *cache(256), i32())
    for jaxpr, rungs in ((suffix, 1), (decode, len(block_prefix_widths(F)))):
        names = kernels(jaxpr.jaxpr)   # the scan's body: one period
        assert names.count("window_prefix_attention") == 3 * rungs
        assert names.count("flash_prefix_attention_parts") == rungs
