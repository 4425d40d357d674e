"""models/mamba2_hybrid.py at a toy size of the Granite 4.0-H family on the
CPU: two periods of four Mamba-2 layers and one attention without position
encoding (at the third place of a period, not the last), a convolution
whose window crosses every join, a per-sequence state beside the per-token
cache, the four multipliers and the tied head, against the plain reference
the benchmark keeps (benchmark/reference/mamba2_hybrid.py: the recurrence
token by token, float32 at `highest`, nothing of the program imported).
Seeded random weights; every mechanism present, every width shrunk.
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

from k8s_llm_scheduler_tpu.models import family, mamba2_hybrid
from k8s_llm_scheduler_tpu.models.configs import Mamba2HybridConfig, get_config
from k8s_llm_scheduler_tpu.ops.attention import block_prefix_widths

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"t_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(BENCH / "reference" / "mamba2_hybrid.py")

# The toy, in the published key names (what a configuration file holds): two
# periods of five layers, the attention third in each.
TOY = {
    "name": "toy-mamba2-hybrid", "hidden_size": 64, "num_hidden_layers": 10,
    "layer_types": (["mamba"] * 2 + ["attention"] + ["mamba"] * 2) * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "shared_intermediate_size": 128,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 32, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False, "position_embedding_type": "nope",
    "num_local_experts": 0, "num_experts_per_tok": 0, "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 1 / 16, "logits_scaling": 8, "vocab_size": 512, "max_position_embeddings": 2048,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
}

# Program and reference both compute in float32 here and differ in the ORDER
# of their sums alone: the chunked form's products against the recurrence's
# token-by-token updates, flash parts against one softmax. The tied table is
# drawn at 0.02 (models/mamba2_hybrid.py `init_params` says why), so the
# logits' scale is ~0.02 at this width and the tolerance is relative to it:
# read at this size, 1e-5 of the scale; a state that is lost moves them by
# three times the scale (the test of a lost pin state, below).
TOL = 1e-3


def toy_cfg(dtype=jnp.float32, conf=TOY, **overrides) -> Mamba2HybridConfig:
    return Mamba2HybridConfig.from_hf(conf["name"], conf, **{"dtype": dtype, **overrides})


def toy_params(cfg, seed=0):
    return jax.jit(lambda k: mamba2_hybrid.init_params(k, cfg))(jax.random.PRNGKey(seed))


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ----------------------------------------------------- the chunked recurrence
def _ssd_inputs(rng, lens, chunk, H, P, N, periods):
    B = len(lens)
    T = -(-max(max(lens), 1) // chunk) * chunk
    x = jnp.asarray(rng.normal(size=(B, H, T, P)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(B, T, N)) * N**-0.5, jnp.float32) for _ in range(2))
    ok = np.arange(T)[None, :] < np.asarray(lens)[:, None]
    dt = jnp.where(ok[:, None], jnp.asarray(rng.uniform(1e-3, 0.3, size=(B, H, T)), jnp.float32), 0.0)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, size=H), jnp.float32)
    member = jnp.asarray(rng.normal(size=(periods, B, H, P, N)), jnp.float32)
    return x, dt, a, b, c, ok, member


# the last three: the cell's call shapes in small (a decode block as one chunk
# of 24 with ragged rows, one of them empty; a suffix call as two chunks of 64;
# a prefix prefill as many chunks for one row), on the middle entry of a member
@pytest.mark.parametrize("lens, chunk, heads, periods, period", [
    ((48, 48), 16, 4, 1, 0), ((48, 17), 16, 4, 1, 0), ((0, 5), 24, 4, 1, 0), ((24, 1), 24, 32, 1, 0),
    ((3, 0, 24, 1, 8), 24, 4, 3, 1), ((70, 128, 83), 64, 4, 3, 1), ((300,), 64, 4, 3, 1)])
def test_the_chunked_scan_is_the_recurrence(lens, chunk, heads, periods, period):
    """`ssd_chunks` (the decay's sums and ops/ssd_scan.py's kernel,
    interpreted) against the reference's token-by-token scan: outputs at
    the valid positions and the state after them; a position that is not
    valid (dt = 0) leaves the state as it was, so a row of length 0 keeps
    the state it came with, to the bit; of a member of several periods only
    the entry named is advanced; heads in one block and in two."""
    rng = np.random.default_rng(3)
    x, dt, a, b, c, ok, member = _ssd_inputs(rng, lens, chunk, heads, 16, 32, periods)
    y, new = mamba2_hybrid.ssd_chunks(x, dt, a, b, c, jnp.asarray(lens, jnp.int32), member, period, chunk)
    zero_d = jnp.zeros((heads,), jnp.float32)
    # the products with the state carry 16 bits of mantissa (three bfloat16 passes, ops/ssd_scan.py):
    # a few 1e-5 on entries of unit scale, where the recurrence runs in float32
    atol = 5e-5
    for r, n in enumerate(lens):
        t = lambda arr: jnp.moveaxis(arr[r], 0, 1)  # noqa: E731  [H, T, ..] -> [T, H, ..]
        want_y, want_s = REF.ssd(t(x), t(dt), a, b[r], c[r], zero_d, jnp.asarray(ok[r]), member[period, r])
        np.testing.assert_allclose(np.asarray(t(y))[:n], np.asarray(want_y)[:n], rtol=1e-4, atol=atol)
        np.testing.assert_allclose(np.asarray(new[period, r]), np.asarray(want_s), rtol=1e-4, atol=atol)
        if n == 0:  # no valid position: the row's state comes back to the bit
            np.testing.assert_array_equal(np.asarray(new[period, r]), np.asarray(member[period, r]))
    for p in range(periods):  # the other periods' entries are not this call's to touch
        if p != period:
            np.testing.assert_array_equal(np.asarray(new[p]), np.asarray(member[p]))


def test_the_convolution_and_the_state_cross_every_join():
    """One Mamba-2 mixer over a sequence in ONE call, against the same
    sequence in three calls (prefix | suffix | a decode block with padding
    behind its valid tokens), each seeded with the state and the window the
    call before it returned: the first tokens after a join see the last
    tokens before it through the convolution, and the pieces' outputs are
    the whole's."""
    cfg = toy_cfg()
    params = toy_params(cfg)
    lp = {k: a[1] for k, a in params["ssm"].items()}
    rng = np.random.default_rng(5)
    cuts = (37, 9, 5)   # prefix, suffix, decode block (8 wide, 5 valid)
    T = sum(cuts)
    u = jnp.asarray(rng.normal(size=(1, T, cfg.d_model)), jnp.float32)
    zero = mamba2_hybrid.zero_state(cfg, 1)
    s0, w0 = zero[0][:1], zero[-1][0]  # one period's entry of a member; its window
    ones = lambda n: jnp.ones((1, n), bool)  # noqa: E731
    whole, s_whole, w_whole = mamba2_hybrid.ssm_mixer(lp, cfg, u, ones(T), jnp.asarray([T]), s0, 0, w0)
    s, w, at, pieces, states = s0, w0, 0, [], []
    for n, width in zip(cuts, (64, 16, 8)):
        piece = jnp.zeros((1, width, cfg.d_model), jnp.float32).at[:, :n].set(u[:, at: at + n])
        y, s, w = mamba2_hybrid.ssm_mixer(lp, cfg, piece, jnp.arange(width)[None] < n, jnp.asarray([n]), s, 0, w)
        pieces.append(y[:, :n])
        states.append(s)
        at += n
    np.testing.assert_allclose(np.asarray(jnp.concatenate(pieces, axis=1)), np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_whole), atol=2e-5)
    # the last three inputs, as the projection made them (in products of another width: to rounding)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_whole), atol=2e-6)
    # and with the prefix's state but without its window the first tokens behind a join differ
    y, _, _ = mamba2_hybrid.ssm_mixer(lp, cfg, u[:, cuts[0]: cuts[0] + 8], ones(8), jnp.asarray([8]),
                                      states[0], 0, w0)
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
        _, pk, pv, state = jax.jit(mamba2_hybrid.forward_prefill_kv, static_argnums=1)(
            params, cfg, jnp.asarray(tok), jnp.asarray([P]))
        pk, pv, self.prefix_state = pk[:, 0], pv[:, 0], tuple(a[:, 0] for a in state)
        state = tuple(jnp.zeros_like(a) for a in self.prefix_state) if lose_state else self.prefix_state
        stok = np.zeros((R, SS), np.int32)
        lens = np.asarray(SUFFIX_LENS, np.int32)
        for r, s in enumerate(self.suffixes):
            stok[r, : len(s)] = s
        logits, ks, vs, rows, self.suffix_counters = jax.jit(
            mamba2_hybrid.forward_prefill_suffix_dense, static_argnums=1)(
            params, cfg, jnp.asarray(stok), jnp.asarray(lens), pk, pv, jnp.int32(P), state=state)
        self.logits = [[np.asarray(logits[r])] for r in range(R)]
        gk, gv = (jnp.zeros((cfg.n_attn_layers, R, CAP + F, *shape), cfg.dtype)
                  for shape in mamba2_hybrid.cache_token_shapes(cfg))
        done = np.zeros(R, np.int32)
        decode = jax.jit(mamba2_hybrid.forward_block_decode, static_argnums=1)
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
    _, _, wave, ref = toy
    scale = float(np.std(ref))
    assert scale > 0.01  # logits of the table's scale, not zeros: the tolerance means something
    assert _gaps(wave, ref) < TOL * scale
    # the counters: every valid token once a call, the scan's width with padding; block
    # decode's prefix positions at the narrowest width that holds the call's longest run
    c = dict(zip(mamba2_hybrid.COUNTERS, wave.suffix_counters))
    assert c == {"state_tokens_valid": sum(SUFFIX_LENS), "state_tokens_computed": R * SS,
                 "block_prefix_positions": 0, "block_prefix_positions_full": 0}
    for blk, counters in zip(BLOCKS, wave.decode_counters):
        w = min(w for w in block_prefix_widths(F) if w >= max(blk))
        assert dict(zip(mamba2_hybrid.COUNTERS, counters)) == {
            "state_tokens_valid": sum(blk), "state_tokens_computed": R * F,
            "block_prefix_positions": R * w, "block_prefix_positions_full": R * F}


def test_a_lost_pin_state_fails_the_tolerance(toy):
    """The same wave with the prefix's state zeroed before the rows are
    seeded (what a pin that held the cache alone would serve): the logits
    leave the reference's by thousands of tolerances, at the end of 65-83
    token suffixes and after them, so the comparison sees a state that is
    lost.
    Read at this size: 3.3 times the logits' scale, where the sound wave
    reads 1e-5 of it and the int8 control's widest difference 0.47 of it."""
    cfg, params, _, ref = toy
    lost = Wave(cfg, params, lose_state=True)
    assert _gaps(lost, ref) > 100 * TOL * float(np.std(ref))


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
    decay = np.exp(np.asarray(ours["ssm"]["A_log"])) * np.log1p(np.exp(np.asarray(ours["ssm"]["dt_bias"])))
    assert 1e-3 < decay.min() and decay.max() < 1.6  # dt A: a step of 1e-3..1e-1 times (1, 16)


def test_the_registered_toy_is_the_hand_written_one():
    cfg = get_config("tiny-mamba2-hybrid")
    assert family(cfg) is mamba2_hybrid
    assert cfg == dataclasses.replace(toy_cfg(jnp.bfloat16), name="tiny-mamba2-hybrid")
    assert (cfg.period, cfg.attn_position, cfg.n_periods, cfg.n_ssm_layers, cfg.head_dim) == (5, 2, 2, 8, 16)
    assert mamba2_hybrid.cache_layers(cfg) == 2 and mamba2_hybrid.state_layers(cfg) == 2
    members = mamba2_hybrid.state_shapes(cfg)  # S of the period's four Mamba-2 layers, then their windows
    assert [m[0] for m in members] == [(8, 16, 32)] * 4 + [(3, 128 + 2 * 32)] * 4
    assert all(m[1] == jnp.float32 for m in members)
    with pytest.raises(ValueError, match="whole period"):
        toy_cfg(conf={**TOY, "layer_types": ["mamba"] * 2 + ["attention"] + ["mamba"] * 4 + ["attention"] * 3})
    with pytest.raises(ValueError, match="sparse experts"):
        toy_cfg(conf={**TOY, "num_local_experts": 8})
    with pytest.raises(ValueError, match="mamba_n_groups"):
        toy_cfg(conf={**TOY, "mamba_n_groups": 2})


def test_the_lowered_forwards_hold_the_scopes_and_kernel_names():
    """What benchmark/metrics/ reads by name is in the program text. JAX's
    caches are cleared first: a helper traced earlier in the process
    (ops/_f32dot.py under the delta rule's kernel) would otherwise bring the
    name scopes of that first trace into these programs' locations."""
    jax.clear_caches()
    cfg = toy_cfg(jnp.bfloat16)
    model = mamba2_hybrid
    params = jax.eval_shape(lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    cache = lambda *lead: tuple(  # noqa: E731
        jax.ShapeDtypeStruct((cfg.n_attn_layers, *lead, *s), cfg.dtype) for s in model.cache_token_shapes(cfg))
    state = lambda *lead: tuple(  # noqa: E731
        jax.ShapeDtypeStruct((model.state_layers(cfg), *lead, *s), d) for s, d in model.state_shapes(cfg))
    prefix = jax.jit(model.forward_prefill_kv, static_argnums=1).lower(
        params, cfg, i32(1, 256), i32(1)).as_text(debug_info=True)
    suffix = jax.jit(model.forward_prefill_suffix_dense, static_argnums=1).lower(
        params, cfg, i32(R, SS), i32(R), *cache(256), i32(), state=state()).as_text(debug_info=True)
    decode = jax.jit(model.forward_block_decode, static_argnums=1).lower(
        params, cfg, i32(R, F), jax.ShapeDtypeStruct((R, F), jnp.bool_), i32(R), i32(R, F),
        *cache(R, SS), i32(R), *cache(R, CAP + F), i32(R), *cache(256), i32(), state=state(R)
    ).as_text(debug_info=True)
    for scope in ("attn/ssm/ssm_proj/", "attn/ssm/ssm_conv/", "attn/ssm/ssm_scan/", "attn/ssm/ssm_out/",
                  "attn/ssm/state_writeback/", "attn/full_attn/", "mlp/", "lm_head/", "embed/"):
        assert scope in suffix and scope in decode, scope
    assert "state_seed" in suffix and "state_seed" not in decode
    assert "kv_writeback" in decode
    # the scan's kernel, under the scope ssm_scan_device_ms_per_bind.tput reads, in all three forwards
    for name, text in (("prefix", prefix), ("suffix", suffix), ("decode", decode)):
        assert re.search(r"attn/ssm/ssm_scan/[^\"]*ssd_chunk_scan", text), name
        assert "prefix_prefill/" in text if name == "prefix" else "prefix_prefill/" not in text
        assert "gdn_chunk_scan" not in text and "rope" not in text


def test_a_suffix_call_leaves_the_pins_state_bit_identical(toy):
    """The kernel updates a state where it lies, and the rows' state it is
    handed in the suffix call is their own copy (`state_seed`): the prefix's
    arrays, which a pin holds for every later wave, still hold the same
    bits after a wave has been seeded from them, and are still alive."""
    cfg, params, wave, _ = toy
    held = wave.prefix_state
    before = [np.asarray(a).copy() for a in held]
    pk = jnp.zeros((cfg.n_attn_layers, P_BUCKET, *mamba2_hybrid.cache_token_shapes(cfg)[0]), cfg.dtype)
    tokens = jnp.ones((R, SS), jnp.int32)
    out = jax.jit(mamba2_hybrid.forward_prefill_suffix_dense, static_argnums=1)(
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

    cfg = chip_smoke.smoke_config(model="tiny-mamba2-hybrid", bpe_fixture=False)
    cluster = synthetic_cluster(3)
    scheduler, backend = _build_stack(cfg, cluster)
    yield scheduler, backend, cluster
    backend.close()


def test_scheduler_run_binds_pods_from_the_model(stack):
    """`cli._build_stack` -> `Scheduler.run()` -> LocalLLMBackend ->
    submit_wave / harvest_wave on the toy, the path the other families
    take: pods are bound by the model's decisions, the prefix the engine
    holds is a cache of the attention layers AND the state of the Mamba-2
    layers, and the counters came back with the harvest."""
    import chip_smoke
    from k8s_llm_scheduler_tpu.testing import pod_burst

    scheduler, backend, cluster = stack
    engine = backend.engine
    assert family(engine.cfg) is mamba2_hybrid and not engine.paged
    burst = pod_burst(6, distinct_shapes=6)
    asyncio.run(chip_smoke._serve(scheduler, cluster, burst, timeout_s=100.0))
    stats = scheduler.get_stats()
    assert cluster.bind_count == 6
    assert stats["llm_decisions"] == 6 and stats["fallback_decisions"] == 0
    cfg = engine.cfg
    pfx = engine._prefix
    assert pfx.k.shape == (cfg.n_attn_layers, pfx.k.shape[1], cfg.n_kv_heads, cfg.head_dim)
    assert [a.shape for a in pfx.state] == [(cfg.n_periods, *s) for s, _ in mamba2_hybrid.state_shapes(cfg)]
    assert pfx.nbytes == sum(a.nbytes for a in (*pfx.kv, *pfx.state))
    assert float(jnp.max(jnp.abs(pfx.state[0]))) > 0
    es = backend.get_stats()
    assert es["waves"] >= 1 and es["state_seeds"] >= 6
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
        with pytest.raises(ValueError, match="tiny-mamba2-hybrid") as err:
            build_local_backend("tiny-mamba2-hybrid", compile_cache_dir=None, **kwargs)
        assert path in str(err.value) and "not served" in str(err.value)
        assert time.perf_counter() - t0 < 30

    def test_ragged_decode_refuses_in_the_forward_too(self):
        with pytest.raises(ValueError, match="mamba2_hybrid.py"):
            mamba2_hybrid.forward_block_decode(None, toy_cfg(), *([jnp.zeros((1, 1), jnp.int32)] * 13),
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
        with pytest.raises(ValueError, match="tiny-mamba2-hybrid") as err:
            call(engine)
        assert path in str(err.value) and "per-sequence state" in str(err.value)
        assert "models/mamba2_hybrid.py" in str(err.value)

    @pytest.mark.parametrize("call, path", [
        (lambda e: e.export_prefix_kv((1, 2, 3)), "export_prefix_kv()"),
        (lambda e: e.adopt_prefix_pages([1, 2, 3], jnp.zeros((1,)), jnp.zeros((1,))), "adopt_prefix_pages()"),
    ])
    def test_the_prefix_plane_refuses_a_pin_that_is_not_k_and_v(self, stack, call, path):
        engine = stack[1].engine
        with pytest.raises(ValueError, match="tiny-mamba2-hybrid") as err:
            call(engine)
        assert path in str(err.value) and "state" in str(err.value) and "models/mamba2_hybrid.py" in str(err.value)

    def test_the_profiler_books_ask_the_config(self):
        from k8s_llm_scheduler_tpu.observability.profiler import (
            attn_flops_per_token,
            matmul_flops_per_token,
        )

        cfg = get_config("tiny-mamba2-hybrid")
        d = cfg.d_model
        ssm = d * (128 + 192 + 8) + 128 * d   # W_in [z | x B C | dt], W_out
        attn = d * 64 + 2 * d * 32 + 64 * d    # W_q, W_k, W_v, W_o (4 heads of 16, 2 KV heads)
        state = 2 * 2.0 * 8 * 16 * 32
        assert matmul_flops_per_token(cfg) == (
            2.0 * (8 * ssm + 2 * attn + 10 * 3 * d * 128 + d * cfg.vocab_size) + 8 * state)
        assert attn_flops_per_token(cfg, 10) == 10 * 4.0 * 2 * 4 * 16  # the two layers that attend
