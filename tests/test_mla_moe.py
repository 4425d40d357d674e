"""models/mla_moe.py at a toy size of the GLM-4.7-Flash family on the CPU:
latent attention through the latent prefix / suffix / generated caches, the
sigmoid router and its expert layer, against the plain reference the
benchmark keeps (benchmark/reference/mla_moe.py: written-out attention, a
loop over experts, float32 at `highest`, nothing of the program imported).
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

from k8s_llm_scheduler_tpu.models import family, mla_moe
from k8s_llm_scheduler_tpu.models.configs import MlaMoeConfig, get_config
from k8s_llm_scheduler_tpu.ops.grouped_matmul import grouped_matmul

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"t_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(BENCH / "reference" / "mla_moe.py")

# The toy, in the published key names (what a configuration file holds).
TOY = {
    "name": "toy-mla-moe", "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1.8, "norm_topk_prob": True,
    "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc", "vocab_size": 512,
    "max_position_embeddings": 2048, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
}


def toy_cfg(dtype=jnp.float32, **overrides) -> MlaMoeConfig:
    return MlaMoeConfig.from_hf(TOY["name"], {**TOY, **overrides}, dtype=dtype)


def toy_params(cfg, seed=0):
    return jax.jit(lambda k: mla_moe.init_params(k, cfg))(jax.random.PRNGKey(seed))


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
    caches, as engine._wave_impl strings them. Returns ([logits at every
    position the program computed one for, per row], counters summed).
    `junk` is written to every padding position of the suffix and of every
    block: it must not matter."""
    R = len(suffixes)
    toks = np.full((1, PREFIX_CAP), junk, np.int32)
    toks[0, :PREFIX_LEN] = prefix
    _, pc, pr = mla_moe.forward_prefill_kv(params, cfg, jnp.asarray(toks), jnp.asarray([PREFIX_LEN]))
    pc, pr = pc[:, 0], pr[:, 0]
    sfx = np.full((R, SS), junk, np.int32)
    for r, s in enumerate(suffixes):
        sfx[r, :len(s)] = s
    lens = jnp.asarray([len(s) for s in suffixes], jnp.int32)
    logits, sc, sr, counters = mla_moe.forward_prefill_suffix_dense(
        params, cfg, jnp.asarray(sfx), lens, pc, pr, jnp.int32(PREFIX_LEN))
    out = [[np.asarray(logits[r])] if len(suffixes[r]) else [] for r in range(R)]
    gc, gr = (jnp.zeros((cfg.n_layers, R, CAP + F, *shape), cfg.dtype)
              for shape in mla_moe.cache_token_shapes(cfg))
    tail = np.zeros(R, np.int32)
    for blk in BLOCKS:
        tok = np.full((R, F), junk, np.int32)
        for r in range(R):
            tok[r, :blk[r]] = served[r][tail[r]:tail[r] + blk[r]]
        blk_len = jnp.asarray(blk, jnp.int32)
        valid = jnp.arange(F)[None, :] < blk_len[:, None]
        pos = (PREFIX_LEN + lens + tail)[:, None] + jnp.arange(F)[None, :]
        logits, gc, gr, c = mla_moe.forward_block_decode(
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
        ends = np.cumsum([0] + [b[r] for b in BLOCKS])  # served tokens consumed after each step
        out[r] = [mine[e] for e in ends]
    return out


class TestWaveAgainstReference:
    """(a) prefix prefill -> suffix prefill -> block decode through the
    latent cache against the reference's one full forward: logits, not
    tokens."""

    # float32 on the CPU: the two sides differ in the ORDER of float32 sums
    # (absorbed products, merged softmax parts, grouped experts against
    # written-out attention and a loop over experts), a few 1e-6 of logits
    # of order 1. The same toy in bfloat16 rounds every activation to 8
    # bits of mantissa and lands near 1e-1: 2e-4 sits two orders above the
    # one and two under the other.
    TOL = 2e-4

    @pytest.fixture(scope="class")
    def wave(self):
        return _wave_tokens(np.random.default_rng(7))

    def _worst(self, dtype, wave):
        cfg = toy_cfg(dtype)
        params = toy_params(cfg)
        got, counters = program_wave_logits(cfg, params, *wave)
        weights = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        want = reference_wave_logits(TOY, weights, *wave)
        worst = 0.0
        for r, rows in want.items():
            assert len(rows) == len(got[r]) == len(BLOCKS) + 1
            for a, b in zip(got[r], rows):
                worst = max(worst, float(np.max(np.abs(a.astype(np.float32) - b))))
        return worst, counters

    def test_float32_agrees_with_the_reference(self, wave):
        worst, counters = self._worst(jnp.float32, wave)
        assert worst < self.TOL, worst
        valid = sum(SUFFIX_LENS) + sum(map(sum, BLOCKS))
        calls = 1 + len(BLOCKS)
        n_moe = TOY["num_hidden_layers"] - TOY["first_k_dense_replace"]
        assert counters[0] == valid * TOY["num_experts_per_tok"] * n_moe  # moe_assignments
        assert counters[2] == calls * n_moe                               # moe_layer_calls
        assert 0 < counters[1] <= TOY["n_routed_experts"] * counters[2]   # moe_experts_hit
        assert counters[3] >= counters[2]                                 # moe_max_load

    def test_bfloat16_fails_the_same_tolerance(self, wave):
        """The tolerance is tight enough to tell the precisions apart."""
        worst, _ = self._worst(jnp.bfloat16, wave)
        assert worst > 10 * self.TOL, worst

    def test_padding_changes_neither_logits_nor_assignments(self, wave):
        """(d) padding positions of a block and of a suffix are not routed
        and not seen: other tokens there change nothing."""
        cfg = toy_cfg()
        params = toy_params(cfg)
        a, ca = program_wave_logits(cfg, params, *wave, junk=0)
        b, cb = program_wave_logits(cfg, params, *wave, junk=311)
        for rows_a, rows_b in zip(a, b):
            for x, y in zip(rows_a, rows_b):
                np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(ca, cb)


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
    assert float(jnp.min(jnp.abs(mine["moe_layers"]["router_bias"]))) > 0  # drawn, not zero


# ------------------------------------------------------------- (b) the forms
def test_absorbed_and_written_out_attention_agree():
    cfg = toy_cfg()
    lp = jax.tree_util.tree_map(lambda a: a[0], toy_params(cfg)["moe_layers"])
    rng = np.random.default_rng(3)
    B, S, H = 2, 12, cfg.n_heads
    q_nope = jnp.asarray(rng.normal(size=(B, S, H, cfg.qk_nope_head_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(B, S, H, cfg.qk_rope_head_dim)), jnp.float32)
    c_kv = jnp.asarray(rng.normal(size=(B, S, cfg.kv_lora_rank)), jnp.float32)
    k_r = jnp.asarray(rng.normal(size=(B, S, cfg.qk_rope_head_dim)), jnp.float32)
    lens = jnp.asarray([12, 7])
    j = jnp.arange(S)
    mask = (j[:, None] >= j[None, :])[None, None] & (j[None, :] < lens[:, None])[:, None, None, :]
    # written out, as the layer equations say it: k_nope and v made of c_kv per head
    dn = cfg.qk_nope_head_dim
    kv = jnp.einsum("btc,chd->bthd", c_kv, mla_moe._w_ukv(lp, cfg).astype(jnp.float32))
    logits = (jnp.einsum("bshd,bthd->bhst", q_nope, kv[..., :dn])
              + jnp.einsum("bshr,btr->bhst", q_rope, k_r)) * cfg.qk_head_dim**-0.5
    p = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
    written = jnp.einsum("bhst,bthd->bshd", p, kv[..., dn:])
    # the absorbed form, its keys cut in two segments that it has to merge
    cut = 5
    absorbed = mla_moe.attend_absorbed(lp, cfg, q_nope, q_rope, [
        (c_kv[:, :cut], k_r[:, :cut], mask[..., :cut]),
        (c_kv[:, cut:], k_r[:, cut:], mask[..., cut:]),
    ])
    assert written.shape == absorbed.shape == (B, S, H, cfg.v_head_dim)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(written), rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------- (c) the router
class TestRouter:
    """Selection uses the bias, the weights do not, they are renormalised
    and scaled: a case in which dropping any one of the four changes the
    answer, for the program's router and for the reference's."""

    LOGITS = np.array([[2.0, 1.0, 0.5, -1.0]], np.float32)   # scores 0.881 0.731 0.622 0.269
    BIAS = np.array([0.0, -0.5, 0.0, 0.6], np.float32)       # + bias  0.881 0.231 0.622 0.869
    K, SCALE = 2, 1.8

    def _expected(self, use_bias=True, bias_in_weights=False, renorm=True, scale=True):
        s = 1.0 / (1.0 + np.exp(-self.LOGITS[0]))
        pick = np.argsort(-(s + self.BIAS if use_bias else s))[: self.K]
        w = (s + self.BIAS if bias_in_weights else s)[pick]
        if renorm:
            w = w / w.sum()
        out = np.zeros(4, np.float32)
        out[pick] = w * (self.SCALE if scale else 1.0)
        return out

    def _inputs(self):
        # h = e_0 scaled so that h @ router == LOGITS exactly
        h = np.zeros((1, 8), np.float32)
        h[0, 0] = 1.0
        router = np.zeros((8, 4), np.float32)
        router[0] = self.LOGITS[0]
        return h, router

    def test_the_case_tells_the_four_apart(self):
        want = self._expected()
        assert set(np.nonzero(want)[0]) == {0, 3}  # by bias: expert 3 displaces expert 1
        for variant in (dict(use_bias=False), dict(bias_in_weights=True),
                        dict(renorm=False), dict(scale=False)):
            assert np.max(np.abs(self._expected(**variant) - want)) > 0.05, variant

    def test_program_router(self):
        h, router = self._inputs()
        cfg = toy_cfg(n_routed_experts=4, routed_scaling_factor=self.SCALE)
        sel, w = mla_moe.route({"router": jnp.asarray(router), "router_bias": jnp.asarray(self.BIAS)},
                               cfg, jnp.asarray(h))
        got = np.zeros(4, np.float32)
        got[np.asarray(sel[0])] = np.asarray(w[0])
        np.testing.assert_allclose(got, self._expected(), rtol=1e-5)

    def test_reference_router(self):
        h, router = self._inputs()
        got = REF.route(jnp.asarray(h), jnp.asarray(router), jnp.asarray(self.BIAS),
                        self.K, True, self.SCALE)
        np.testing.assert_allclose(np.asarray(got[0]), self._expected(), rtol=1e-5)


# ------------------------------------------------------------- (e) the shares
def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """An expert-parallel share holds 8 of 64 experts, routes over all 64
    and computes its own experts' part; the parts of all eight shares and
    the shared expert, counted once, are the uncut layer: the program's
    and the reference's."""
    conf = {**TOY, "n_routed_experts": 64, "num_experts_per_tok": 4, "moe_intermediate_size": 16}
    cfg = toy_cfg(n_routed_experts=64, num_experts_per_tok=4, moe_intermediate_size=16)
    lp = jax.tree_util.tree_map(lambda a: a[0], toy_params(cfg)["moe_layers"])
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(24, cfg.d_model)), jnp.float32)
    h = mla_moe.rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    valid = jnp.ones((24,), bool)
    whole, counters = mla_moe.routed_experts(lp, cfg, h, valid)
    assert counters[0] == 24 * 4
    parts, assigned = 0.0, 0
    for share in range(8):
        cut = dataclasses.replace(cfg, expert_first=8 * share, expert_count=8)
        lp_cut = {**lp, **{k: lp[k][8 * share: 8 * share + 8] for k in ("we_gate", "we_up", "we_down")}}
        part, c = mla_moe.routed_experts(lp_cut, cut, h, valid)
        parts = parts + part
        assigned += int(c[0])
    assert assigned == 24 * 4  # every assignment computed by exactly one share
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), rtol=1e-4, atol=1e-5)
    layer = parts + mla_moe.shared_experts(lp, h)
    want = REF._expert_ffn(x, lp, REF._dims(conf), "f32") - x
    np.testing.assert_allclose(np.asarray(layer), np.asarray(want), rtol=1e-4, atol=2e-5)
    # and the reference's own shares
    ref_parts = sum(
        REF._expert_ffn(x, {**lp, **{k: lp[k][8 * s: 8 * s + 8] for k in ("we_gate", "we_up", "we_down")},
                            "ws_down": jnp.zeros_like(lp["ws_down"])},
                        REF._dims(conf), "f32", first=8 * s) - x
        for s in range(8))
    np.testing.assert_allclose(np.asarray(ref_parts + mla_moe.shared_experts(lp, h)), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def test_grouped_matmul_is_a_loop_over_groups():
    """ops/grouped_matmul.py in interpret mode (the code path the chip
    compiles) against one matmul per group; rows of no group are left
    alone, whatever they hold."""
    rng = np.random.default_rng(0)
    L, G, K, N, M = 3, 8, 64, 32, 256
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w0, w1 = (jnp.asarray(rng.normal(size=(L, G, K, N)), jnp.float32) for _ in range(2))
    cases = ([3, 0, 5, 0, 0, 2, 1, 0], [0] * 8, [130, 5, 0, 0, 0, 0, 0, 120], [32] * 8)
    for layer, sizes in enumerate(cases):
        layer %= L  # the stack is read in place, at the layer asked for
        fused = np.asarray(grouped_matmul(x, (w0, w1), jnp.asarray(sizes, jnp.int32), layer, swiglu=True))
        plain = np.asarray(grouped_matmul(x, (w0,), jnp.asarray(sizes, jnp.int32), jnp.int32(layer)))
        at = 0
        for g, n in enumerate(sizes):
            rows = x[at:at + n]
            np.testing.assert_allclose(plain[at:at + n], rows @ w0[layer, g], rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(
                fused[at:at + n], jax.nn.silu(rows @ w0[layer, g]) * (rows @ w1[layer, g]),
                rtol=1e-4, atol=1e-4)
            at += n


# ------------------------------------------------------------ the names
def test_the_lowered_forwards_hold_the_scopes_and_kernel_names():
    """The scopes a device trace reads this model's time by, and the
    `name=` of the two grouped-matmul kernels (benchmark/metrics/
    moe_*_device_ms_per_bind.py, mla_proj_*, moe_grouped_*_roofline.py)."""
    cfg = toy_cfg()
    params = toy_params(cfg)
    R, L = 2, cfg.n_layers
    c_tok, r_tok = mla_moe.cache_token_shapes(cfg)
    z = lambda *shape: jnp.zeros(shape, cfg.dtype)  # noqa: E731
    text = jax.jit(mla_moe.forward_block_decode, static_argnums=1).lower(
        params, cfg, jnp.zeros((R, F), jnp.int32), jnp.ones((R, F), bool), jnp.full((R,), F, jnp.int32),
        jnp.zeros((R, F), jnp.int32), z(L, R, SS, *c_tok), z(L, R, SS, *r_tok), jnp.ones((R,), jnp.int32),
        z(L, R, CAP + F, *c_tok), z(L, R, CAP + F, *r_tok), jnp.zeros((R,), jnp.int32),
        z(L, PREFIX_CAP, *c_tok), z(L, PREFIX_CAP, *r_tok), jnp.int32(PREFIX_LEN),
    ).as_text(debug_info=True)
    for path in ("attn/mla_down", "attn/mla_up", "attn/latent_attention", "attn/wo", "kv_writeback",
                 "mlp/moe_router", "mlp/moe_dispatch", "mlp/moe_experts", "mlp/moe_combine",
                 "mlp/moe_shared", "lm_head", "embed"):
        assert f"{path}/" in text, path
    # the latent write is dense copies, a window a row (ops/attention.write_block)
    assert "kv_writeback/dynamic_update_slice" in text and "kv_writeback/scatter" not in text
    for kernel in ("moe_grouped_swiglu", "moe_grouped_matmul"):
        assert f"mlp/moe_experts/{kernel}" in text or kernel in text, kernel
    # the dense layer's feed-forward stays bare `mlp`
    assert re.search(r'"mlp/(?!moe_)[^"]*dot_general', text)
    prefill = jax.jit(mla_moe.forward_prefill_kv, static_argnums=1).lower(
        params, cfg, jnp.zeros((1, PREFIX_CAP), jnp.int32), jnp.asarray([PREFIX_LEN])).as_text(debug_info=True)
    # (the scan's body is a function of its own: its paths start at `attn`)
    assert "/prefix_prefill/" in prefill and "attn/latent_attention/" in prefill
    assert "lm_head/" not in prefill  # the cache alone


# --------------------------------------------------- (f) a whole decision
@pytest.fixture(scope="module")
def stack():
    import chip_smoke
    from k8s_llm_scheduler_tpu.cli import _build_stack
    from k8s_llm_scheduler_tpu.testing import synthetic_cluster

    cfg = chip_smoke.smoke_config(model="tiny-mla-moe", bpe_fixture=False)
    cluster = synthetic_cluster(3)
    scheduler, backend = _build_stack(cfg, cluster)
    yield scheduler, backend, cluster
    backend.close()


def test_scheduler_run_binds_pods_from_the_model(stack):
    """`cli._build_stack` -> `Scheduler.run()` -> LocalLLMBackend ->
    submit_wave / harvest_wave on the toy: pods are bound by the model's
    decisions, the prefix the engine holds is the latent pair, and the
    expert counters came back with the harvest."""
    import chip_smoke
    from k8s_llm_scheduler_tpu.testing import pod_burst

    scheduler, backend, cluster = stack
    engine = backend.engine
    assert family(engine.cfg) is mla_moe and not engine.paged
    burst = pod_burst(6, distinct_shapes=6)
    asyncio.run(chip_smoke._serve(scheduler, cluster, burst, timeout_s=100.0))
    stats = scheduler.get_stats()
    assert cluster.bind_count == 6
    assert stats["llm_decisions"] == 6 and stats["fallback_decisions"] == 0
    cfg = engine.cfg
    assert engine._prefix.k.shape == (cfg.n_layers, engine._prefix.k.shape[1], cfg.kv_lora_rank)
    assert engine._prefix.v.shape == (cfg.n_layers, engine._prefix.k.shape[1], cfg.qk_rope_head_dim)
    assert engine._prefix.k.shape[1] >= engine.prefix_len > 0
    assert engine.kv.k.shape[1] == 1  # no paged pool: the scratch page alone
    es = backend.get_stats()
    assert es["waves"] >= 1 and es["moe_layer_calls"] == cfg.n_moe_layers * (
        es["wave_model_calls"] + es["waves"])
    assert 0 < es["moe_experts_hit"] <= cfg.n_routed_experts * es["moe_layer_calls"]
    assert es["moe_assignments"] > 0 and es["moe_max_load"] >= es["moe_layer_calls"]


# ------------------------------------------------------ (g) what is refused
class TestRefusedPaths:
    """What this model does not serve refuses at build time (or at the
    call, for an entry point), naming the model and the path, before
    anything is traced."""

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
        with pytest.raises(ValueError, match="tiny-mla-moe") as err:
            build_local_backend("tiny-mla-moe", compile_cache_dir=None, **kwargs)
        assert path in str(err.value) and "not served" in str(err.value)
        assert time.perf_counter() - t0 < 30

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
        with pytest.raises(ValueError, match="tiny-mla-moe") as err:
            call(engine)
        assert path in str(err.value) and "paged" in str(err.value)

    def test_packed_admission_rides_waves(self, stack):
        """`admission.packed` is a preference for engines that have the
        packed path: here the batch surface stays on waves."""
        assert stack[1]._packed_admission is False

    def test_the_profiler_books_ask_the_config(self):
        from k8s_llm_scheduler_tpu.observability.profiler import (
            attn_flops_per_token,
            matmul_flops_per_token,
        )

        cfg = get_config("tiny-mla-moe")
        d, fe = cfg.d_model, cfg.d_ff_expert
        active = (cfg.n_layers * cfg.attn_params() + 3 * d * cfg.d_ff
                  + cfg.n_moe_layers * (d * 8 + (2 + 1) * 3 * d * fe) + d * cfg.vocab_size)
        assert matmul_flops_per_token(cfg) == 2.0 * active
        assert attn_flops_per_token(cfg, 10) == 10 * 2.0 * cfg.n_layers * cfg.n_heads * (2 * 32 + 8)
