"""The engine's per-sequence state beside its per-token cache (PR 37), on the
toy of the one family that has one (models/gdn_moe.py): a prefix entry holds
the state its prefill left, waves seed their rows from it and never write
it, the chunked prefix path carries it from chunk to chunk and takes no LCP
seed. What the other three families' programs lower to is held by
tests/test_lowered_forwards.py; the build-time refusals by
tests/test_gdn_moe.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_gdn_moe import REF, TOL, TOY, toy_cfg, toy_params


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _engine(cfg, params, **kw):
    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine

    kw = {"prefill_buckets": (128, 256), **kw}
    return InferenceEngine(params, cfg, num_pages=8, page_size=64, max_slots=4, max_pages_per_seq=8,
                           chunk_steps=4, temperature=0.0, **kw)


@pytest.fixture(scope="module")
def toy():
    with jax.default_matmul_precision("highest"):
        cfg = toy_cfg()
        return cfg, toy_params(cfg)


def _texts(tok):
    prefix = tok.encode("cluster state: " + "node cpu mem " * 8)
    suffixes = [tok.encode(f"pod-{i} wants " + "cpu " * (2 + 3 * i)) for i in range(3)]
    return prefix, suffixes


def test_a_wave_through_the_engine_serves_the_references_best_tokens(toy):
    """set_prefix (prefix prefill: cache and state) -> submit_wave /
    harvest_wave (rows seeded from the prefix's state, block decode through
    cache and state in the `while` carry) in float32 at greedy decode,
    against the reference's full forward over prefix + suffix + served
    tokens: every served token lies within TOL, in logits, of the best token
    the reference sees at its place."""
    cfg, params = toy
    eng = _engine(cfg, params)
    tok = eng.tokenizer
    prefix, suffixes = _texts(tok)
    eng.set_prefix(prefix)
    assert len(eng._prefix.state) == 6 and eng._prefix.state[0].shape[0] == cfg.n_periods
    fins = eng.harvest_wave(eng.submit_wave(suffixes, max_new_tokens=6))
    served = [f.token_ids for f in fins]
    assert all(1 <= len(s) <= 6 for s in served)
    spans = [(len(s) - 1, len(t)) for s, t in zip(suffixes, served)]
    tails = [s + t for s, t in zip(suffixes, served)]
    logits = np.array(REF.wave_logits(TOY, params, prefix, tails, spans, "f32", tok.vocab_size))
    logits[:, tok.pad_id] = -np.inf  # the engine never samples the pad
    flat = [t for s in served for t in s]
    assert len(flat) == logits.shape[0]
    gaps = [float(row.max() - row[t]) for row, t in zip(logits, flat)]
    assert max(gaps) < TOL, gaps
    c = eng.stats
    assert c["state_seeds"] == 4  # the wave's four rows, padding row included
    # every suffix token and every served token went through the delta rule once
    assert c["state_tokens_valid"] == sum(map(len, suffixes)) + sum(map(len, served))
    assert c["state_tokens_valid"] < c["state_tokens_computed"]
    assert c["moe_bounded_calls"] == c["moe_layer_calls"] > 0


def test_two_waves_from_one_pin_agree_and_leave_its_state_bit_identical(toy):
    """A pinned prefix is a cache AND a state; every wave seeds its rows
    from the pin's state with a copy of their own and none writes into it:
    the second wave from the pin serves what the first served, and the
    pin's arrays are the same objects holding the same bits."""
    cfg, params = toy
    eng = _engine(cfg, params)
    prefix, suffixes = _texts(eng.tokenizer)
    key, epoch = eng.pin_prefix(prefix)
    assert eng.pin_alive(key, epoch)
    eng.set_prefix(prefix)  # the cached entry, made active
    pin = eng._prefix_cache[key]
    assert eng._prefix is pin and eng.stats["prefix_prefills"] == 1
    before = [np.asarray(a).copy() for a in (*pin.kv, *pin.state)]
    assert pin.nbytes == sum(a.nbytes for a in before)
    first = [f.token_ids for f in eng.harvest_wave(eng.submit_wave(suffixes, max_new_tokens=6))]
    held = (*pin.kv, *pin.state)
    second = [f.token_ids for f in eng.harvest_wave(eng.submit_wave(suffixes, max_new_tokens=6))]
    assert first == second
    after = (*eng._prefix_cache[key].kv, *eng._prefix_cache[key].state)
    assert all(a is b for a, b in zip(held, after))
    for a, b in zip(before, after):
        assert not b.is_deleted()
        np.testing.assert_array_equal(a, np.asarray(b))
    # a hot swap drops the pin with its state, as it drops its cache
    eng.swap_params(params)
    assert not eng.pin_alive(key, epoch) and key not in eng._prefix_cache


def test_a_long_prefix_goes_through_the_chunked_path_whole_and_is_the_single_shot_state(toy):
    """A prefix longer than `prefix_chunk` is prefilled chunk after chunk,
    the state carried from one chunk into the next; it equals the state a
    single-shot prefill of the same tokens leaves. A second prefix that
    shares a long head with a cached one takes NO LCP seed (a state exists
    only at the lengths it was saved at): the whole of it is prefilled, and
    `prefill_tokens` says so."""
    cfg, params = toy
    rng = np.random.default_rng(2)
    ids = rng.integers(1, 500, 300).tolist()
    chunked = _engine(cfg, params, prefix_chunk=128)
    chunked.set_prefix(ids)
    single = _engine(cfg, params, prefill_buckets=(128, 512), prefix_chunk=512)
    single.set_prefix(ids)
    assert chunked._prefix.k.shape[1] == 512 and single._prefix.k.shape[1] == 512  # 3 chunks + headroom; one bucket
    for a, b in zip(chunked._prefix.state, single._prefix.state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    np.testing.assert_allclose(np.asarray(chunked._prefix.k[:, :300]), np.asarray(single._prefix.k[:, :300]),
                               atol=2e-5)
    assert chunked.stats["prefill_tokens"] == 300 and chunked.stats.get("prefix_reused_tokens", 0) == 0

    drifted = ids[:280] + rng.integers(1, 500, 40).tolist()  # a 280-token common head: over the LCP threshold
    chunked.set_prefix(drifted)
    assert chunked.stats["prefill_tokens"] == 300 + 320
    assert chunked.stats.get("prefix_reused_tokens", 0) == 0
    single.set_prefix(drifted)
    for a, b in zip(chunked._prefix.state, single._prefix.state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    # the dense family on the same path still seeds from the common head
    from k8s_llm_scheduler_tpu.models import family, get_config

    dense_cfg = get_config("tiny")
    dense = _engine(dense_cfg, jax.jit(lambda k: family(dense_cfg).init_params(k, dense_cfg))(
        jax.random.PRNGKey(0)), prefix_chunk=128)
    dense.set_prefix(ids)
    dense.set_prefix(drifted)
    assert dense.stats["prefix_reused_tokens"] == 280 and dense._prefix.state == ()
    assert dense.stats["prefill_tokens"] == 300 + 40
