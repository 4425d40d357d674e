"""Block decode attends the shared prefix at the block positions a model call
carries (ops/attention.py `block_prefix_attend_parts`): the prefix part runs
at the narrowest rung of `block_prefix_widths(F)` that holds every row's
valid run, and comes back padded to F with o = 0, m = NEG_INF, l = 0.

Held here on the CPU: at each rung the narrowed part is the full-width part
at every position the rung computes (the full kernel, the window kernel and
the einsum, the kernels in interpret mode), positions past it come out
finite after the merge, whole waves of the three families that narrow emit
the tokens they emit at full width, the kernels read the query rows the
full width makes, and the counter the forwards return is the rungs the
program took.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.engine.constrained import build_decision_dfa
from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
from k8s_llm_scheduler_tpu.models import family, get_config
from k8s_llm_scheduler_tpu.ops import attention
from k8s_llm_scheduler_tpu.ops.attention import (
    NEG_INF,
    attend_part,
    block_prefix_attend_parts,
    block_prefix_counts,
    block_prefix_rung,
    block_prefix_widths,
    merge_attention_parts,
    prefix_attend_parts,
)

TOK = ByteTokenizer()


@pytest.mark.parametrize("F, widths", [(24, (2, 8, 24)), (8, (2, 8)), (2, (2,)), (1, (1,))])
def test_the_ladder_ends_at_the_block(F, widths):
    assert block_prefix_widths(F) == widths


@pytest.mark.parametrize("lens, rung", [((0, 0, 0), 0), ((1, 0, 2), 0), ((3, 1, 0), 1), ((8, 8, 2), 1),
                                        ((9, 1, 1), 2), ((1, 24, 0), 2)])
def test_the_rung_holds_the_longest_run(lens, rung):
    assert int(block_prefix_rung(jnp.asarray(lens, jnp.int32), 24)) == rung


# --------------------------------------------------------- one prefix part
B, F, H, HKV, HD = 2, 24, 8, 2, 64
SP, PLEN = 2048, 1500
# a window whose first query, right behind the prefix, sees from key 1024 on:
# the lower edge of the window on a key block's edge (the kernel's blocks are
# 1,024 keys here)
WINDOW = PLEN - 1024 + 1
# valid tokens a row: both rows empty; each rung at its lower and upper edge
CASES = {"empty": (0, 0), "rung0": (2, 1), "rung1_low": (0, 3), "rung1": (8, 5), "top_low": (1, 9),
         "top": (24, 5)}


def _inputs():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, F, H, HD)), jnp.float32)
    qg = (q * HD**-0.5).reshape(B, F, HKV, H // HKV, HD)
    pk = jnp.asarray(rng.standard_normal((SP, HKV, HD)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((SP, HKV, HD)), jnp.float32)
    offset = jnp.asarray([0, 37], jnp.int32)  # row 0's block sits right behind the prefix
    positions = PLEN + offset[:, None] + jnp.arange(F)[None, :]
    return q, qg, pk, pv, positions - (WINDOW - 1)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_narrowed_part_is_the_full_part_where_it_is_computed(impl, windowed, case):
    """At the rung the block's runs take, (o, m, l) of the narrowed part equal
    the full-width part's at every position the rung computes (every valid
    one among them), and are (0, NEG_INF, 0) past it; merged beside the
    block's own part, every position is finite."""
    q, qg, pk, pv, key_lo = _inputs()
    blk_len = jnp.asarray(CASES[case], jnp.int32)
    rung = block_prefix_rung(blk_len, F)
    w = block_prefix_widths(F)[int(rung)]
    assert w >= max(CASES[case])
    window = (WINDOW, key_lo) if windowed else None
    got = block_prefix_attend_parts(rung, q, qg, pk, pv, jnp.int32(PLEN), impl=impl, window=window)
    want = prefix_attend_parts(q, qg, pk, pv, jnp.int32(PLEN), impl=impl, window=window)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a[:, :, :, :w]), np.asarray(b[:, :, :, :w]), rtol=2e-5, atol=2e-5)
    o, m, l = (np.asarray(a[:, :, :, w:]) for a in got)
    assert (o == 0).all() and (m == NEG_INF).all() and (l == 0).all()
    j = jnp.arange(F)
    own = (j[:, None] >= j[None, :])[None, None, None] & (j[None, :] < blk_len[:, None])[:, None, None, None]
    k = v = qg.reshape(B, F, HKV, -1, HD)[:, :, :, 0]
    merged = merge_attention_parts([got, attend_part(qg, k, v, own, "bqkgh,bskh->bkgqs")])
    assert np.isfinite(np.asarray(merged)).all()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_narrow_kernels_run_at_the_narrow_width(impl):
    """The kernels' result shapes say the rows they computed: one branch a
    rung, each at [n_kv, B g w, hd], so a trace reader that takes the rows
    from the result shape counts what ran."""
    q, qg, pk, pv, key_lo = _inputs()
    record = attention.AttnImpl(kind=impl)
    jax.make_jaxpr(lambda r: block_prefix_attend_parts(r, q, qg, pk, pv, jnp.int32(PLEN), impl=record))(
        jnp.int32(0))
    jax.make_jaxpr(lambda r: block_prefix_attend_parts(r, q, qg, pk, pv, jnp.int32(PLEN), impl=record,
                                                       window=(WINDOW, key_lo)))(jnp.int32(0))
    how = "xla" if impl == "xla" else "pallas_interpret"
    assert record.resolved == {(site, (B, w, H, HD), SP): how
                               for site in ("prefix", "window_prefix") for w in block_prefix_widths(F)}


@pytest.mark.parametrize("rung, w", [(0, 2), (1, 8), (2, 24)])
def test_a_call_counts_its_rows_at_the_width_its_rung_takes(rung, w):
    got = jax.jit(lambda r: block_prefix_counts(r, 3, F))(jnp.int32(rung))
    assert got.dtype == jnp.int32 and np.asarray(got).tolist() == [3 * w, 3 * F]


def _eqns(jaxpr):
    """jaxpr's equations and those of the calls in it, a kernel's body
    left out."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from _eqns(sub)


def _branch_eqns(fn):
    """The equations of each branch of the one lax.switch in fn's jaxpr."""
    (cond,) = [e for e in _eqns(jax.make_jaxpr(fn)(jnp.int32(0)).jaxpr) if e.primitive.name == "cond"]
    return [list(_eqns(b.jaxpr)) for b in cond.params["branches"]]


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
def test_the_kernels_read_the_query_rows_the_full_width_makes(windowed):
    """The kernels' query rows (cast to float32 and scaled) are made once, at
    width F, outside the switch, and a branch only takes its first w
    positions of them: no branch rounds, casts or scales a query, so on the
    chip a kernel reads the values it reads at full width (a bf16 query
    handed into a branch and cast there is rounded where the full width's
    fused producer is not)."""
    q, _, pk, pv, key_lo = _inputs()
    q = q.astype(jnp.bfloat16)
    qg = (q.astype(jnp.float32) * HD**-0.5).reshape(B, F, HKV, H // HKV, HD)
    window = (WINDOW, key_lo) if windowed else None
    branches = _branch_eqns(lambda r: block_prefix_attend_parts(r, q, qg, pk, pv, jnp.int32(PLEN), impl="pallas",
                                                              window=window))
    assert len(branches) == len(block_prefix_widths(F))
    for eqns in branches:
        names = [e.primitive.name for e in eqns]
        assert names.count("pallas_call") == 1
        floats = [e for e in eqns if e.primitive.name in ("convert_element_type", "mul")
                  and jnp.issubdtype(e.invars[0].aval.dtype, jnp.floating) and e.invars[0].aval.ndim > 0]
        assert floats == []


# ------------------------------------------------------------- whole waves
NAMES = ["node-a1", "node-a2", "node-b1", "node-b22", "worker-9"]
PREFIX = TOK.encode("cluster: " + ", ".join(f"{n} cpu={i}/8 mem={2 * i}Gi" for i, n in enumerate(NAMES)) * 3)
SUFFIXES = [TOK.encode(f"pod-{i} wants {i + 1} cpu and {3 * i}Gi") for i in range(3)]


def _wave(preset: str):
    """One whole wave of the toy preset through the engine: its tokens, and
    the engine's stats after it."""
    cfg = get_config(preset)
    params = jax.jit(lambda k: family(cfg).init_params(k, cfg))(jax.random.PRNGKey(3))
    eng = InferenceEngine(params, cfg, TOK, num_pages=16, page_size=64, max_slots=4, max_pages_per_seq=16,
                          temperature=0.0)
    eng.set_grammar(build_decision_dfa(TOK, NAMES, max_reason_tokens=6))
    eng.set_prefix(PREFIX)
    fins = eng.decide_wave(SUFFIXES, max_new_tokens=120)
    return [f.token_ids for f in fins], eng.get_stats()


@pytest.mark.parametrize("preset", ["tiny", "tiny-mamba2-hybrid", "tiny-cohere2-moe"])
def test_a_wave_emits_what_it_emits_at_full_width(preset, monkeypatch):
    """The same wave with the ladder cut to its top rung (every call at the
    block's width) and as it is."""
    monkeypatch.setattr(attention, "BLOCK_PREFIX_WIDTHS", ())
    jax.clear_caches()
    wide, wide_stats = _wave(preset)
    assert wide_stats["block_prefix_positions"] == wide_stats["block_prefix_positions_full"] > 0
    monkeypatch.undo()
    jax.clear_caches()
    narrow, stats = _wave(preset)
    assert narrow == wide
    assert 0 < stats["block_prefix_positions"] < stats["block_prefix_positions_full"]
    assert stats["block_prefix_positions_full"] == wide_stats["block_prefix_positions_full"]


def test_the_counter_is_the_rungs_the_program_took(monkeypatch):
    """The rung each model call of a toy wave computed on the device (read
    back by a callback the test puts behind the family's own call) against
    the counters the wave's forwards returned, summed on the device and
    fetched with its tokens."""
    from k8s_llm_scheduler_tpu.models import llama

    taken = []

    def recording(blk_len, F_):
        rung = block_prefix_rung(blk_len, F_)
        jax.debug.callback(lambda r: taken.append(int(r)), rung)
        return rung

    monkeypatch.setattr(llama, "block_prefix_rung", recording)
    jax.clear_caches()
    try:
        _, stats = _wave("tiny")
        jax.effects_barrier()
    finally:
        jax.clear_caches()
    widths = block_prefix_widths(24)
    assert len(taken) == stats["wave_model_calls"] and len(set(taken)) > 1
    assert stats["block_prefix_positions"] == sum(4 * widths[r] for r in taken)
    assert stats["block_prefix_positions_full"] == 4 * 24 * len(taken)
