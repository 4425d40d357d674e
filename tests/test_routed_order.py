"""The routed layer picks and orders its experts without a sort, and gives
what the sorts gave, bit for bit (ops/router_top_k.py `router_top_k`;
models/mla_moe.py `group_positions`, `order_head`, and `route` /
`routed_experts` over them).

- The selection equals `jax.lax.top_k` in values and indices, ties broken
  to the lower index, with the selection bias added and left out, at the
  three routers the benchmark runs (64 / 4, 512 / 10, 768 / 12), and the
  weights it hands back are the scores at the picks.
- The order equals `jnp.argsort(group, stable=True)`: its inverse, its
  head, and the scatter-add's `sizes`, with empty groups, rows of no group
  alone, one group alone, and up to 24,576 rows (a prefix prefill's).
- `routed_experts` on each sparse family's tiny preset returns what the
  sorted formulation (kept below) returns, output and counters, on the
  every-row path, the short path and a call that overflows the bound.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_scheduler_tpu.models import family, mla_moe
from k8s_llm_scheduler_tpu.models.configs import get_config
from k8s_llm_scheduler_tpu.ops.router_top_k import router_top_k


# ------------------------------------------------- the sorted formulation
def route_sorted(lp, cfg, h, sel=None):
    """`route` as it stood: the selection by `jax.lax.top_k`."""
    logits = jnp.einsum("td,de->te", h.astype(jnp.float32), lp["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = mla_moe.SCORES[cfg.router_score](logits)
    if sel is None:
        bias = lp.get("router_bias")
        _, sel = jax.lax.top_k(scores if bias is None else scores + bias, cfg.n_experts_per_tok)
    w = jnp.take_along_axis(scores, sel, axis=1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * cfg.routed_scaling_factor


def routed_experts_sorted(lp, cfg, h, valid, sel=None):
    """`routed_experts` as it stood: the order by a stable argsort, `sizes`
    by a scatter-add, the un-sort by a scatter."""
    T, D = h.shape
    k, held_n = cfg.n_experts_per_tok, cfg.experts_held
    bound = mla_moe.held_bound(T * k, held_n, lp["router"].shape[-1])
    sel, w = route_sorted(lp, cfg, h, sel)
    local = sel - cfg.expert_first
    held = valid[:, None] & (local >= 0) & (local < held_n)
    group = jnp.where(held, local, held_n).reshape(T * k)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((held_n + 1,), jnp.int32).at[group].add(1)[:held_n]

    def experts(head):
        rows = h.astype(lp["we_gate"].dtype)[head // k]
        gate, up, down = (x if x.ndim == 4 else x[None] for x in (lp["we_gate"], lp["we_up"], lp["we_down"]))
        layer = lp.get("layer", 0)
        mid = mla_moe.grouped_matmul(rows, (gate, up), sizes, layer, swiglu=True)
        return mla_moe.grouped_matmul(mid, (down,), sizes, layer, out_dtype=jnp.float32)

    def every_row():
        out = experts(order)
        inverse = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))
        back = out[inverse].reshape(T, k, D)
        return jnp.sum(jnp.where(held[..., None], back * w[..., None], 0.0), axis=1)

    def held_rows():
        head = order[:bound]
        out = experts(head)
        live = jnp.arange(bound)[:, None] < jnp.sum(sizes)
        weighted = jnp.where(live, out * w.reshape(T * k)[head][:, None], 0.0)
        to_token = (head // k)[None, :] == jnp.arange(T)[:, None]
        return jnp.dot(to_token.astype(jnp.float32), weighted, precision=jax.lax.Precision.HIGHEST)

    if bound == T * k:
        y, fits = every_row(), True
    else:
        fits = jnp.sum(sizes) <= bound
        y = jax.lax.cond(fits, held_rows, every_row)
    counters = jnp.stack([jnp.sum(held), jnp.sum(sizes > 0), jnp.int32(1), jnp.max(sizes)]).astype(jnp.int32)
    more = []
    if cfg.n_zero_experts is not None:
        y_zero, zero_counters = mla_moe.zero_experts(cfg, h, sel, w, valid)
        y = y + y_zero
        more.append(zero_counters)
    if held_n < lp["router"].shape[-1]:
        more.append(jnp.asarray(fits, jnp.int32)[None])
    return y, jnp.concatenate([counters, *more]) if more else counters


# ---------------------------------------------------------------- selection
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("outputs, k", [(64, 4), (512, 10), (768, 12)])
def test_the_selection_is_lax_top_k(outputs, k, ties, bias):
    rng = np.random.default_rng(outputs + k)
    T = 512  # two row tiles of the kernel
    if ties:  # a handful of levels: every row holds many equal scores, at the selection's edge too
        scores = np.floor(rng.random((T, outputs)) * 5).astype(np.float32) / 5
        scores[0] = 0.5  # a row of one value: the k lowest indices
    else:
        logits = rng.normal(size=(T, outputs)).astype(np.float32)
        scores = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    s = jnp.asarray(scores)
    b = None
    if bias:
        b = rng.normal(size=(outputs,)).astype(np.float32) * 2e-2
        if ties:
            b = np.round(b * 50) / 50  # biased scores that tie as well
        b = jnp.asarray(b)
    x = s if b is None else s + b
    got, weights = router_top_k(s, b, k)
    values, want = jax.lax.top_k(x, k)
    assert got.dtype == jnp.int32 and got.shape == (T, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(jnp.take_along_axis(x, got, axis=1)), np.asarray(values))
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(jnp.take_along_axis(s, got, axis=1)))
    if ties and not bias:
        np.testing.assert_array_equal(np.asarray(got[0]), np.arange(k))


@pytest.mark.parametrize("name", ["tiny-mla-moe", "tiny-mla-scmoe", "tiny-gdn-moe"])
def test_route_is_the_sorted_route_bit_for_bit(name):
    cfg, lp = _layer(name)
    h = jnp.asarray(np.random.default_rng(5).normal(size=(96, cfg.d_model)), jnp.float32)
    for got, want in zip(mla_moe.route(lp, cfg, h), route_sorted(lp, cfg, h)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -------------------------------------------------------------------- order
def _groups(case: str, n: int, n_held: int, rng) -> np.ndarray:
    if case == "no_group":
        return np.full(n, n_held, np.int32)
    if case == "one_group":
        return np.full(n, n_held // 2, np.int32)
    if case == "empty_groups":  # a third of the groups never met, rows of no group among them
        ids = rng.choice(n_held, max(1, n_held // 3), replace=False)
        return np.where(rng.random(n) < 0.6, rng.choice(ids, n), n_held).astype(np.int32)
    return np.where(rng.random(n) < 0.5, rng.integers(0, n_held, n), n_held).astype(np.int32)


@pytest.mark.parametrize("n, n_held, case", [
    (1, 4, "mixed"), (5, 3, "mixed"), (127, 16, "mixed"), (128, 16, "empty_groups"), (129, 1, "mixed"),
    (768, 64, "mixed"), (1920, 128, "empty_groups"), (2304, 16, "no_group"), (4096, 64, "one_group"),
    (10240, 128, "mixed"), (20480, 128, "empty_groups"), (24576, 16, "mixed"),
])
def test_the_order_is_the_stable_argsort(n, n_held, case):
    rng = np.random.default_rng(n + n_held)
    group = _groups(case, n, n_held, rng)
    sizes, position = jax.jit(mla_moe.group_positions, static_argnums=1)(jnp.asarray(group), n_held)
    order = np.argsort(group, kind="stable")
    inverse = np.empty(n, np.int32)
    inverse[order] = np.arange(n)
    np.testing.assert_array_equal(np.asarray(position), inverse)
    want_sizes = np.asarray(jnp.zeros((n_held + 1,), jnp.int32).at[jnp.asarray(group)].add(1)[:n_held])
    np.testing.assert_array_equal(np.asarray(sizes), want_sizes)
    bound = mla_moe.held_bound(n, n_held, 4 * n_held)
    for m in sorted({1, bound, n}):
        np.testing.assert_array_equal(np.asarray(jax.jit(mla_moe.order_head, static_argnums=1)(position, m)),
                                      order[:m])


# ---------------------------------------------------------------- the layer
def _layer(name: str, **replace):
    """(cfg, one routed layer's leaves as the family hands them over)."""
    cfg = dataclasses.replace(get_config(name), **replace)
    params = jax.jit(lambda key: family(cfg).init_params(key, cfg))(jax.random.PRNGKey(0))
    if "moe_layers" in params:  # a layer's own slices
        return cfg, jax.tree_util.tree_map(lambda a: a[1], params["moe_layers"])
    layers = params["layers"]  # the whole expert stack and the layer's index
    own = {k: layers[k][1] for k in ("router", "router_bias") if k in layers}
    return cfg, {**own, **{k: layers[k] for k in mla_moe.EXPERT_LEAVES}, "layer": jnp.int32(1)}


def _selection(cfg, valid: np.ndarray, n_held: int, rng) -> jnp.ndarray:
    """[T, k] router outputs, distinct within a token: `n_held` slots of
    valid tokens on held experts, every other slot of a valid token off
    the share; padding tokens on held experts alone (they must not count)."""
    T, k = valid.shape[0], cfg.n_experts_per_tok
    first, held = cfg.expert_first, cfg.experts_held
    off = np.array([e for e in range(cfg.n_routed_experts + (cfg.n_zero_experts or 0))
                    if not first <= e < first + held])
    sel = np.stack([rng.choice(off, k, replace=False) for _ in range(T)])
    on_held = first + (np.arange(T)[:, None] + np.arange(k)[None, :]) % held
    slots = np.argwhere(np.broadcast_to(valid[:, None], sel.shape))
    slots = slots[rng.permutation(len(slots))[:n_held]]
    sel[slots[:, 0], slots[:, 1]] = on_held[slots[:, 0], slots[:, 1]]
    sel[~valid] = on_held[~valid]
    return jnp.asarray(sel, jnp.int32)


@pytest.mark.parametrize("name, replace, tokens, case", [
    ("tiny-mla-moe", {}, 4, "own"),        # holds every output: every row, always
    ("tiny-mla-moe", {}, 96, "own"),
    ("tiny-mla-scmoe", {}, 96, "own"),
    ("tiny-mla-scmoe", {}, 64, "short"),
    ("tiny-mla-scmoe", {}, 64, "overflow"),
    ("tiny-gdn-moe", {}, 96, "own"),
    ("tiny-gdn-moe", {}, 64, "short"),
    ("tiny-gdn-moe", {}, 64, "overflow"),
    ("tiny-gdn-moe", {"expert_count": 16}, 64, "own"),  # a layer that holds all sixteen
])
def test_the_routed_layer_is_the_sorted_one_bit_for_bit(monkeypatch, name, replace, tokens, case):
    cfg, lp = _layer(name, **replace)
    rng = np.random.default_rng(tokens)
    h = mla_moe.rms_norm(jnp.asarray(rng.normal(size=(tokens, cfg.d_model)), jnp.float32),
                         jnp.ones((cfg.d_model,), jnp.float32), cfg.rms_eps)
    valid = rng.random(tokens) < 0.75
    k, outputs = cfg.n_experts_per_tok, lp["router"].shape[-1]
    bound = mla_moe.held_bound(tokens * k, cfg.experts_held, outputs)
    forced = None
    if case != "own":
        assert bound < valid.sum() * k
        forced = _selection(cfg, valid, bound - 5 if case == "short" else bound + 3, rng)
        real = mla_moe.route
        monkeypatch.setattr(mla_moe, "route", lambda lp_, cfg_, h_, sel=None: real(lp_, cfg_, h_, sel=forced))
    y, counters = jax.jit(lambda lp_, h_, v: mla_moe.routed_experts(lp_, cfg, h_, v))(lp, h, jnp.asarray(valid))
    y0, counters0 = jax.jit(lambda lp_, h_, v: routed_experts_sorted(lp_, cfg, h_, v, forced))(
        lp, h, jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(counters), np.asarray(counters0))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    assert np.abs(np.asarray(y)).max() > 0  # the layer did something
    n_assigned = int(counters[0])
    if outputs > cfg.experts_held:
        assert int(counters[-1]) == (n_assigned <= bound)  # moe_bounded_calls: which path ran
        if case != "own":
            assert (n_assigned <= bound) == (case == "short")
