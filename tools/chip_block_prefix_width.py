"""On-chip check: block decode with its prefix part narrowed against the same
block decode at full width, on the same inputs.

For each configuration named (files under benchmark/configs/, at their own
size; by default the three whose cells narrow: the command-a-plus share, the
dense InternLM2.5 1.8B and Granite 4.0 H Micro), the family's
`forward_block_decode` over random prefix, suffix and generated caches (and a
zero state, for a family that has one) and six patterns of valid runs that
cover every rung of ops/attention.py `block_prefix_widths`, compiled twice
before either runs: with the ladder as committed (one `lax.switch` a layer),
and with `BLOCK_PREFIX_WIDTHS` set to () while that second program is
lowered (no switch: the program without the narrowing). Reports, per
pattern, whether the logits of the rows that hold a token and the block
cache written for them are bitwise equal, the largest logit difference
against the logits' scale, and whether the greedy tokens agree.

    python tools/chip_block_prefix_width.py [configuration ...]   # needs a TPU
    JAX_PLATFORMS=cpu python tools/chip_block_prefix_width.py toy   # interpreted

The last stdout line is a summary (`all_equal`).
"""

from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "benchmark"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from harness import seam  # noqa: E402
from k8s_llm_scheduler_tpu.models import family, get_config  # noqa: E402
from k8s_llm_scheduler_tpu.ops import attention  # noqa: E402

R, F = 8, 24
# valid tokens a row: each rung at its lower and upper edge, a row left out
RUNS = ([1] * 8, [2, 1, 1, 0, 2, 1, 1, 1], [4, 1, 1, 1, 1, 1, 1, 1], [8, 7, 8, 1, 0, 8, 8, 8],
        [9, 1, 1, 1, 1, 1, 1, 1], [20, 24, 3, 1, 1, 1, 1, 1])
CONFIGS = ("command-a-plus-05-2026", "internlm2_5-1_8b", "granite-4_0-h-micro")
# (prefix buffer, prefix tokens) a configuration's cell holds; others 2,048 / 1,500
SIZES = {"command-a-plus-05-2026": (12288, 9800), "toy": (256, 200)}


def _config(name: str):
    if name == "toy":
        return get_config("tiny-cohere2-moe")
    conf = seam.load_config(REPO / "benchmark" / "configs" / f"{name}.json")
    return get_config(seam.program(conf).register(conf))


def check(name: str) -> dict:
    """{pattern: result} for one configuration."""
    cfg = _config(name)
    model = family(cfg)
    buffer, plen = SIZES.get(name, (2048, 1500))
    suffix_cap, cap = 128, 102
    params = jax.jit(lambda k: model.init_params(k, cfg))(jax.random.PRNGKey(0))
    L, shapes = model.cache_layers(cfg), model.cache_token_shapes(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 3 * len(shapes) + 1))

    def draw(*shape):
        return jax.random.normal(next(keys), shape, cfg.dtype)

    prefix = tuple(draw(L, buffer, *s) for s in shapes)
    suffix = tuple(draw(L, R, suffix_cap, *s) for s in shapes)
    gen = tuple(draw(L, R, cap + F, *s) for s in shapes)
    state = {}
    if model.state_shapes(cfg):
        state = {"state": tuple(jnp.zeros((model.state_layers(cfg), R, *s), d) for s, d in model.state_shapes(cfg))}
    suffix_lens = jnp.asarray([70, 65, 80, 72, 64, 85, 77, 69], jnp.int32)
    tail = jnp.asarray([10, 12, 9, 14, 10, 11, 13, 12], jnp.int32)
    tokens = jax.random.randint(next(keys), (R, F), 1, min(cfg.vocab_size, 1000))

    def decode(params, *a, state=None):
        return model.forward_block_decode(params, cfg, *a, prefix_impl="pallas",
                                          **({} if state is None else {"state": state}))

    def inputs(runs):
        lens = jnp.asarray(runs, jnp.int32)
        valid = jnp.arange(F)[None, :] < lens[:, None]
        positions = (plen + suffix_lens + tail)[:, None] + jnp.arange(F)[None, :]
        return (params, jnp.where(valid, tokens, 0), valid, lens, positions, *suffix, suffix_lens, *gen, tail,
                *prefix, jnp.int32(plen))

    # both programs compiled before either runs: the second traced afresh (JAX keeps a trace a
    # function) with the ladder cut to F while it is lowered
    lowered = jax.jit(decode).lower(*inputs(RUNS[0]), **state)
    widths, attention.BLOCK_PREFIX_WIDTHS = attention.BLOCK_PREFIX_WIDTHS, ()
    try:
        jax.clear_caches()
        lowered_full = jax.jit(decode).lower(*inputs(RUNS[0]), **state)
    finally:
        attention.BLOCK_PREFIX_WIDTHS = widths
        jax.clear_caches()
    switches = [t.as_text().count("stablehlo.case") for t in (lowered, lowered_full)]
    assert switches[0] > switches[1], switches  # the narrowed program holds a switch a layer more
    narrow, full = lowered.compile(), lowered_full.compile()
    n = len(shapes)
    results = {}
    for runs in RUNS:
        args = inputs(runs)
        a, b = narrow(*args, **state), full(*args, **state)
        live = np.asarray(runs) > 0
        la, lb = np.asarray(a[0])[live], np.asarray(b[0])[live]
        cache_equal = all(
            np.array_equal(np.asarray(x[:, r, int(tail[r]):int(tail[r]) + runs[r]]),
                           np.asarray(y[:, r, int(tail[r]):int(tail[r]) + runs[r]]))
            for x, y in zip(a[1:1 + n], b[1:1 + n]) for r in range(R))
        key = ",".join(map(str, runs))
        results[key] = {
            "rung": int(attention.block_prefix_rung(jnp.asarray(runs), F)),
            "logits_equal": bool(np.array_equal(la, lb)), "max_abs": float(np.abs(la - lb).max()),
            "scale": float(np.abs(lb).max()), "greedy_equal": bool((la.argmax(-1) == lb.argmax(-1)).all()),
            "cache_equal": cache_equal}
        print(json.dumps({"configuration": name, key: results[key]}), flush=True)
    return results


def main(names) -> int:
    t0 = time.time()
    equal = {}
    for name in names:
        results = check(name)
        equal[name] = all(v["logits_equal"] and v["cache_equal"] for v in results.values())
        gc.collect()
    print(json.dumps({"all_equal": all(equal.values()), "by_configuration": equal,
                      "device": jax.devices()[0].device_kind, "s": round(time.time() - t0, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or CONFIGS))
