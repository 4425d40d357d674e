"""The families the benchmark runs lower to the text their parent lowered
to: the three that carry no per-sequence state as before the engine learnt
of one, and the delta-rule hybrid (`tiny-gdn-moe`) as before a
second state-space family shared its layer scan's pattern and the
convolution's window (models/mamba2_hybrid.py).

A family that declares `state_shapes(cfg) == ()` must cost nothing: the
engine's wave program and the family's three forwards are traced through
Python branches only, so their StableHLO text is the parent's, byte for
byte. The digests in tests/fixtures/lowered_forwards.json were recorded
from the PARENT commit's tree with this very file (`python
tests/test_lowered_forwards.py --write`, run from that checkout), toy
presets on XLA:CPU. A PR that means to change one of these programs
records the file again from its own tree and says so.
"""

from __future__ import annotations

import functools
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "lowered_forwards.json"
STATELESS = ("tiny", "tiny-mla-moe", "tiny-mla-scmoe")
PRESETS = (*STATELESS, "tiny-gdn-moe")
R, SS, SP, F, CAP = 4, 128, 256, 24, 48


@functools.lru_cache(maxsize=None)
def _texts(name: str) -> dict[str, str]:
    """Lowered text of the preset's three forwards and of its wave program."""
    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
    from k8s_llm_scheduler_tpu.models import family, get_config

    cfg = get_config(name)
    model = family(cfg)
    params = jax.eval_shape(lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0))
    layers = model.cache_layers(cfg)
    shapes = model.cache_token_shapes(cfg)

    def cache(*lead):
        return tuple(jax.ShapeDtypeStruct((layers, *lead, *s), cfg.dtype) for s in shapes)

    def state(*lead):  # a family without a state is called as before: no keyword at all
        members = model.state_shapes(cfg)
        if not members:
            return {}
        return {"state": tuple(jax.ShapeDtypeStruct((model.state_layers(cfg), *lead, *s), d) for s, d in members)}

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    out = {}
    out["prefill_kv"] = jax.jit(model.forward_prefill_kv, static_argnums=(1,)).lower(
        params, cfg, i32(1, SP), i32(1)).as_text()
    out["suffix_dense"] = jax.jit(model.forward_prefill_suffix_dense, static_argnums=(1,)).lower(
        params, cfg, i32(R, SS), i32(R), *cache(SP), i32(), **state()).as_text()
    out["block_decode"] = jax.jit(model.forward_block_decode, static_argnums=(1,)).lower(
        params, cfg, i32(R, F), jax.ShapeDtypeStruct((R, F), jnp.bool_), i32(R), i32(R, F),
        *cache(R, SS), i32(R), *cache(R, CAP + F), i32(R), *cache(SP), i32(), **state(R)).as_text()

    real = jax.jit(lambda k: model.init_params(k, cfg))(jax.random.PRNGKey(0))
    eng = InferenceEngine(real, cfg, num_pages=8, page_size=64, max_slots=R, max_pages_per_seq=8)
    prefix = eng._get_empty_prefix()
    n_iters = 8
    out["wave"] = eng._wave.lower(
        eng.params, cfg, jnp.zeros((R, SS), jnp.int32), jnp.zeros((R,), jnp.int32),
        prefix.kv, jnp.int32(0), jnp.zeros((R,), jnp.int32),
        eng._sp_tokens, eng._sp_next, eng._forced, eng._forced_next, eng._done_state,
        jnp.int32(eng.tokenizer.eos_id), jnp.int32(eng.tokenizer.pad_id), jnp.int32(0),
        jax.random.PRNGKey(0), jnp.float32(0.0),
        n_iters, 1, n_iters, False, **eng._prefix_state_kw(prefix),
    ).as_text()
    return out


def digests(name: str) -> dict[str, str]:
    return {k: hashlib.sha256(v.encode()).hexdigest() for k, v in _texts(name).items()}


@pytest.mark.parametrize("name", PRESETS)
def test_a_family_lowers_as_the_parent_did(name):
    recorded = json.loads(FIXTURE.read_text())
    assert recorded["jax"] == jax.__version__, "recorded under another jax: record again"
    assert digests(name) == recorded["digests"][name]


@pytest.mark.parametrize("name", STATELESS)
def test_a_family_without_state_runs_nothing_of_the_delta_rules_kernel(name):
    """ops/gdn_scan.py's kernel (PR 38) serves models/gdn_moe.py alone: the
    three forwards and the wave program of the other families do not hold
    it, so the cells that run them run their parent's programs."""
    for program, text in _texts(name).items():
        assert "gdn_chunk_scan" not in text and "gdn_scan" not in text, program


@pytest.mark.parametrize("name", PRESETS)
def test_no_family_here_runs_the_mamba2_scan(name):
    """ops/ssd_scan.py's kernel serves models/mamba2_hybrid.py alone."""
    for program, text in _texts(name).items():
        assert "ssd_chunk_scan" not in text and "ssm_scan" not in text, program


if __name__ == "__main__" and "--write" in sys.argv:
    sys.path.insert(0, str(Path.cwd()))
    jax.config.update("jax_platforms", "cpu")
    np.random.seed(0)
    target = Path(sys.argv[sys.argv.index("--write") + 1])
    target.write_text(json.dumps(
        {"jax": jax.__version__, "digests": {n: digests(n) for n in PRESETS}}, indent=1) + "\n")
    print(target.read_text())
