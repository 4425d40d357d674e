"""Flagship-scale AOT validation: 70B tensor-parallel programs compile.

The BASELINE north star serves Llama-3.3-70B tensor-parallel over a
v5p-16 (SCALING.md). No such hardware exists in CI — but XLA can compile
the EXACT programs ahead-of-time from abstract (shape+sharding) arguments
over the virtual 8-device mesh, with zero parameter bytes materialized.
This pins, hermetically:

- param_specs divisibility and sharding consistency at 70B/tp=8 (a spec
  that GSPMD cannot honor fails compilation);
- per-device parameter footprint ~17.5 GB (140 GB bf16 / 8), within the
  v5p's 95 GB HBM;
- both serving-path programs: full-prompt prefill (prefix path) and the
  cascade suffix prefill the decision waves start with.

`compiled.memory_analysis()` figures are per device. The temp estimate
comes from the CPU backend and is indicative only (TPU fusion differs),
so the assertions are generous.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from k8s_llm_scheduler_tpu.models.configs import get_config
from k8s_llm_scheduler_tpu.models.llama import (
    forward_prefill,
    forward_prefill_suffix_dense,
    init_params,
)
from k8s_llm_scheduler_tpu.parallel.mesh import make_mesh
from k8s_llm_scheduler_tpu.parallel.sharding import (
    param_specs,
    validate_specs_divisibility,
)

# Everything here jit-compiles models/kernels (seconds per test):
# full-suite only, excluded from the fast tier (TESTING.md).
pytestmark = pytest.mark.slow

CFG = get_config("llama-3.3-70b-instruct")
GB = 1e9


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"tp": 8})


@pytest.fixture(scope="module")
def abstract_params(mesh):
    validate_specs_divisibility(CFG, mesh)
    specs = param_specs(CFG, tp="tp")
    shapes = jax.eval_shape(lambda k: init_params(k, CFG), jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda sds, spec: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)
        ),
        shapes,
        specs,
    )


def _repl(mesh, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P()))


class TestAOT70B:
    def test_prefill_compiles_within_v5p_budget(self, mesh, abstract_params):
        B, S = 4, 2048
        compiled = (
            jax.jit(forward_prefill, static_argnums=(1,))
            .lower(
                abstract_params, CFG,
                _repl(mesh, (B, S), jnp.int32),
                _repl(mesh, (B,), jnp.int32),
            )
            .compile()
        )
        ma = compiled.memory_analysis()
        args_gb = ma.argument_size_in_bytes / GB
        # 140 GB of bf16 weights / tp=8 ~= 17.5 GB per device (+ the small
        # replicated token inputs)
        assert 15.0 < args_gb < 20.0, args_gb
        total_gb = (
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
        ) / GB
        assert total_gb < 95.0, total_gb  # v5p HBM per chip

    def test_wave_suffix_prefill_compiles(self, mesh, abstract_params):
        """The decision wave's first stage at 70B scale: 16 pod suffixes
        against a shared 8k-token dense prefix (256-node BPE prompt)."""
        R, Ss, Sp = 16, 512, 8192
        kv_sds = _repl(
            mesh, (CFG.n_layers, Sp, CFG.n_kv_heads, CFG.head_dim), CFG.dtype
        )
        # prefix KV shards over tp like the params' kv heads
        kv_sds = jax.ShapeDtypeStruct(
            kv_sds.shape, kv_sds.dtype,
            sharding=NamedSharding(mesh, P(None, None, "tp", None)),
        )
        compiled = (
            jax.jit(forward_prefill_suffix_dense, static_argnums=(1,))
            .lower(
                abstract_params, CFG,
                _repl(mesh, (R, Ss), jnp.int32),
                _repl(mesh, (R,), jnp.int32),
                kv_sds, kv_sds,
                _repl(mesh, (), jnp.int32),
            )
            .compile()
        )
        ma = compiled.memory_analysis()
        total_gb = (
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
        ) / GB
        assert total_gb < 95.0, total_gb

    def test_wave_block_decode_compiles(self, mesh, abstract_params):
        """The decision wave program itself (_wave_impl) at 70B/tp=8 —
        suffix prefill + grammar-accelerated block decode to completion.
        This is the program that runs ONCE PER WAVE on the flagship config;
        round 2 pinned only the two prefill programs, so a sharding bug in
        the block-decode stage would have surfaced on real hardware only."""
        from k8s_llm_scheduler_tpu.engine.engine import _wave_impl

        R, Ss, Sp, NS, K = 16, 512, 8192, 4096, 64
        n_iters, F, cap = 12, 24, 200
        kv_sds = jax.ShapeDtypeStruct(
            (CFG.n_layers, Sp, CFG.n_kv_heads, CFG.head_dim), CFG.dtype,
            sharding=NamedSharding(mesh, P(None, None, "tp", None)),
        )
        i32 = jnp.int32
        key_sds = jax.eval_shape(functools.partial(jax.random.PRNGKey, 0))
        key_sds = jax.ShapeDtypeStruct(
            key_sds.shape, key_sds.dtype, sharding=NamedSharding(mesh, P())
        )
        compiled = (
            jax.jit(_wave_impl, static_argnums=(1, 17, 18, 19, 20))
            .lower(
                abstract_params, CFG,
                _repl(mesh, (R, Ss), i32),      # tokens
                _repl(mesh, (R,), i32),         # suffix_lens
                (kv_sds, kv_sds),               # prefix cache tuple (k, v)
                _repl(mesh, (), i32),           # prefix_len
                _repl(mesh, (R,), i32),         # max_new
                _repl(mesh, (NS, K), i32),      # sp_tokens
                _repl(mesh, (NS, K), i32),      # sp_next
                _repl(mesh, (NS,), i32),        # forced
                _repl(mesh, (NS,), i32),        # forced_next
                _repl(mesh, (), i32),           # done_state
                _repl(mesh, (), i32),           # eos_id
                _repl(mesh, (), i32),           # pad_id
                _repl(mesh, (), i32),           # dfa_start
                key_sds,                        # rng
                _repl(mesh, (), jnp.float32),   # temperature
                n_iters, F, cap, True,
            )
            .compile()
        )
        ma = compiled.memory_analysis()
        total_gb = (
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
        ) / GB
        assert total_gb < 95.0, total_gb

    def test_chunked_decode_compiles(self, mesh, abstract_params):
        """_decode_chunk_impl (the paged continuous-batching decode chunk)
        at 70B/tp=8 with the gather own-token path: KV cache pages shard
        their kv-head dim over tp (parallel/sharding.kv_cache_spec)."""
        from k8s_llm_scheduler_tpu.engine.engine import _decode_chunk_impl

        M, Pg, num_pages, ps, NS, K = 17, 20, 512, 64, 4096, 64
        n_steps = 16
        cache_sds = jax.ShapeDtypeStruct(
            (CFG.n_layers, num_pages, ps, CFG.n_kv_heads, CFG.head_dim),
            CFG.dtype,
            sharding=NamedSharding(mesh, P(None, None, None, "tp", None)),
        )
        i32 = jnp.int32
        key_sds = jax.eval_shape(functools.partial(jax.random.PRNGKey, 0))
        key_sds = jax.ShapeDtypeStruct(
            key_sds.shape, key_sds.dtype, sharding=NamedSharding(mesh, P())
        )
        kv_sds = jax.ShapeDtypeStruct(
            (CFG.n_layers, 8192, CFG.n_kv_heads, CFG.head_dim), CFG.dtype,
            sharding=NamedSharding(mesh, P(None, None, "tp", None)),
        )
        compiled = (
            jax.jit(_decode_chunk_impl, static_argnums=(1, 20, 21, 22))
            .lower(
                abstract_params, CFG,
                cache_sds, cache_sds,           # k_cache, v_cache
                _repl(mesh, (M, Pg), i32),      # page_tables
                kv_sds, kv_sds,                 # prefix_k, prefix_v
                _repl(mesh, (), i32),           # prefix_len
                _repl(mesh, (M,), i32),         # tok
                _repl(mesh, (M,), i32),         # pos
                _repl(mesh, (M,), jnp.bool_),   # act
                _repl(mesh, (M,), i32),         # st
                _repl(mesh, (M,), i32),         # budget
                _repl(mesh, (NS, K), i32),      # sp_tokens
                _repl(mesh, (NS, K), i32),      # sp_next
                _repl(mesh, (), i32),           # done_state
                _repl(mesh, (), i32),           # eos_id
                _repl(mesh, (), i32),           # pad_id
                key_sds,                        # rng
                _repl(mesh, (), jnp.float32),   # temperature
                n_steps, True, "gather",
            )
            .compile()
        )
        ma = compiled.memory_analysis()
        total_gb = (
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
        ) / GB
        assert total_gb < 95.0, total_gb
