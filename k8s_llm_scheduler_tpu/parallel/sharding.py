"""GSPMD partition specs for the Llama param pytree and engine state.

Megatron-style tensor parallelism expressed purely as weight shardings —
XLA inserts the all-reduces (reference equivalent: whatever HF's hosted
deployment does server-side behind scheduler.py:425, invisible to the
reference's code):

- wq/wk/wv shard the HEAD (output) dim over tp  -> column parallel
- wo shards the head (input) dim over tp        -> row parallel, psum after
- w_gate/w_up shard d_ff over tp                -> column parallel
- w_down shards d_ff (input) over tp            -> row parallel, psum after
- embedding shards the vocab dim over tp (logits come out vocab-sharded,
  argmax/sample runs sharded then psums)
- layer norms replicated

The stacked-layer leading axis (L) is never sharded — scan iterates it.
An optional fsdp axis shards the remaining weight dim for training.
KV cache pages shard the kv-head dim over tp; page tables replicate.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.models.llama import Params


def param_specs(
    cfg: LlamaConfig,
    tp: str | None = "tp",
    fsdp: str | None = None,
) -> Params:
    """PartitionSpec pytree matching models.llama.init_params structure."""
    specs: Params = {
        "embed": P(tp, None),
        "final_norm": P(None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, fsdp, tp),
            "wk": P(None, fsdp, tp),
            "wv": P(None, fsdp, tp),
            "wo": P(None, tp, fsdp),
            "mlp_norm": P(None, None),
            "w_gate": P(None, fsdp, tp),
            "w_up": P(None, fsdp, tp),
            "w_down": P(None, tp, fsdp),
        },
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(fsdp, tp)
    return specs


def kv_cache_spec(tp: str | None = "tp") -> P:
    """[L, num_pages, page_size, n_kv, hd] — shard kv heads over tp."""
    return P(None, None, None, tp, None)


def named_shardings(mesh: Mesh, specs: Params) -> Params:
    """PartitionSpec pytree -> NamedSharding pytree on `mesh` (P is a
    tuple subclass, hence the is_leaf)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def shard_params(params: Params, mesh: Mesh, specs: Params | None = None,
                 cfg: LlamaConfig | None = None) -> Params:
    """Place a param pytree onto the mesh with NamedShardings."""
    if specs is None:
        assert cfg is not None, "need cfg to derive specs"
        specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        jax.device_put, params, named_shardings(mesh, specs)
    )


def validate_specs_divisibility(cfg: LlamaConfig, mesh: Mesh, tp: str = "tp") -> None:
    """TP axis must divide heads/kv-heads/d_ff/vocab, or GSPMD pads
    inefficiently. Raise early with a clear message."""
    size = mesh.shape.get(tp, 1)
    problems = []
    if cfg.n_heads % size:
        problems.append(f"n_heads={cfg.n_heads} % tp={size}")
    if cfg.n_kv_heads % size:
        problems.append(f"n_kv_heads={cfg.n_kv_heads} % tp={size}")
    if cfg.d_ff % size:
        problems.append(f"d_ff={cfg.d_ff} % tp={size}")
    if cfg.vocab_size % size:
        problems.append(f"vocab={cfg.vocab_size} % tp={size}")
    if problems:
        raise ValueError(f"model {cfg.name} not divisible by tp axis: {problems}")
