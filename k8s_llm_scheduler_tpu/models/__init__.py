"""Decision-model families in functional JAX: Llama 3.x dense
(models/llama.py), latent attention with sparse experts
(models/mla_moe.py), shortcut-connected double layers over latent
attention with identity experts (models/mla_scmoe.py), gated-delta-rule
linear attention, three layers to one of gated softmax attention, over
sparse experts (models/gdn_moe.py), Mamba-2 state-space mixers, nine
layers to one of softmax attention without position encoding, over dense
SwiGLUs (models/mamba2_hybrid.py) and window attention, three layers to one
of global attention without position encoding, each beside a sparse-expert
feed-forward in a parallel block (models/cohere2_moe.py)."""

from k8s_llm_scheduler_tpu.models.configs import (  # noqa: F401
    LLAMA_3_1_8B,
    LLAMA_3_2_1B,
    LLAMA_3_3_70B,
    TINY,
    Cohere2MoeConfig,
    GdnMoeConfig,
    LlamaConfig,
    Mamba2HybridConfig,
    MlaMoeConfig,
    MlaScmoeConfig,
    get_config,
)


def family(cfg):
    """The model module a config's TYPE selects. Each has `init_params`,
    `cache_token_shapes(cfg)` (the per-token trailing shapes of its cache
    tuple: (k, v) here, (c_kv, k_r) there), `cache_layers(cfg)` (the cache
    tuple's leading axis: the attention sublayers, `n_layers` unless a layer
    attends more than once), `state_shapes(cfg)` (what a SEQUENCE carries
    besides its tokens' cache: (trailing shape, dtype) of each member of a
    per-sequence state, `()` for a family that has none) with
    `state_layers(cfg)` (the state tuple's leading axis), `COUNTERS` (what
    its wave forwards count on the device, may be empty) and the three
    forwards of the decision path: `forward_prefill_kv`,
    `forward_prefill_suffix_dense`, `forward_block_decode`. Where
    `state_shapes` is not empty the three take the state as `state=` and
    return it behind the cache (prefix prefill: after `seq_lens` tokens;
    suffix: each row's, seeded from the prefix's; block decode: advanced by
    `blk_len`)."""
    if isinstance(cfg, Cohere2MoeConfig):
        from k8s_llm_scheduler_tpu.models import cohere2_moe

        return cohere2_moe
    if isinstance(cfg, Mamba2HybridConfig):
        from k8s_llm_scheduler_tpu.models import mamba2_hybrid

        return mamba2_hybrid
    if isinstance(cfg, GdnMoeConfig):
        from k8s_llm_scheduler_tpu.models import gdn_moe

        return gdn_moe
    if isinstance(cfg, MlaScmoeConfig):
        from k8s_llm_scheduler_tpu.models import mla_scmoe

        return mla_scmoe
    if isinstance(cfg, MlaMoeConfig):
        from k8s_llm_scheduler_tpu.models import mla_moe

        return mla_moe
    from k8s_llm_scheduler_tpu.models import llama

    return llama
