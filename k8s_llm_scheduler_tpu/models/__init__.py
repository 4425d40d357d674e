"""Decision-model families in functional JAX: Llama 3.x dense
(models/llama.py), latent attention with sparse experts
(models/mla_moe.py) and shortcut-connected double layers over latent
attention with identity experts (models/mla_scmoe.py)."""

from k8s_llm_scheduler_tpu.models.configs import (  # noqa: F401
    LLAMA_3_1_8B,
    LLAMA_3_2_1B,
    LLAMA_3_3_70B,
    TINY,
    LlamaConfig,
    MlaMoeConfig,
    MlaScmoeConfig,
    get_config,
)


def family(cfg):
    """The model module a config's TYPE selects. Each has `init_params`,
    `cache_token_shapes(cfg)` (the per-token trailing shapes of its cache
    tuple: (k, v) here, (c_kv, k_r) there), `cache_layers(cfg)` (the cache
    tuple's leading axis: the attention sublayers, `n_layers` unless a layer
    attends more than once), `COUNTERS` (what its wave forwards count on the
    device, may be empty) and the three forwards of the decision path:
    `forward_prefill_kv`, `forward_prefill_suffix_dense`,
    `forward_block_decode`."""
    if isinstance(cfg, MlaScmoeConfig):
        from k8s_llm_scheduler_tpu.models import mla_scmoe

        return mla_scmoe
    if isinstance(cfg, MlaMoeConfig):
        from k8s_llm_scheduler_tpu.models import mla_moe

        return mla_moe
    from k8s_llm_scheduler_tpu.models import llama

    return llama
