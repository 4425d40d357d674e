"""Model-family configurations: the Llama dense family, the latent-
attention sparse-expert family (`MlaMoeConfig`, models/mla_moe.py), the
shortcut-connected double layer over it (`MlaScmoeConfig`,
models/mla_scmoe.py), the gated-delta-rule / gated-attention hybrid over
sparse experts (`GdnMoeConfig`, models/gdn_moe.py), the Mamba-2 /
attention hybrid with dense SwiGLUs (`Mamba2HybridConfig`,
models/mamba2_hybrid.py) and window / global attention in parallel blocks
over sparse experts (`Cohere2MoeConfig`, models/cohere2_moe.py).

The reference consumes Llama-3.3-70B-Instruct behind the HuggingFace API
(reference scheduler.py:425, config.yaml:8); the BASELINE ladder also names
Llama-3.2-1B and Llama-3.1-8B (BASELINE.json configs). These are the public
architecture hyperparameters for those checkpoints, plus a TINY config for
tests/benches that exercises every code path (GQA, RoPE scaling, stacked
scan) at toy scale.

All sizes are chosen/padded with the TPU in mind: vocab and hidden dims are
multiples of 128 (MXU lane width), head_dim 64/128 (VPU/MXU friendly).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Llama-3.x rope frequency scaling (the "llama3" scheme)."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position: int = 8192


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rope_scaling: RopeScaling | None = None
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def __post_init__(self) -> None:
        assert self.d_model % self.n_heads == 0
        assert self.n_heads % self.n_kv_heads == 0

    def matmul_flops_per_token(self) -> float:
        """Dense matmul FLOPs of one token's forward pass (2 a multiply-add):
        the books observability/profiler.py keeps its MFU against."""
        d, hd = self.d_model, self.head_dim
        attn_proj = (
            d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
            + self.n_heads * hd * d
        )
        return 2.0 * (
            self.n_layers * (attn_proj + 3 * d * self.d_ff)
            + d * self.vocab_size
        )

    def attn_flops_per_key(self) -> float:
        """Score + value FLOPs of one token against one key, all layers."""
        return 4.0 * self.n_layers * self.n_heads * self.head_dim


def _mla_attn_params(cfg) -> int:
    """Matrix parameters of one latent-attention sublayer (norms left out)."""
    d, h = cfg.d_model, cfg.n_heads
    return (
        d * cfg.q_lora_rank + cfg.q_lora_rank * h * cfg.qk_head_dim
        + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        + h * cfg.v_head_dim * d
    )


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """Latent attention (MLA) over one leading dense stack and a stack of
    sigmoid-routed sparse-expert layers with a shared expert: the
    `glm4_moe_lite` / DeepSeek-V3 layer (models/mla_moe.py writes the
    equations out). Published key names are mapped once, in `from_hf`.

    `expert_first` / `expert_count` are the range of routed experts held
    HERE: the router always scores all `n_routed_experts`, the layer
    computes its own experts' part (an expert-parallel share; the whole
    range on one chip)."""

    name: str
    vocab_size: int
    d_model: int
    n_dense_layers: int      # leading layers with a dense SwiGLU (first_k_dense_replace)
    n_moe_layers: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int                # dense layers' SwiGLU width (intermediate_size)
    d_ff_expert: int         # one expert's width (moe_intermediate_size)
    n_routed_experts: int
    n_shared_experts: int
    n_experts_per_tok: int
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    expert_first: int = 0
    expert_count: int | None = None  # None: every routed expert
    max_seq_len: int = 8192
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = False

    # What the code shared with models/mla_scmoe.py (the attention sublayer,
    # `route`, `routed_experts`) asks of a config, as this family has it:
    # sigmoid scores, no identity experts among the router's outputs (None:
    # not counted either), latents at their normed scale.
    router_score = "sigmoid"
    n_zero_experts = None
    q_lora_scale = 1.0
    kv_lora_scale = 1.0

    def __post_init__(self) -> None:
        if self.tie_embeddings:
            raise ValueError(f"{self.name}: MlaMoeConfig serves an untied output head only")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"{self.name}: qk_rope_head_dim must be even")
        if not 0 < self.n_experts_per_tok <= self.n_routed_experts:
            raise ValueError(f"{self.name}: n_experts_per_tok outside 1..n_routed_experts")
        if self.expert_first < 0 or self.expert_first + self.experts_held > self.n_routed_experts:
            raise ValueError(f"{self.name}: held expert range outside the routed experts")

    @property
    def n_layers(self) -> int:
        return self.n_dense_layers + self.n_moe_layers

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts if self.expert_count is None else self.expert_count

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @classmethod
    def from_hf(cls, name: str, conf: dict, **overrides) -> "MlaMoeConfig":
        """From the published `config.json` keys (glm4_moe_lite)."""
        if conf.get("n_group", 1) != 1 or conf.get("topk_group", 1) != 1:
            raise ValueError(f"{name}: group-limited routing (n_group/topk_group > 1) is not served")
        if conf.get("topk_method", "noaux_tc") != "noaux_tc":
            raise ValueError(f"{name}: only topk_method noaux_tc (sigmoid scores + selection bias)")
        kw = dict(
            name=name, vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
            n_dense_layers=conf["first_k_dense_replace"],
            n_moe_layers=conf["num_hidden_layers"] - conf["first_k_dense_replace"],
            n_heads=conf["num_attention_heads"], q_lora_rank=conf["q_lora_rank"],
            kv_lora_rank=conf["kv_lora_rank"], qk_nope_head_dim=conf["qk_nope_head_dim"],
            qk_rope_head_dim=conf["qk_rope_head_dim"], v_head_dim=conf["v_head_dim"],
            d_ff=conf["intermediate_size"], d_ff_expert=conf["moe_intermediate_size"],
            n_routed_experts=conf["n_routed_experts"], n_shared_experts=conf["n_shared_experts"],
            n_experts_per_tok=conf["num_experts_per_tok"],
            routed_scaling_factor=conf["routed_scaling_factor"],
            norm_topk_prob=conf["norm_topk_prob"],
            max_seq_len=conf["max_position_embeddings"], rope_theta=float(conf["rope_theta"]),
            rms_eps=conf["rms_norm_eps"], tie_embeddings=conf["tie_word_embeddings"],
        )
        kw.update(overrides)
        return cls(**kw)

    attn_params = _mla_attn_params

    def matmul_flops_per_token(self) -> float:
        """ACTIVE matmul FLOPs of one token: in an expert layer the router,
        the experts a token is sent to and the shared experts."""
        d = self.d_model
        expert = 3 * d * self.d_ff_expert
        moe = (d * self.n_routed_experts
               + (self.n_experts_per_tok + self.n_shared_experts) * expert)
        return 2.0 * (
            self.n_layers * self.attn_params()
            + self.n_dense_layers * 3 * d * self.d_ff
            + self.n_moe_layers * moe
            + d * self.vocab_size
        )

    def attn_flops_per_key(self) -> float:
        """Absorbed form, as every forward runs it: a head's
        query scores the latent (kv_lora_rank + rope) and sums it
        (kv_lora_rank)."""
        return 2.0 * self.n_layers * self.n_heads * (
            2 * self.kv_lora_rank + self.qk_rope_head_dim
        )


@dataclasses.dataclass(frozen=True)
class MlaScmoeConfig:
    """Shortcut-connected double layers over latent attention: the
    LongCat-Flash layer (models/mla_scmoe.py writes the equations out). A
    layer holds TWO attention sublayers and two dense feed-forwards, and one
    routed feed-forward that reads the stream after the first attention and
    joins it after the second dense feed-forward. The router has
    `n_routed_experts + n_zero_experts` outputs, softmax scores that are not
    renormalised; the last `n_zero_experts` are identity experts that return
    their input and hold no weights.

    `expert_first` / `expert_count`: the range of FEED-FORWARD experts held
    here, as in MlaMoeConfig (an expert-parallel share); the identity
    experts are applied wherever the token is."""

    name: str
    vocab_size: int
    d_model: int
    n_layers: int            # double layers
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int                # each dense feed-forward's width (ffn_hidden_size)
    d_ff_expert: int         # one expert's width (expert_ffn_hidden_size)
    n_routed_experts: int    # feed-forward experts
    n_zero_experts: int      # identity experts behind them in the router's outputs
    n_experts_per_tok: int
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    expert_first: int = 0
    expert_count: int | None = None  # None: every feed-forward expert
    max_seq_len: int = 8192
    rope_theta: float = 10000000.0
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = False

    router_score = "softmax"

    def __post_init__(self) -> None:
        if self.tie_embeddings:
            raise ValueError(f"{self.name}: MlaScmoeConfig serves an untied output head only")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"{self.name}: qk_rope_head_dim must be even")
        if not 0 < self.n_experts_per_tok <= self.n_router_outputs:
            raise ValueError(f"{self.name}: n_experts_per_tok outside 1..router outputs")
        if self.expert_first < 0 or self.expert_first + self.experts_held > self.n_routed_experts:
            raise ValueError(f"{self.name}: held expert range outside the feed-forward experts")

    @property
    def n_router_outputs(self) -> int:
        return self.n_routed_experts + self.n_zero_experts

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts if self.expert_count is None else self.expert_count

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def q_lora_scale(self) -> float:
        """What the normed query latent is multiplied by (mla_scale_q_lora)."""
        return (self.d_model / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def kv_lora_scale(self) -> float:
        """What the normed key/value latent is multiplied by; the rotary
        key is not scaled (mla_scale_kv_lora)."""
        return (self.d_model / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0

    @classmethod
    def from_hf(cls, name: str, conf: dict, **overrides) -> "MlaScmoeConfig":
        """From the published `config.json` keys (longcat_flash)."""
        if conf.get("attention_method", "MLA") != "MLA":
            raise ValueError(f"{name}: only attention_method MLA")
        if conf.get("zero_expert_type", "identity") != "identity":
            raise ValueError(f"{name}: only identity zero-computation experts")
        kw = dict(
            name=name, vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
            n_layers=conf["num_layers"], n_heads=conf["num_attention_heads"],
            q_lora_rank=conf["q_lora_rank"], kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_head_dim=conf["qk_nope_head_dim"], qk_rope_head_dim=conf["qk_rope_head_dim"],
            v_head_dim=conf["v_head_dim"], d_ff=conf["ffn_hidden_size"],
            d_ff_expert=conf["expert_ffn_hidden_size"],
            n_routed_experts=conf["n_routed_experts"], n_zero_experts=conf["zero_expert_num"],
            n_experts_per_tok=conf["moe_topk"],
            routed_scaling_factor=float(conf["routed_scaling_factor"]),
            mla_scale_q_lora=conf["mla_scale_q_lora"], mla_scale_kv_lora=conf["mla_scale_kv_lora"],
            max_seq_len=conf["max_position_embeddings"], rope_theta=float(conf["rope_theta"]),
            rms_eps=conf["rms_norm_eps"],
        )
        kw.update(overrides)
        return cls(**kw)

    attn_params = _mla_attn_params

    def matmul_flops_per_token(self) -> float:
        """Matmul FLOPs of one token AS THIS SHARE RUNS IT: two attention
        sublayers and two dense feed-forwards a layer, the router over all
        its outputs, and of the `n_experts_per_tok` picks the part that
        falls, under even routing, on the feed-forward experts held HERE
        (an identity expert multiplies nothing; an expert held elsewhere is
        another chip's). The measured counterpart of that expectation is
        the wave counter `moe_assignments`."""
        d = self.d_model
        held_picks = self.n_experts_per_tok * self.experts_held / self.n_router_outputs
        layer = (2 * self.attn_params() + 2 * 3 * d * self.d_ff
                 + d * self.n_router_outputs + held_picks * 3 * d * self.d_ff_expert)
        return 2.0 * (self.n_layers * layer + d * self.vocab_size)

    def attn_flops_per_key(self) -> float:
        """Absorbed form, in each of the 2 x n_layers attention sublayers."""
        return 2.0 * 2 * self.n_layers * self.n_heads * (
            2 * self.kv_lora_rank + self.qk_rope_head_dim
        )


@dataclasses.dataclass(frozen=True)
class GdnMoeConfig:
    """Gated-delta-rule linear attention and gated softmax attention in a
    fixed period over sparse experts with a gated shared expert: the
    `qwen3_next` layer (models/gdn_moe.py writes the equations out). Layer i
    attends (16 query / 2 KV heads) where (i + 1) % `full_attention_interval`
    == 0 and is a delta-rule mixer otherwise; every layer's feed-forward is
    the sparse block. A delta-rule layer keeps no per-token cache: it keeps a
    STATE a sequence, `gdn_value_heads` matrices [gdn_key_dim, gdn_value_dim]
    in float32 and the last `conv_kernel - 1` inputs of its convolution.

    `expert_first` / `expert_count`: the range of routed experts held here,
    as in MlaMoeConfig (an expert-parallel share)."""

    name: str
    vocab_size: int
    d_model: int
    n_layers: int                 # whole periods of full_attention_interval
    full_attention_interval: int
    n_heads: int                  # the attention layers' query heads
    n_kv_heads: int
    head_dim: int
    partial_rotary_factor: float  # the share of head_dim that is rotated
    gdn_key_heads: int            # linear_num_key_heads
    gdn_value_heads: int          # linear_num_value_heads
    gdn_key_dim: int              # linear_key_head_dim
    gdn_value_dim: int            # linear_value_head_dim
    conv_kernel: int              # linear_conv_kernel_dim
    d_ff_expert: int              # one expert's width (moe_intermediate_size)
    d_ff_shared: int              # the shared expert's width
    n_routed_experts: int
    n_experts_per_tok: int
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    expert_first: int = 0
    expert_count: int | None = None  # None: every routed expert
    max_seq_len: int = 8192
    rope_theta: float = 10000000.0
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = False

    # what `route` and `routed_experts` of models/mla_moe.py ask of a config
    router_score = "softmax"
    n_zero_experts = None

    def __post_init__(self) -> None:
        if self.tie_embeddings:
            raise ValueError(f"{self.name}: GdnMoeConfig serves an untied output head only")
        if self.n_layers % self.full_attention_interval:
            raise ValueError(f"{self.name}: n_layers must be whole periods of full_attention_interval")
        if self.n_heads % self.n_kv_heads or self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(f"{self.name}: query / value heads must be a multiple of their key heads")
        if self.rotary_dim % 2:
            raise ValueError(f"{self.name}: the rotated part of a head must be even")
        if not 0 < self.n_experts_per_tok <= self.n_routed_experts:
            raise ValueError(f"{self.name}: n_experts_per_tok outside 1..n_routed_experts")
        if self.expert_first < 0 or self.expert_first + self.experts_held > self.n_routed_experts:
            raise ValueError(f"{self.name}: held expert range outside the routed experts")

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.full_attention_interval

    @property
    def n_attn_layers(self) -> int:
        return self.n_periods

    @property
    def n_gdn_layers(self) -> int:
        return self.n_layers - self.n_periods

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts if self.expert_count is None else self.expert_count

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def gdn_key_width(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def gdn_value_width(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def conv_width(self) -> int:
        """Channels the causal convolution runs over: [q | k | v]."""
        return 2 * self.gdn_key_width + self.gdn_value_width

    @classmethod
    def from_hf(cls, name: str, conf: dict, **overrides) -> "GdnMoeConfig":
        """From the published `config.json` keys (qwen3_next)."""
        if conf.get("decoder_sparse_step", 1) != 1 or conf.get("mlp_only_layers"):
            raise ValueError(f"{name}: every layer's feed-forward is served as the sparse block")
        if conf.get("rope_scaling") is not None or conf.get("use_sliding_window", False):
            raise ValueError(f"{name}: rope scaling and sliding windows are not served")
        kw = dict(
            name=name, vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
            n_layers=conf["num_hidden_layers"],
            full_attention_interval=conf["full_attention_interval"],
            n_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
            head_dim=conf["head_dim"], partial_rotary_factor=conf["partial_rotary_factor"],
            gdn_key_heads=conf["linear_num_key_heads"], gdn_value_heads=conf["linear_num_value_heads"],
            gdn_key_dim=conf["linear_key_head_dim"], gdn_value_dim=conf["linear_value_head_dim"],
            conv_kernel=conf["linear_conv_kernel_dim"],
            d_ff_expert=conf["moe_intermediate_size"],
            d_ff_shared=conf["shared_expert_intermediate_size"],
            n_routed_experts=conf["num_experts"], n_experts_per_tok=conf["num_experts_per_tok"],
            norm_topk_prob=conf["norm_topk_prob"],
            max_seq_len=conf["max_position_embeddings"], rope_theta=float(conf["rope_theta"]),
            rms_eps=conf["rms_norm_eps"], tie_embeddings=conf["tie_word_embeddings"],
        )
        kw.update(overrides)
        return cls(**kw)

    def gdn_params(self) -> int:
        """Matrix parameters of one delta-rule mixer (the convolution's
        taps, the norms and the two per-head vectors left out)."""
        d = self.d_model
        return (d * (2 * self.gdn_key_width + 2 * self.gdn_value_width)
                + d * 2 * self.gdn_value_heads + self.gdn_value_width * d)

    def attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return d * self.n_heads * 2 * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d

    def gdn_state_flops_per_token(self) -> float:
        """What a token costs a delta-rule layer beside its projections,
        counted per token as the recurrence states it: S^T k, the rank-one
        update and S^T q, 2 x dk x dv each a value head."""
        return 3 * 2.0 * self.gdn_value_heads * self.gdn_key_dim * self.gdn_value_dim

    def matmul_flops_per_token(self) -> float:
        """Matmul FLOPs of one token AS THIS SHARE RUNS IT: the mixers and
        the shared expert whole, the router over all its outputs, and of the
        `n_experts_per_tok` picks the part that falls, under even routing,
        on the experts held here."""
        d = self.d_model
        held_picks = self.n_experts_per_tok * self.experts_held / self.n_routed_experts
        moe = (d * self.n_routed_experts + held_picks * 3 * d * self.d_ff_expert
               + 3 * d * self.d_ff_shared + d)
        return (2.0 * (self.n_gdn_layers * self.gdn_params() + self.n_attn_layers * self.attn_params()
                       + self.n_layers * moe + d * self.vocab_size)
                + self.n_gdn_layers * self.gdn_state_flops_per_token())

    def attn_flops_per_key(self) -> float:
        """Score + value FLOPs of one token against one key, in the layers
        that attend (a delta-rule layer's cost does not grow with context)."""
        return 4.0 * self.n_attn_layers * self.n_heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class Mamba2HybridConfig:
    """Mamba-2 state-space mixers and softmax attention without position
    encoding in a fixed period, every layer with a dense SwiGLU, a tied
    output head and four scalar multipliers: the `granitemoehybrid` layer
    with no experts (models/mamba2_hybrid.py writes the equations out). The
    layers listed in `attn_layers` attend, the others are Mamba-2 mixers; the
    pattern repeats whole every `period` layers. A Mamba-2 layer keeps no
    per-token cache: it keeps a STATE a sequence, `ssm_heads` matrices
    [ssm_head_dim, ssm_state] in float32 and the last `conv_kernel - 1`
    inputs of its convolution."""

    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    attn_layers: tuple[int, ...]  # the layers whose layer_types entry is "attention"
    n_heads: int                  # the attention layers' query heads
    n_kv_heads: int
    d_ff: int                     # every layer's SwiGLU width (shared_intermediate_size)
    ssm_heads: int                # mamba_n_heads
    ssm_head_dim: int             # mamba_d_head
    ssm_state: int                # mamba_d_state: B and C, one group shared by every head
    conv_kernel: int              # mamba_d_conv
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float   # the softmax's scale, in place of head_dim^-1/2
    logits_scaling: float         # the head's logits are divided by it
    max_seq_len: int = 131072
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = True

    def __post_init__(self) -> None:
        if not self.tie_embeddings:
            raise ValueError(f"{self.name}: Mamba2HybridConfig serves a tied output head only")
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.name}: query heads must divide d_model and be a multiple of KV heads")
        per = self.period
        if not self.attn_layers or self.n_layers % len(self.attn_layers) or any(
                i != p * per + self.attn_position for p, i in enumerate(self.attn_layers)):
            raise ValueError(f"{self.name}: the attention layers must repeat at one place of a whole period")

    @property
    def period(self) -> int:
        return self.n_layers // len(self.attn_layers)

    @property
    def attn_position(self) -> int:
        """Where in its period a layer attends."""
        return self.attn_layers[0] % self.period

    @property
    def n_periods(self) -> int:
        return len(self.attn_layers)

    @property
    def n_attn_layers(self) -> int:
        return len(self.attn_layers)

    @property
    def n_ssm_layers(self) -> int:
        return self.n_layers - self.n_attn_layers

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the causal convolution runs over: [x | B | C]."""
        return self.ssm_inner + 2 * self.ssm_state

    @classmethod
    def from_hf(cls, name: str, conf: dict, **overrides) -> "Mamba2HybridConfig":
        """From the published `config.json` keys (granitemoehybrid)."""
        if conf.get("num_local_experts", 0) or conf.get("num_experts_per_tok", 0):
            raise ValueError(f"{name}: sparse experts (num_local_experts > 0) are not served")
        if conf.get("position_embedding_type", "nope") != "nope":
            raise ValueError(f"{name}: only position_embedding_type nope")
        if conf.get("mamba_proj_bias", False) or not conf.get("mamba_conv_bias", True):
            raise ValueError(f"{name}: bias-free projections and a biased convolution only")
        if conf["mamba_n_groups"] != 1:
            raise ValueError(f"{name}: one group of B and C for every head (mamba_n_groups 1) only")
        if conf["mamba_expand"] * conf["hidden_size"] != conf["mamba_n_heads"] * conf["mamba_d_head"]:
            raise ValueError(f"{name}: mamba_n_heads x mamba_d_head must be mamba_expand x hidden_size")
        types = conf["layer_types"]
        if len(types) != conf["num_hidden_layers"] or set(types) - {"mamba", "attention"}:
            raise ValueError(f"{name}: layer_types must name every layer mamba or attention")
        kw = dict(
            name=name, vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
            n_layers=conf["num_hidden_layers"],
            attn_layers=tuple(i for i, t in enumerate(types) if t == "attention"),
            n_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
            d_ff=conf["shared_intermediate_size"], ssm_heads=conf["mamba_n_heads"],
            ssm_head_dim=conf["mamba_d_head"], ssm_state=conf["mamba_d_state"], conv_kernel=conf["mamba_d_conv"],
            embedding_multiplier=float(conf["embedding_multiplier"]),
            residual_multiplier=float(conf["residual_multiplier"]),
            attention_multiplier=float(conf["attention_multiplier"]),
            logits_scaling=float(conf["logits_scaling"]),
            max_seq_len=conf["max_position_embeddings"], rms_eps=conf["rms_norm_eps"],
            tie_embeddings=conf["tie_word_embeddings"],
        )
        kw.update(overrides)
        return cls(**kw)

    def ssm_params(self) -> int:
        """Matrix parameters of one Mamba-2 mixer: W_in ([z | x B C | dt])
        and W_out."""
        d = self.d_model
        return d * (self.ssm_inner + self.conv_width + self.ssm_heads) + self.ssm_inner * d

    def attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d

    def ssm_state_flops_per_token(self) -> float:
        """What a token costs a Mamba-2 layer beside its projections,
        counted per token as the recurrence states it: the update x B^T and
        the read-out S C, 2 x head_dim x d_state each a head."""
        return 2 * 2.0 * self.ssm_heads * self.ssm_head_dim * self.ssm_state

    def matmul_flops_per_token(self) -> float:
        """Matmul FLOPs of one token: the mixers, every layer's SwiGLU, the
        state products of the Mamba-2 layers and the tied head."""
        d = self.d_model
        return (2.0 * (self.n_ssm_layers * self.ssm_params() + self.n_attn_layers * self.attn_params()
                       + self.n_layers * 3 * d * self.d_ff + d * self.vocab_size)
                + self.n_ssm_layers * self.ssm_state_flops_per_token())

    def attn_flops_per_key(self) -> float:
        """Score + value FLOPs of one token against one key, in the layers
        that attend (a Mamba-2 layer's cost does not grow with context)."""
        return 4.0 * self.n_attn_layers * self.n_heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    """Window and global attention in a fixed period, each layer a PARALLEL
    block (attention and a sparse-expert feed-forward on one LayerNorm of
    the stream), four averaged shared experts and a tied head: the
    `cohere2_moe` layer (Command A+; models/cohere2_moe.py writes the
    equations out). The layers in `global_layers` attend causally with no
    position encoding; the others see the last `window` positions and are
    rotated. `expert_first` / `expert_count`: the range of routed experts
    held here, as in MlaMoeConfig (an expert-parallel share)."""

    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    global_layers: tuple[int, ...]  # the layers whose layer_types entry is "full_attention"
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int                     # sliding_window: a window layer's query sees this many positions
    d_ff_expert: int                # one routed or shared expert's width (intermediate_size)
    n_routed_experts: int
    n_shared_experts: int
    n_experts_per_tok: int
    norm_topk_prob: bool = True
    logit_scale: float = 1.0
    expert_first: int = 0
    expert_count: int | None = None  # None: every routed expert
    max_seq_len: int = 200000
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = True

    # what `route`, `routed_experts` and `shared_experts` of models/mla_moe.py
    # ask of a config: sigmoid scores without a selection bias, no identity
    # experts, no routed scaling; the shared experts AVERAGED
    # (shared_expert_combination_strategy "average")
    router_score = "sigmoid"
    n_zero_experts = None
    routed_scaling_factor = 1.0

    def __post_init__(self) -> None:
        if not self.tie_embeddings:
            raise ValueError(f"{self.name}: Cohere2MoeConfig serves a tied output head only")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.name}: query heads must be a multiple of KV heads")
        if self.head_dim % 2:
            raise ValueError(f"{self.name}: a rotated head must be even")
        per = self.period
        if not self.global_layers or self.n_layers % len(self.global_layers) or any(
                i != p * per + self.global_position for p, i in enumerate(self.global_layers)):
            raise ValueError(f"{self.name}: the global layers must repeat at one place of a whole period")
        if not 0 < self.n_experts_per_tok <= self.n_routed_experts:
            raise ValueError(f"{self.name}: n_experts_per_tok outside 1..n_routed_experts")
        if self.expert_first < 0 or self.expert_first + self.experts_held > self.n_routed_experts:
            raise ValueError(f"{self.name}: held expert range outside the routed experts")

    @property
    def period(self) -> int:
        return self.n_layers // len(self.global_layers)

    @property
    def global_position(self) -> int:
        """Where in its period a layer attends globally."""
        return self.global_layers[0] % self.period

    @property
    def n_periods(self) -> int:
        return len(self.global_layers)

    @property
    def n_window_layers(self) -> int:
        return self.n_layers - len(self.global_layers)

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts if self.expert_count is None else self.expert_count

    @property
    def d_ff_shared(self) -> int:
        """The shared experts as one SwiGLU: their widths side by side."""
        return self.n_shared_experts * self.d_ff_expert

    @property
    def shared_scale(self) -> float:
        """The shared experts' mean: their sum times 1 / n_shared_experts."""
        return 1.0 / self.n_shared_experts

    @classmethod
    def from_hf(cls, name: str, conf: dict, **overrides) -> "Cohere2MoeConfig":
        """From the published `config.json` keys (cohere2_moe). `layer_types`
        may be the published list whole: its first `num_hidden_layers`
        entries are read."""
        refused = {
            "shared_expert_combination_strategy other than average":
                conf.get("shared_expert_combination_strategy", "average") != "average",
            "use_qk_norm": conf.get("use_qk_norm", False),
            "first_k_dense_replace > 0 (leading dense layers)": conf.get("first_k_dense_replace", 0) > 0,
            "attention_bias": conf.get("attention_bias", False),
            "rotary_pct other than 1": conf.get("rotary_pct", 1) != 1,
            "use_parallel_block false": not conf.get("use_parallel_block", True),
            "expert_selection_fn other than sigmoid": conf.get("expert_selection_fn", "sigmoid") != "sigmoid",
            "use_gated_activation false": not conf.get("use_gated_activation", True),
            "hidden_act other than silu": conf.get("hidden_act", "silu") != "silu",
            "rope scaling": (conf.get("rope_scaling") is not None
                             or conf.get("rope_parameters", {}).get("rope_type", "default") != "default"),
        }
        for what, on in refused.items():
            if on:
                raise ValueError(f"{name}: {what} is not served by models/cohere2_moe.py")
        n = conf["num_hidden_layers"]
        types = conf["layer_types"][:n]
        if len(types) != n or set(types) - {"sliding_attention", "full_attention"}:
            raise ValueError(f"{name}: layer_types must name every layer sliding_attention or full_attention")
        kw = dict(
            name=name, vocab_size=conf["vocab_size"], d_model=conf["hidden_size"], n_layers=n,
            global_layers=tuple(i for i, t in enumerate(types) if t == "full_attention"),
            n_heads=conf["num_attention_heads"], n_kv_heads=conf["num_key_value_heads"],
            head_dim=conf["head_dim"], window=conf["sliding_window"],
            d_ff_expert=conf["intermediate_size"], n_routed_experts=conf["num_experts"],
            n_shared_experts=conf["num_shared_experts"], n_experts_per_tok=conf["num_experts_per_tok"],
            norm_topk_prob=conf["norm_topk_prob"], logit_scale=float(conf["logit_scale"]),
            max_seq_len=conf["max_position_embeddings"], rope_theta=float(conf["rope_theta"]),
            norm_eps=conf["layer_norm_eps"], tie_embeddings=conf["tie_word_embeddings"],
        )
        kw.update(overrides)
        return cls(**kw)

    def attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd

    def matmul_flops_per_token(self) -> float:
        """Matmul FLOPs of one token AS THIS SHARE RUNS IT: every layer's
        attention projections, the router over all its outputs, the shared
        experts whole and, of the `n_experts_per_tok` picks, the part that
        falls, under even routing, on the experts held here; the tied head."""
        d = self.d_model
        held_picks = self.n_experts_per_tok * self.experts_held / self.n_routed_experts
        ffn = d * self.n_routed_experts + (held_picks * 3 * d * self.d_ff_expert + 3 * d * self.d_ff_shared)
        return 2.0 * (self.n_layers * (self.attn_params() + ffn) + d * self.vocab_size)

    def attn_flops_per_key(self) -> float:
        """Score + value FLOPs of one token against one key it sees, all
        layers (a context within the window: every layer sees every key)."""
        return 4.0 * self.n_layers * self.n_heads * self.head_dim

    def attn_flops_per_token(self, ctx: float) -> float:
        """Score + value FLOPs of one token at context `ctx`: the global
        layers see every key, a window layer `window` of them at most."""
        per_key = 4.0 * self.n_heads * self.head_dim
        return per_key * (len(self.global_layers) * ctx + self.n_window_layers * min(ctx, self.window))


TINY = LlamaConfig(
    name="tiny",
    vocab_size=512,          # byte tokenizer fits in 512
    d_model=256,
    n_layers=4,
    n_heads=4,
    n_kv_heads=2,            # exercises GQA
    d_ff=512,
    max_seq_len=2048,
    rope_theta=10000.0,
    rope_scaling=None,
    tie_embeddings=True,
)

# A mid-size test config: big enough that kernels/meshes matter, small enough
# to run on one chip in seconds.
SMALL = LlamaConfig(
    name="small",
    vocab_size=512,
    d_model=1024,
    n_layers=8,
    n_heads=8,
    n_kv_heads=4,
    d_ff=2816,
    max_seq_len=8192,
    rope_theta=500000.0,
    tie_embeddings=True,
)

LLAMA_3_2_1B = LlamaConfig(
    name="llama-3.2-1b-instruct",
    vocab_size=128256,
    d_model=2048,
    n_layers=16,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    max_seq_len=131072,
    rope_theta=500000.0,
    rope_scaling=RopeScaling(factor=32.0),
    tie_embeddings=True,
)

LLAMA_3_1_8B = LlamaConfig(
    name="llama-3.1-8b-instruct",
    vocab_size=128256,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    max_seq_len=131072,
    rope_theta=500000.0,
    rope_scaling=RopeScaling(factor=8.0),
)

LLAMA_3_3_70B = LlamaConfig(
    name="llama-3.3-70b-instruct",
    vocab_size=128256,
    d_model=8192,
    n_layers=80,
    n_heads=64,
    n_kv_heads=8,
    d_ff=28672,
    max_seq_len=131072,
    rope_theta=500000.0,
    rope_scaling=RopeScaling(factor=8.0),
)

# Toy of the latent-attention sparse-expert family for the CPU tests: every
# mechanism present (a leading dense layer, two expert layers, a shared
# expert, a selection bias), every width shrunk.
TINY_MLA_MOE = MlaMoeConfig(
    name="tiny-mla-moe",
    vocab_size=512,
    d_model=64,
    n_dense_layers=1,
    n_moe_layers=2,
    n_heads=4,
    q_lora_rank=32,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    d_ff=128,
    d_ff_expert=32,
    n_routed_experts=8,
    n_shared_experts=1,
    n_experts_per_tok=2,
    routed_scaling_factor=1.8,
    max_seq_len=2048,
    rope_theta=10000.0,
)

# Toy of the shortcut-connected family for the CPU tests: two double layers,
# a share of the feed-forward experts (4 of 8), identity experts, both scale
# factors, every width shrunk.
TINY_MLA_SCMOE = MlaScmoeConfig(
    name="tiny-mla-scmoe",
    vocab_size=512,
    d_model=64,
    n_layers=2,
    n_heads=4,
    q_lora_rank=32,
    kv_lora_rank=16,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    d_ff=128,
    d_ff_expert=32,
    n_routed_experts=8,
    n_zero_experts=4,
    n_experts_per_tok=3,
    routed_scaling_factor=6.0,
    expert_count=4,
    max_seq_len=2048,
    rope_theta=10000.0,
)

# Toy of the delta-rule / attention hybrid for the CPU tests: two periods of
# three delta-rule layers and one gated attention, a share of the routed
# experts (4 of 16), a gated shared expert, every width shrunk (two value
# heads a key head and a quarter of the head rotated, as published).
TINY_GDN_MOE = GdnMoeConfig(
    name="tiny-gdn-moe",
    vocab_size=512,
    d_model=64,
    n_layers=8,
    full_attention_interval=4,
    n_heads=4,
    n_kv_heads=2,
    head_dim=32,
    partial_rotary_factor=0.25,
    gdn_key_heads=2,
    gdn_value_heads=4,
    gdn_key_dim=16,
    gdn_value_dim=16,
    conv_kernel=4,
    d_ff_expert=32,
    d_ff_shared=32,
    n_routed_experts=16,
    n_experts_per_tok=3,
    expert_count=4,
    max_seq_len=2048,
    rope_theta=10000.0,
)

# Toy of the Mamba-2 / attention hybrid for the CPU tests: two periods of
# five layers, attention at the third place of each (not the last, as
# published), every width shrunk, the four multipliers as published.
TINY_MAMBA2_HYBRID = Mamba2HybridConfig(
    name="tiny-mamba2-hybrid",
    vocab_size=512,
    d_model=64,
    n_layers=10,
    attn_layers=(2, 7),
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    ssm_heads=8,
    ssm_head_dim=16,
    ssm_state=32,
    conv_kernel=4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=1 / 16,
    logits_scaling=8.0,
    max_seq_len=2048,
)

# Toy of the window / global parallel-block family for the CPU tests: two
# periods of three window layers and one global layer, a window of 32 (so
# that the tests' prompts outrun it), a share of the routed experts (4 of
# 16), four averaged shared experts, a logit scale, every width shrunk.
TINY_COHERE2_MOE = Cohere2MoeConfig(
    name="tiny-cohere2-moe",
    vocab_size=512,
    d_model=64,
    n_layers=8,
    global_layers=(3, 7),
    n_heads=8,
    n_kv_heads=2,
    head_dim=16,
    window=32,
    d_ff_expert=32,
    n_routed_experts=16,
    n_shared_experts=4,
    n_experts_per_tok=4,
    expert_count=4,
    logit_scale=0.5,
    max_seq_len=2048,
    rope_theta=10000.0,
)

_REGISTRY = {
    c.name: c
    for c in (TINY, SMALL, LLAMA_3_2_1B, LLAMA_3_1_8B, LLAMA_3_3_70B, TINY_MLA_MOE,
              TINY_MLA_SCMOE, TINY_GDN_MOE, TINY_MAMBA2_HYBRID, TINY_COHERE2_MOE)
}


def get_config(name: str) -> (LlamaConfig | MlaMoeConfig | MlaScmoeConfig | GdnMoeConfig | Mamba2HybridConfig
                              | Cohere2MoeConfig):
    key = name.lower()
    if key not in _REGISTRY:
        raise KeyError(f"unknown model config {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]
