"""Model configuration dataclasses.

TPU-native re-design of the reference's per-arch ``ModelArgs`` dataclasses
(ref: shard/server/model/llama.py:11-24, gemma2.py:9-21, deepseek_v2.py:11-28).
Like the reference, a model config is constructed from an HF-style
``config.json`` dict, and the pipeline-stage bounds ``start_layer`` /
``end_layer`` ride along inside the config (ref: shard/utils.py:36-39 injects
them; sharding_weight.py:48-60 bakes them into the shard's config.json).

Unlike the reference we keep one base dataclass with arch-specific
subclasses registered in ``CONFIG_REGISTRY`` — resolution replaces the
reference's importlib trick (shard/utils.py:20-30).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass
class BaseConfig:
    """Fields shared by every decoder-only architecture we support."""

    model_type: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    # Pipeline-stage bounds, [start_layer, end_layer). Mirrors the reference's
    # dynamic-sharding config injection (shard/utils.py:36-39).
    start_layer: int = 0
    end_layer: Optional[int] = None
    # MLX-style grouped affine quantization descriptor, e.g.
    # {"group_size": 64, "bits": 4} (ref: shard/utils.py:54-65).
    quantization: Optional[dict] = None
    # KV-cache storage dtype for paged engines: "int8" stores per-row-per-
    # head-scaled codes ({d, s} pools, see cache.quantize_kv_rows); None/
    # "bf16" keeps the dense cache_dtype pool. Server/CLI --kv-dtype
    # overrides; checkpoints may pin it here.
    kv_cache_dtype: Optional[str] = None

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.end_layer is None:
            self.end_layer = self.num_hidden_layers
        if not (0 <= self.start_layer < self.end_layer <= self.num_hidden_layers):
            raise ValueError(
                f"Invalid stage bounds [{self.start_layer}, {self.end_layer}) "
                f"for a {self.num_hidden_layers}-layer model."
            )

    # -- stage placement helpers (semantics of sharding_weight.py:16-24) ----
    @property
    def is_first_stage(self) -> bool:
        return self.start_layer == 0

    @property
    def is_last_stage(self) -> bool:
        return self.end_layer == self.num_hidden_layers

    @property
    def num_local_layers(self) -> int:
        return self.end_layer - self.start_layer

    # Whether this stage needs the token-embedding table. Gemma-2 overrides:
    # its lm_head is tied to the embedding, so the LAST stage needs it too
    # (ref: shard/server/model/gemma2.py:23-24).
    @property
    def needs_embed(self) -> bool:
        return self.is_first_stage or (self.tie_word_embeddings and self.is_last_stage)

    @property
    def needs_head(self) -> bool:
        return self.is_last_stage

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "BaseConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
class LlamaConfig(BaseConfig):
    model_type: str = "llama"
    attention_bias: bool = False
    mlp_bias: bool = False


@dataclass
class Qwen3Config(LlamaConfig):
    model_type: str = "qwen3"


@dataclass
class Gemma2Config(BaseConfig):
    """Gemma-2: softcapped logits/attention, tied embeddings, alternating
    sliding/global attention (ref: shard/server/model/gemma2.py)."""

    model_type: str = "gemma2"
    head_dim: Optional[int] = 256
    rms_norm_eps: float = 1e-6
    final_logit_softcapping: float = 30.0
    attn_logit_softcapping: float = 50.0
    query_pre_attn_scalar: float = 256.0
    sliding_window: int = 4096
    tie_word_embeddings: bool = True


@dataclass
class DeepseekV2Config(BaseConfig):
    """DeepSeek-V2: MLA attention + fine-grained MoE with shared experts
    (ref: shard/server/model/deepseek_v2.py:11-28)."""

    model_type: str = "deepseek_v2"
    moe_intermediate_size: int = 1407
    n_shared_experts: Optional[int] = 2
    n_routed_experts: Optional[int] = 64
    routed_scaling_factor: float = 1.0
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    topk_method: str = "greedy"
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "softmax"
    norm_topk_prob: bool = False
    num_experts_per_tok: int = 6
    moe_layer_freq: int = 1
    first_k_dense_replace: int = 1
    attention_bias: bool = False
    max_position_embeddings: int = 163840
    rope_theta: float = 10000.0
    # "compressed": cache the shared KV latent (kv_lora_rank + rope dims per
    # token, independent of head count) and absorb kv_b into the query/output
    # sides at attention time — the MLA inference optimization. "full": cache
    # decompressed per-head K/V (the reference's layout, deepseek_v2.py:120-125).
    mla_cache_mode: str = "compressed"

    def __post_init__(self):
        super().__post_init__()
        # MLA: query/key dim differs from value dim.
        self.head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass
class MixtralConfig(BaseConfig):
    """Mixtral 8x7B-style MoE (BASELINE.json config #4; experts stage-local)."""

    model_type: str = "mixtral"
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    sliding_window: Optional[int] = None


@dataclass
class NemotronHConfig(BaseConfig):
    """Nemotron-H / Nemotron-3 hybrids: every block is one mixer behind one
    RMSNorm, the kind chosen per layer by ``hybrid_override_pattern`` — ``M``
    Mamba-2, ``*`` attention (GQA, no rotary), ``E`` latent MoE (sigmoid
    router with a selection bias, un-gated relu^2 experts in a
    ``moe_latent_size``-wide space, one shared expert at full width).

    A layer may hold one chip's SHARE of the routed experts:
    ``n_routed_experts`` counts the experts held, ``moe_expert_share`` says
    over how many holders a layer's experts are divided (the router has
    ``n_routed_experts * moe_expert_share`` outputs) and
    ``moe_expert_share_index`` which of them this is. A checkpoint's own
    config (no share keys) is the whole model."""

    model_type: str = "nemotron_h"
    hybrid_override_pattern: str = ""
    layer_norm_epsilon: float = 1e-5
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    # latent MoE
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    moe_expert_share: int = 1
    moe_expert_share_index: int = 0
    max_position_embeddings: int = 262144

    def __post_init__(self):
        if not self.hybrid_override_pattern:
            raise ValueError("nemotron_h needs hybrid_override_pattern")
        if len(self.hybrid_override_pattern) != self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern has "
                f"{len(self.hybrid_override_pattern)} characters for "
                f"{self.num_hidden_layers} layers"
            )
        bad = set(self.hybrid_override_pattern) - set("M*E")
        if bad:
            raise ValueError(
                f"hybrid_override_pattern: layer kinds {sorted(bad)} are not "
                "wired (M Mamba-2, * attention, E latent MoE are)"
            )
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("nemotron_h routing is wired for n_group = topk_group = 1")
        if not 0 <= self.moe_expert_share_index < self.moe_expert_share:
            raise ValueError("moe_expert_share_index must lie in [0, moe_expert_share)")
        super().__post_init__()
        self.rms_norm_eps = self.layer_norm_epsilon

    @property
    def router_width(self) -> int:
        return self.n_routed_experts * self.moe_expert_share


@dataclass
class AfmoeConfig(BaseConfig):
    """Arcee Trinity (``afmoe``): gated GQA attention with QK-norm, sliding-
    window layers (rotary) and full-attention layers (no rotary) mixed by
    ``layer_types``, sandwich norms, ``num_dense_layers`` SwiGLU layers and
    then MoE layers (sigmoid router with a selection bias, top-k normalised
    and scaled by ``route_scale``, one shared expert).

    A layer may hold one chip's SHARE of the routed experts, as
    :class:`NemotronHConfig` says: ``num_experts`` counts the experts held,
    ``moe_expert_share`` the holders, ``moe_expert_share_index`` which one
    this is. A checkpoint's own config (no share keys) is the whole model."""

    model_type: str = "afmoe"
    layer_types: Optional[list] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 4096
    num_dense_layers: int = 0
    moe_intermediate_size: int = 3072
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    route_norm: bool = True
    route_scale: float = 1.0
    score_func: str = "sigmoid"
    mup_enabled: bool = True
    moe_expert_share: int = 1
    moe_expert_share_index: int = 0
    max_position_embeddings: int = 262144

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = [
                "full_attention" if (i + 1) % n == 0 else "sliding_attention"
                for i in range(self.num_hidden_layers)
            ]
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"{self.num_hidden_layers} layers"
            )
        bad = set(self.layer_types) - {"sliding_attention", "full_attention"}
        if bad:
            raise ValueError(f"layer_types: {sorted(bad)} are not wired")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("afmoe routing is wired for n_group = topk_group = 1")
        if self.score_func != "sigmoid":
            raise ValueError("afmoe routing is wired for score_func sigmoid")
        if self.num_shared_experts != 1:
            raise ValueError("afmoe is wired for one shared expert")
        if self.rope_scaling is not None:
            raise ValueError("afmoe is wired for rope_scaling null")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers must lie in [0, num_hidden_layers]")
        if not 0 <= self.moe_expert_share_index < self.moe_expert_share:
            raise ValueError("moe_expert_share_index must lie in [0, moe_expert_share)")
        super().__post_init__()

    @property
    def router_width(self) -> int:
        return self.num_experts * self.moe_expert_share


@dataclass
class ZayaConfig(BaseConfig):
    """Zyphra ZAYA1 (``zaya``): every layer is an attention sub-layer and a
    MoE sub-layer. Attention runs in a compressed latent (Compressed
    Convolutional Attention): queries project to ``num_attention_heads x
    head_dim`` (half the hidden size), keys and values to
    ``num_key_value_heads x head_dim``; the packed q, k pass two causal
    convolutions over time (``cca_time0`` taps a channel, ``cca_time1`` taps
    mixing each head's channels), half of the value channels come from the
    previous token, rotary covers ``partial_rotary_factor`` of each head.
    The MoE picks ``num_experts_per_tok`` of ``num_experts`` by softmax over
    an MLP router's logits plus a selection bias; the router's
    ``router_hidden_size``-wide state runs down the layers.

    A layer may hold one chip's SHARE of the routed experts, as
    :class:`NemotronHConfig` says: ``num_experts`` counts the experts held,
    ``moe_expert_share`` the holders, ``moe_expert_share_index`` which one
    this is. A checkpoint's own config (no share keys) is the whole model."""

    model_type: str = "zaya"
    layer_types: Optional[list] = None
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_parameters: Optional[dict] = None
    rope_theta: float = 5000000.0
    moe_intermediate_size: int = 2048
    num_experts: int = 16
    num_experts_per_tok: int = 1
    router_hidden_size: int = 256
    sliding_window: Optional[int] = None
    moe_expert_share: int = 1
    moe_expert_share_index: int = 0
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 131072

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = ["hybrid"] * self.num_hidden_layers
        self.layer_types = list(self.layer_types)
        if self.layer_types != ["hybrid"] * self.num_hidden_layers:
            raise ValueError(
                "zaya is wired for layer_types of num_hidden_layers x 'hybrid'"
            )
        if self.cca_time0 != 2 or self.cca_time1 != 2:
            raise ValueError("zaya is wired for cca_time0 = cca_time1 = 2")
        if self.sliding_window is not None:
            raise ValueError("zaya is wired for sliding_window null")
        hybrid = (self.rope_parameters or {}).get("hybrid", {})
        self.rope_theta = float(hybrid.get("rope_theta", self.rope_theta))
        self.partial_rotary_factor = float(
            hybrid.get("partial_rotary_factor", self.partial_rotary_factor)
        )
        if not 0 <= self.moe_expert_share_index < self.moe_expert_share:
            raise ValueError("moe_expert_share_index must lie in [0, moe_expert_share)")
        super().__post_init__()
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")

    @property
    def router_width(self) -> int:
        return self.num_experts * self.moe_expert_share


@dataclass
class GraniteMoeHybridConfig(BaseConfig):
    """IBM Granite 4.0-H (``granitemoehybrid``): every block is a mixer —
    Mamba-2 or attention, by ``layer_types`` — and a SwiGLU MLP
    (``shared_intermediate_size`` wide), each behind its RMSNorm and each
    added to the residual times ``residual_multiplier``. Embeddings are
    scaled by ``embedding_multiplier``, attention scores by
    ``attention_multiplier`` (not ``head_dim**-0.5``), logits divided by
    ``logits_scaling``; the head is the embedding. Attention applies no
    rotary embedding (``position_embedding_type`` ``nope``). The family's
    routed experts (``num_local_experts > 0``: ``block_sparse_moe`` beside
    the shared MLP) are not wired."""

    model_type: str = "granitemoehybrid"
    layer_types: Optional[list] = None
    attention_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    shared_intermediate_size: int = 8192
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    position_embedding_type: str = "nope"
    attention_bias: bool = False
    hidden_act: str = "silu"
    normalization_function: str = "rmsnorm"
    # Mamba-2
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 131072

    def __post_init__(self):
        if not self.layer_types:
            raise ValueError("granitemoehybrid needs layer_types")
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"{self.num_hidden_layers} layers"
            )
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(
                f"layer_types: kinds {sorted(bad)} are not wired (mamba and "
                "attention are)"
            )
        if self.num_local_experts:
            raise ValueError(
                "granitemoehybrid is wired for num_local_experts 0: routed "
                "experts (block_sparse_moe) beside the shared MLP are not"
            )
        wired = {
            "position_embedding_type": "nope", "attention_bias": False,
            "hidden_act": "silu", "normalization_function": "rmsnorm",
            "mamba_conv_bias": True, "mamba_proj_bias": False,
        }
        for key, want in wired.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"granitemoehybrid is wired for {key} = {want!r}, not "
                    f"{getattr(self, key)!r}"
                )
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size:
            raise ValueError(
                "mamba_n_heads * mamba_d_head must be mamba_expand * hidden_size"
            )
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide mamba_n_heads")
        super().__post_init__()
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")


@dataclass
class KimiLinearConfig(BaseConfig):
    """Moonshot Kimi-Linear (``kimi_linear``): every block is a mixer and a
    feed-forward, each behind its RMSNorm. ``linear_attn_config`` lists, with
    1-BASED layer numbers, the layers whose mixer is Kimi Delta Attention
    (``kda_layers``: a gated delta rule, ``num_heads`` heads of ``head_dim``
    behind a ``short_conv_kernel_size``-tap convolution) and those whose
    mixer is multi-head latent attention (``full_attn_layers``: DeepSeek-V2's
    head sizes, no query LoRA, NO rotary embedding, ``mla_use_nope``). The
    first ``first_k_dense_replace`` layers' feed-forward is a SwiGLU MLP, the
    others' ``num_experts`` routed SwiGLU experts under a sigmoid router
    with a selection bias (top ``num_experts_per_token``, renormalised, times
    ``routed_scaling_factor``) plus one shared expert. ``head_dim`` and
    ``num_key_value_heads`` are inert: both mixers have their own head sizes.

    A layer may hold one chip's SHARE of the routed experts, as
    :class:`NemotronHConfig` says: ``num_experts`` counts the experts held,
    ``moe_expert_share`` the holders, ``moe_expert_share_index`` which one
    this is. A checkpoint's own config (no share keys) is the whole model."""

    model_type: str = "kimi_linear"
    linear_attn_config: Optional[dict] = None
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    num_expert_group: int = 1
    topk_group: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    hidden_act: str = "silu"
    moe_expert_share: int = 1
    moe_expert_share_index: int = 0
    max_position_embeddings: int = 1048576

    def __post_init__(self):
        lin = dict(self.linear_attn_config or {})
        n = self.num_hidden_layers
        kda, mla = set(lin.get("kda_layers", ())), set(lin.get("full_attn_layers", ()))
        if kda & mla or kda | mla != set(range(1, n + 1)):
            raise ValueError(
                "kimi_linear needs linear_attn_config's kda_layers and "
                f"full_attn_layers to name each of the layers 1..{n} once"
            )
        if mla & set(range(1, self.first_k_dense_replace + 1)):
            raise ValueError(
                "kimi_linear is wired for leading dense layers that are KDA layers"
            )
        wired = {
            "mla_use_nope": True, "q_lora_rank": None, "moe_layer_freq": 1,
            "num_expert_group": 1, "topk_group": 1, "num_shared_experts": 1,
            "moe_router_activation_func": "sigmoid", "hidden_act": "silu",
            "rope_scaling": None, "tie_word_embeddings": False,
        }
        for key, want in wired.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"kimi_linear is wired for {key} = {want!r}, not "
                    f"{getattr(self, key)!r}"
                )
        if not 0 <= self.first_k_dense_replace <= n:
            raise ValueError("first_k_dense_replace must lie in [0, num_hidden_layers]")
        if not 0 <= self.moe_expert_share_index < self.moe_expert_share:
            raise ValueError("moe_expert_share_index must lie in [0, moe_expert_share)")
        super().__post_init__()
        self.linear_attn_config = lin

    @property
    def layer_kinds(self) -> list:
        """Each layer's mixer, ``"kda"`` or ``"mla"``, 0-based."""
        mla = set(self.linear_attn_config["full_attn_layers"])
        return ["mla" if i + 1 in mla else "kda" for i in range(self.num_hidden_layers)]

    @property
    def router_width(self) -> int:
        return self.num_experts * self.moe_expert_share


@dataclass
class Qwen3NextConfig(BaseConfig):
    """Qwen3-Next (``qwen3_next``): every block is a mixer and a mixture of
    experts, each behind a zero-centred RMSNorm (``x_hat * (1 + w)``). Layer
    ``i`` is full attention when ``(i + 1) % full_attention_interval == 0``
    — GQA on ``head_dim``-wide heads behind an output gate that comes out of
    ``q_proj`` itself, per-head Q/K norms, rotary on the first
    ``partial_rotary_factor`` of each head — and a Gated DeltaNet otherwise:
    a gated delta rule with one decay a HEAD (``ops/kda.py``),
    ``linear_num_key_heads`` key heads serving ``linear_num_value_heads``
    value heads behind one ``linear_conv_kernel_dim``-tap convolution. The
    experts: softmax over all of them, the top ``num_experts_per_tok``
    renormalised (``norm_topk_prob``), plus one shared expert behind a
    sigmoid gate of its own.

    A layer may hold one chip's SHARE of the routed experts, as
    :class:`NemotronHConfig` says: ``num_experts`` counts the experts held,
    ``moe_expert_share`` the holders, ``moe_expert_share_index`` which one
    this is. A checkpoint's own config (no share keys) is the whole model."""

    model_type: str = "qwen3_next"
    head_dim: Optional[int] = 256
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    partial_rotary_factor: float = 0.25
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    decoder_sparse_step: int = 1
    mlp_only_layers: Optional[list] = None
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    use_sliding_window: bool = False
    hidden_act: str = "silu"
    moe_expert_share: int = 1
    moe_expert_share_index: int = 0
    max_position_embeddings: int = 262144

    def __post_init__(self):
        self.mlp_only_layers = list(self.mlp_only_layers or [])
        wired = {
            "decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True,
            "use_sliding_window": False, "hidden_act": "silu", "rope_scaling": None,
            "tie_word_embeddings": False,
        }
        for key, want in wired.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"qwen3_next is wired for {key} = {want!r}, not "
                    f"{getattr(self, key)!r}"
                )
        if self.linear_key_head_dim != self.linear_value_head_dim:
            raise ValueError(
                "qwen3_next is wired for linear_key_head_dim == linear_value_head_dim"
            )
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                "linear_num_key_heads must divide linear_num_value_heads"
            )
        if self.full_attention_interval < 1:
            raise ValueError("full_attention_interval must be at least 1")
        if not 0 <= self.moe_expert_share_index < self.moe_expert_share:
            raise ValueError("moe_expert_share_index must lie in [0, moe_expert_share)")
        super().__post_init__()

    @property
    def layer_kinds(self) -> list:
        """Each layer's mixer, ``"attn"`` or ``"gdn"``, 0-based."""
        n = self.full_attention_interval
        return ["attn" if (i + 1) % n == 0 else "gdn"
                for i in range(self.num_hidden_layers)]

    @property
    def router_width(self) -> int:
        return self.num_experts * self.moe_expert_share


@dataclass
class SdarMoeConfig(BaseConfig):
    """SDAR-MoE (``sdar_moe``): Qwen3-MoE's decoder — GQA with per-head Q/K
    RMSNorm before rotary, softmax-routed SwiGLU experts in every layer, the
    top ``num_experts_per_tok`` renormalised, no shared expert — generating
    by DIFFUSION OVER BLOCKS: the sequence is blocks of ``block_length``
    positions, a query sees every key up to the end of its own block, a
    forward computes a whole block whose unknown positions hold
    ``mask_token_id``, and ``denoising_steps`` forwards fill them in by
    ``remasking_strategy`` (``sequential`` | ``low_confidence_static`` |
    ``low_confidence_dynamic`` with ``confidence_threshold``); the block's
    K/V is then committed by the next block's first forward, which carries
    both (``mlx_sharding_tpu/diffusion.py``).

    A layer may hold one chip's SHARE of the routed experts, as
    :class:`NemotronHConfig` says: ``num_experts`` counts the experts held,
    ``moe_expert_share`` the holders, ``moe_expert_share_index`` which one
    this is. A checkpoint's own config (no share keys) is the whole model.
    ``intermediate_size``, ``sliding_window`` and ``max_window_layers`` are
    read and unused, as the published model leaves them."""

    model_type: str = "sdar_moe"
    head_dim: Optional[int] = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    attention_bias: bool = False
    decoder_sparse_step: int = 1
    mlp_only_layers: Optional[list] = None
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    use_sliding_window: bool = False
    hidden_act: str = "silu"
    moe_expert_share: int = 1
    moe_expert_share_index: int = 0
    # generation (the family's generate.py; the catalog gives none of them)
    block_length: int = 4
    denoising_steps: int = 4
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669

    STRATEGIES = ("sequential", "low_confidence_static", "low_confidence_dynamic")

    def __post_init__(self):
        self.mlp_only_layers = list(self.mlp_only_layers or [])
        wired = {
            "decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True,
            "use_sliding_window": False, "hidden_act": "silu", "rope_scaling": None,
            "tie_word_embeddings": False, "attention_bias": False,
        }
        for key, want in wired.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"sdar_moe is wired for {key} = {want!r}, not "
                    f"{getattr(self, key)!r}"
                )
        if self.remasking_strategy not in self.STRATEGIES:
            raise ValueError(
                f"remasking_strategy must be one of {self.STRATEGIES}, not "
                f"{self.remasking_strategy!r}"
            )
        if self.block_length < 1 or self.block_length & (self.block_length - 1):
            raise ValueError("block_length must be a power of two")
        if not 1 <= self.denoising_steps <= self.block_length \
                or self.block_length % self.denoising_steps:
            raise ValueError("denoising_steps must divide block_length")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} lies outside the "
                f"vocabulary of {self.vocab_size}"
            )
        if not 0 <= self.moe_expert_share_index < self.moe_expert_share:
            raise ValueError("moe_expert_share_index must lie in [0, moe_expert_share)")
        super().__post_init__()

    @property
    def router_width(self) -> int:
        return self.num_experts * self.moe_expert_share


# Arch-name resolution. Mirrors the reference's MODEL_REMAPPING
# (shard/utils.py:14-17): mistral runs through the llama implementation.
MODEL_REMAPPING = {
    "mistral": "llama",
    "qwen2": "llama",
}


@dataclass
class OlmoHybridConfig(BaseConfig):
    """Olmo Hybrid (``olmo_hybrid``): ``layer_types[i]`` picks each layer's
    mixer — ``linear_attention``, a Gated DeltaNet (``ops/kda.py``) whose keys
    are ``linear_key_head_dim`` wide and whose values ``linear_value_head_dim``
    (a rectangular state a head), with ``beta`` in ``(0, 2)`` under
    ``linear_allow_neg_eigval``; or ``full_attention``, multi-head attention
    behind an RMSNorm over the WHOLE query and key projections and with NO
    positional encoding (``rope_parameters.rope_theta`` null; a number is
    refused: no rotary is wired here). Every layer has a dense SwiGLU MLP,
    and Olmo 2's reordered norm: a sub-layer reads the residual stream
    un-normed and its OUTPUT is normed before it is added."""

    model_type: str = "olmo_hybrid"
    rms_norm_eps: float = 1e-6
    layer_types: Optional[list] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: Optional[dict] = None
    attention_bias: bool = False
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536

    KINDS = {"linear_attention": "gdn", "full_attention": "attn"}

    def __post_init__(self):
        wired = {
            "attention_bias": False, "hidden_act": "silu", "rope_scaling": None,
            "tie_word_embeddings": False,
        }
        for key, want in wired.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"olmo_hybrid is wired for {key} = {want!r}, not "
                    f"{getattr(self, key)!r}"
                )
        if (self.rope_parameters or {}).get("rope_theta") is not None:
            raise ValueError(
                "olmo_hybrid is wired for rope_parameters.rope_theta = None "
                "(no positional encoding on the full-attention layers)"
            )
        if self.layer_types is None:  # the published period: three linear, one full
            self.layer_types = [
                "full_attention" if (i + 1) % 4 == 0 else "linear_attention"
                for i in range(self.num_hidden_layers)
            ]
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types must name num_hidden_layers layers")
        unknown = sorted(set(self.layer_types) - set(self.KINDS))
        if unknown:
            raise ValueError(f"unknown layer_types {unknown}")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                "linear_num_key_heads must divide linear_num_value_heads"
            )
        super().__post_init__()
        if self.head_dim * self.num_attention_heads != self.hidden_size:
            raise ValueError(
                "olmo_hybrid norms q and k over hidden_size channels: "
                "num_attention_heads * head_dim must equal it"
            )

    @property
    def layer_kinds(self) -> list:
        """Each layer's mixer, ``"attn"`` or ``"gdn"``, 0-based."""
        return [self.KINDS[t] for t in self.layer_types]


CONFIG_REGISTRY: dict[str, type] = {
    "llama": LlamaConfig,
    "qwen3": Qwen3Config,
    "gemma2": Gemma2Config,
    "deepseek_v2": DeepseekV2Config,
    "mixtral": MixtralConfig,
    "nemotron_h": NemotronHConfig,
    "afmoe": AfmoeConfig,
    "zaya": ZayaConfig,
    "granitemoehybrid": GraniteMoeHybridConfig,
    "kimi_linear": KimiLinearConfig,
    "qwen3_next": Qwen3NextConfig,
    "sdar_moe": SdarMoeConfig,
    "olmo_hybrid": OlmoHybridConfig,
}


def resolve_model_type(model_type: str) -> str:
    return MODEL_REMAPPING.get(model_type, model_type)


def config_from_dict(d: dict[str, Any]):
    original_type = d.get("model_type", "llama")
    model_type = resolve_model_type(original_type)
    if model_type not in CONFIG_REGISTRY:
        raise ValueError(
            f"Model type {model_type!r} not supported. "
            f"Supported: {sorted(CONFIG_REGISTRY)}"
        )
    cls = CONFIG_REGISTRY[model_type]
    d = dict(d)
    d["model_type"] = model_type
    if original_type == "qwen2":
        # Qwen2 uses QKV biases unconditionally and its HF config carries no
        # attention_bias field.
        d.setdefault("attention_bias", True)
    return cls.from_dict(d)
