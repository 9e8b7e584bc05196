"""Model registry.

Replaces the reference's importlib-based arch resolution
(shard/utils.py:20-30) with an explicit registry keyed by the remapped
``model_type`` (remapping itself lives in config.MODEL_REMAPPING, mirroring
shard/utils.py:14-17).
"""

from __future__ import annotations

import importlib

from mlx_sharding_tpu.config import config_from_dict, resolve_model_type

# model_type -> (module, class). Keys must match config.CONFIG_REGISTRY.
MODEL_REGISTRY: dict[str, tuple[str, str]] = {
    "llama": ("mlx_sharding_tpu.models.llama", "LlamaModel"),
    "qwen3": ("mlx_sharding_tpu.models.qwen3", "Qwen3Model"),
    "gemma2": ("mlx_sharding_tpu.models.gemma2", "Gemma2Model"),
    "deepseek_v2": ("mlx_sharding_tpu.models.deepseek_v2", "DeepseekV2Model"),
    "mixtral": ("mlx_sharding_tpu.models.mixtral", "MixtralModel"),
    "nemotron_h": ("mlx_sharding_tpu.models.nemotron_h", "NemotronHModel"),
    "afmoe": ("mlx_sharding_tpu.models.afmoe", "AfmoeModel"),
    "zaya": ("mlx_sharding_tpu.models.zaya", "ZayaModel"),
    "granitemoehybrid": (
        "mlx_sharding_tpu.models.granitemoehybrid", "GraniteMoeHybridModel",
    ),
    "kimi_linear": ("mlx_sharding_tpu.models.kimi_linear", "KimiLinearModel"),
    "qwen3_next": ("mlx_sharding_tpu.models.qwen3_next", "Qwen3NextModel"),
    "sdar_moe": ("mlx_sharding_tpu.models.sdar_moe", "SdarMoeModel"),
    "olmo_hybrid": ("mlx_sharding_tpu.models.olmo_hybrid", "OlmoHybridModel"),
}


def get_model_class(model_type: str):
    model_type = resolve_model_type(model_type)
    if model_type not in MODEL_REGISTRY:
        raise ValueError(
            f"Model type {model_type!r} not supported. Supported: {sorted(MODEL_REGISTRY)}"
        )
    module_name, class_name = MODEL_REGISTRY[model_type]
    try:
        module = importlib.import_module(module_name)
    except ModuleNotFoundError as exc:
        raise ValueError(
            f"Model type {model_type!r} is registered but its implementation "
            f"({module_name}) is not available."
        ) from exc
    return getattr(module, class_name)


def build_model(config_dict: dict):
    """config.json dict → (model, config)."""
    cfg = config_from_dict(config_dict)
    return get_model_class(cfg.model_type)(cfg), cfg
