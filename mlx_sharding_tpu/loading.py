"""Checkpoint loading.

TPU-native counterpart of the reference's loader (ref: shard/utils.py:33-68):
resolve a local path or HF repo, read ``config.json``, inject the pipeline
bounds ``start_layer``/``end_layer`` (ref: shard/utils.py:36-39), read every
``*.safetensors``, drop out-of-stage weights (the reference's per-model
``sanitize``, ref: shard/server/model/llama.py:92-107), dequantize MLX
grouped-quant triples when ``config.quantization`` is present
(ref: shard/utils.py:54-65), and hand the result to the model's weight mapper
which transposes/stacks into the scan-ready pytree.
"""

from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.models import build_model
from mlx_sharding_tpu.ops.quant import dequantize

LAYER_RE = re.compile(r"(?:model\.)?layers\.(\d+)\.")


def get_model_path(path_or_repo: str, revision: Optional[str] = None) -> Path:
    """Local directory, else HF hub snapshot (ref: mlx_lm.get_model_path used
    at shard/utils.py:34)."""
    p = Path(path_or_repo)
    if p.exists():
        return p
    from huggingface_hub import snapshot_download

    return Path(
        snapshot_download(
            repo_id=path_or_repo,
            revision=revision,
            # params/** covers native (Orbax) checkpoints uploaded to a repo —
            # the marker alone matching *.json must not strand the payload.
            allow_patterns=[
                "*.json", "*.safetensors", "*.model", "tokenizer*", "params/**",
            ],
        )
    )


def checkpoint_signature(
    path_or_repo: str, *, keep_quantized: bool = False
) -> str:
    """Stable content identity of a checkpoint for ``weights.WeightKey``:
    the resolved on-disk path plus the quantization config and whether the
    load keeps packed triples resident. Two replicas may alias one resident
    tree only when this string matches — same files, same dequant decisions,
    same in-memory layout."""
    path = get_model_path(path_or_repo)
    quant = None
    cfg = path / "config.json"
    if cfg.exists():
        with open(cfg) as f:
            quant = json.load(f).get("quantization")
    if quant:
        qsig = (
            f"gs{int(quant.get('group_size', 64))}"
            f"b{int(quant.get('bits', 4))}"
        )
        packed = "packed" if keep_quantized else "dense"
    else:
        qsig, packed = "dense", "dense"
    return f"{path.resolve()}::{qsig}::{packed}"


def load_config(
    model_path: Path,
    start_layer: Optional[int] = None,
    end_layer: Optional[int] = None,
) -> dict:
    with open(model_path / "config.json") as f:
        config = json.load(f)
    # Dynamic sharding: bounds from the CLI override whatever the checkpoint
    # baked in (ref: shard/utils.py:36-39).
    if start_layer is not None:
        config["start_layer"] = start_layer
    if end_layer is not None:
        config["end_layer"] = end_layer
    return config


def load_raw_weights(model_path: Path) -> dict[str, np.ndarray]:
    """Read every *.safetensors in the directory (ref: shard/utils.py:40-45)
    into HOST memory (bf16 arrives as ml_dtypes.bfloat16). The weight mapper
    transposes and stacks on the host and the finished tree is placed on the
    device once: read straight onto the device, the raw tensors, their
    transposed copies and the stacks are all resident at once — three times
    a 3B bf16 model, which a 16 GB chip does not hold."""
    from safetensors import safe_open

    files = sorted(model_path.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"No safetensors found in {model_path}")
    weights: dict[str, np.ndarray] = {}
    for file in files:
        with safe_open(file, framework="numpy") as f:
            for k in f.keys():
                weights[k] = f.get_tensor(k)
    return weights


def _kernel_readable(x):
    """fp16 quantization scales/biases → f32 (exact); anything else as is."""
    return x.astype(jnp.float32) if x.dtype == jnp.float16 else x


def dequantize_weights(
    weights: dict[str, jnp.ndarray],
    quantization: dict,
    dtype=jnp.bfloat16,
    keep_packed_layers: bool = False,
    keep_dense_re: str | None = None,
) -> dict[str, jnp.ndarray]:
    """Process every MLX ``{weight, scales, biases}`` triple. Default:
    collapse to a dense weight — mirrors the predicate the reference feeds
    nn.quantize, a param is quantized iff its ``.scales`` sibling exists
    (shard/utils.py:58-63). With ``keep_packed_layers``, decoder-layer
    projections AND the vocab pair (embed_tokens / lm_head — published
    4-bit checkpoints quantize them too, and the head matmul is the largest
    dense per-token read) stay packed as ``{q, scales, biases}`` dicts for
    the fused dequant-matmul path; norms are still dequantized.
    ``keep_dense_re`` (model.packed_keep_dense_re) names layer weights that
    are consumed as tensors, not matmul operands — those dequantize even in
    packed mode (MoE routers, MLA kv_b under the compressed cache)."""
    group_size = int(quantization.get("group_size", 64))
    bits = int(quantization.get("bits", 4))
    dense_re = re.compile(keep_dense_re) if keep_dense_re else None
    out: dict = {}
    for name, value in weights.items():
        base, _, leaf = name.rpartition(".")
        if leaf in ("scales", "biases"):
            continue  # consumed alongside their .weight
        if leaf == "weight" and f"{base}.scales" in weights:
            if (
                keep_packed_layers
                and (
                    LAYER_RE.search(name)
                    or "embed_tokens" in name
                    or "lm_head" in name
                )
                and not (dense_re and dense_re.search(name))
            ):
                # scales/biases stay in the checkpoint dtype where the
                # kernels can read it (bf16, f32) — both matmul paths cast
                # to f32 on the fly, and f32 residency would add ~11% to
                # the weight bytes streamed per decode step for nothing.
                # fp16 is the exception: Mosaic has no f16 vector loads on
                # a v5e (both Pallas kernels are refused at compile), and
                # bf16 would round the scales, so fp16 pairs widen to f32.
                out[name] = {
                    "q": value,
                    "scales": _kernel_readable(weights[f"{base}.scales"]),
                    "biases": _kernel_readable(weights[f"{base}.biases"]),
                }
                continue
            value = dequantize(
                value,
                weights[f"{base}.scales"],
                weights[f"{base}.biases"],
                group_size,
                bits,
                dtype,
            )
        out[name] = value
    return out


def filter_stage_weights(
    weights: dict[str, jnp.ndarray], config
) -> dict[str, jnp.ndarray]:
    """Sanitize-by-range (ref: shard/server/model/llama.py:92-107 and
    sharding_weight.py:16-24): keep layers in [start, end); embedding only
    where the stage needs it; final norm + head only on the last stage.
    Rotary inv_freq buffers are always dropped."""
    kept: dict[str, jnp.ndarray] = {}
    for name, value in weights.items():
        if "rotary_emb.inv_freq" in name:
            continue
        m = LAYER_RE.search(name)
        if m:
            if config.start_layer <= int(m.group(1)) < config.end_layer:
                kept[name] = value
            continue
        if "embed_tokens" in name:
            if config.needs_embed:
                kept[name] = value
            continue
        if name.startswith(("model.norm", "norm.")) or "lm_head" in name:
            if config.needs_head:
                kept[name] = value
            continue
        kept[name] = value
    return kept


def load_model(
    path_or_repo: str,
    start_layer: Optional[int] = None,
    end_layer: Optional[int] = None,
    dtype=jnp.bfloat16,
    keep_quantized: bool = False,
):
    """Full load path (ref: shard/utils.py:33-68). Returns (model, params).
    Native (Orbax) checkpoints are detected and restored directly.
    ``keep_quantized`` keeps 4-bit decoder-layer weights packed in HBM
    (fused dequant-matmul) on architectures that support it."""
    model_path = get_model_path(path_or_repo)
    from mlx_sharding_tpu.checkpoint import is_native_checkpoint, load_native_checkpoint

    if is_native_checkpoint(model_path):
        if keep_quantized:
            raise ValueError(
                "keep_quantized is not supported for native (Orbax) "
                "checkpoints — they store dense weights"
            )
        return load_native_checkpoint(model_path, start_layer, end_layer, dtype=dtype)
    config_dict = load_config(model_path, start_layer, end_layer)
    model, config = build_model(config_dict)
    if keep_quantized and not getattr(model, "supports_packed", False):
        raise ValueError(
            f"keep_quantized is not supported for {type(model).__name__}"
        )
    if keep_quantized and config.quantization is None:
        # a silent dense load would quietly cost 4x the expected HBM
        raise ValueError(
            "keep_quantized requires a quantized checkpoint "
            "(no 'quantization' key in config.json)"
        )
    weights = load_raw_weights(model_path)
    if config.quantization is not None:
        weights = dequantize_weights(
            weights, config.quantization, dtype,
            keep_packed_layers=keep_quantized,
            keep_dense_re=model.packed_keep_dense_re(),
        )
    weights = filter_stage_weights(weights, config)
    # host-built leaves (see load_raw_weights) land on the device here
    params = jax.device_put(model.map_weights(weights, dtype))
    # paths that must materialize dense values from packed params (embed
    # row dequant) produce this dtype, so packed and dense loads agree
    model.compute_dtype = dtype
    return model, params


# ---------------------------------------------------------------------------
# Helpers for the per-model weight mappers


def fetch_weight(weights: dict, key: str, dtype, transpose: bool = True):
    """One checkpoint tensor, packed-or-dense: a packed ``{q, scales,
    biases}`` triple passes through untouched (it keeps MLX's (out, in)
    orientation — the fused dequant-matmul contracts against it); a dense
    array is cast and, for projections, transposed to (in, out) for
    ``x @ W``. The single fetch convention for every model's weight mapper."""
    w = weights[key]
    if isinstance(w, dict):
        return w
    # host arrays stay on the host (the transpose is a view): stack_tree
    # makes the one contiguous copy and load_model places it
    w = w.astype(dtype, copy=False)
    return w.T if transpose else w


def stack_tree(items: list):
    """Stack a list of same-structure packed-or-dense entries on a new
    leading axis: a plain array is a single-leaf tree, a packed triple
    stacks per leaf into {q: (N, …), scales: (N, …), biases: (N, …)}.
    Host arrays stack on the host."""

    def stack(*xs):
        if all(isinstance(x, np.ndarray) for x in xs):
            return np.stack(xs)
        return jnp.stack(xs)

    return jax.tree.map(stack, *items)


def collect_layer_stack(
    weights: dict[str, jnp.ndarray],
    config,
    per_layer_names: dict[str, tuple[str, bool]],
    dtype,
) -> dict[str, jnp.ndarray]:
    """{hf_suffix → (our_name, transpose)} applied across the stage's layer
    range and stacked on a leading axis (global HF indices
    start_layer..end_layer map to stack rows 0..L)."""
    stacked: dict[str, list] = {our: [] for our, _ in per_layer_names.values()}
    for i in range(config.start_layer, config.end_layer):
        for hf_suffix, (our_name, transpose) in per_layer_names.items():
            key = f"model.layers.{i}.{hf_suffix}"
            if key not in weights:
                key = f"layers.{i}.{hf_suffix}"
            stacked[our_name].append(fetch_weight(weights, key, dtype, transpose))
    return {k: stack_tree(v) for k, v in stacked.items()}


def first_key(weights: dict, *candidates: str):
    for c in candidates:
        if c in weights:
            return weights[c]
    raise KeyError(f"none of {candidates} present in checkpoint")


def vocab_param(value, dtype, transpose: bool = False):
    """Embed table / LM head param: packed triples (keep-quantized loads)
    stay in MLX (V, …) orientation — base.embed_tokens/apply_head consume
    them directly; dense arrays cast (and for untied heads transpose to the
    (H, V) matmul orientation)."""
    if isinstance(value, dict):
        return value
    value = jnp.asarray(value, dtype)
    return value.T if transpose else value
