"""Offline per-stage checkpoint writer.

Capability parity with the reference's ``sharding_weight.py``: stream the
source checkpoint, keep only one stage's tensors (layers in
``[start, end)``; embedding on the first stage — and on the last too for
tied-embedding models like Gemma-2; final norm + head on the last stage —
ref: sharding_weight.py:16-24, shard/server/model/gemma2.py:23-24), write
``model-{start:05d}-{end:05d}.safetensors`` plus a filtered ``weight_map``
index (ref: sharding_weight.py:26-46), bake ``start_layer``/``end_layer``
into the shard's config.json so the shard self-describes
(ref: sharding_weight.py:48-60), and copy tokenizer/aux files
(ref: sharding_weight.py:63-71).

Improvement over the reference: ``--num-stages N`` emits every stage in one
pass instead of one invocation per shard, and quantized triples
(weight/scales/biases) are kept together automatically since filtering is
key-prefix based.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from mlx_sharding_tpu.config import config_from_dict
from mlx_sharding_tpu.loading import (
    filter_stage_weights,
    get_model_path,
    load_raw_weights,
)

_AUX_SKIP_SUFFIXES = (".safetensors", ".safetensors.index.json")


def save_sharded_weights(
    model_path: str | Path,
    output_dir: str | Path,
    start_layer: int,
    end_layer: int,
    total_layers: int | None = None,
    emit_native: bool = False,
) -> Path:
    """Write one stage's checkpoint into ``output_dir``. Returns the dir.
    With ``emit_native`` the stage is additionally materialized through the
    model's weight mapper and saved as a native (Orbax) checkpoint under
    ``output_dir/native/`` — stacked, transposed, restore-ready."""
    model_path = get_model_path(str(model_path))
    output_dir = Path(output_dir)
    output_dir.mkdir(parents=True, exist_ok=True)

    with open(model_path / "config.json") as f:
        config_dict = json.load(f)
    if total_layers is not None:
        config_dict["num_hidden_layers"] = total_layers
    config_dict["start_layer"] = start_layer
    config_dict["end_layer"] = end_layer
    config = config_from_dict(dict(config_dict))

    weights = load_raw_weights(model_path)
    kept = filter_stage_weights(weights, config)

    from safetensors.numpy import save_file

    shard_name = f"model-{start_layer:05d}-{end_layer:05d}.safetensors"
    save_file(kept, output_dir / shard_name, metadata={"format": "flax"})

    index = {
        "metadata": {"total_parameters": len(kept)},
        "weight_map": {k: shard_name for k in sorted(kept)},
    }
    with open(output_dir / "model.safetensors.index.json", "w") as f:
        json.dump(index, f, indent=2)

    with open(output_dir / "config.json", "w") as f:
        json.dump(config_dict, f, indent=2)

    copy_other_files(model_path, output_dir)

    if emit_native:
        import jax
        import jax.numpy as jnp

        from mlx_sharding_tpu.checkpoint import save_native_checkpoint
        from mlx_sharding_tpu.models import get_model_class
        from mlx_sharding_tpu.loading import dequantize_weights

        weights_for_map = kept
        if config.quantization is not None:
            weights_for_map = dequantize_weights(kept, config.quantization)
        model = get_model_class(config.model_type)(config)
        params = jax.device_put(
            model.map_weights(weights_for_map, jnp.bfloat16)
        )
        native_dir = output_dir / "native"
        save_native_checkpoint(native_dir, params, config)
        copy_other_files(model_path, native_dir)
    return output_dir


def copy_other_files(model_path: Path, output_dir: Path) -> None:
    """Tokenizer + aux files travel with every shard (ref:
    sharding_weight.py:63-71); weights and config are freshly written."""
    for item in model_path.iterdir():
        if item.name == "config.json" or item.name.endswith(_AUX_SKIP_SUFFIXES):
            continue
        if item.is_file():
            shutil.copy2(item, output_dir / item.name)


def even_partition(num_layers: int, num_stages: int) -> list[tuple[int, int]]:
    """[start, end) bounds per stage; remainder layers go to the earliest
    stages so later (post-norm-heavy) stages stay lighter."""
    base, rem = divmod(num_layers, num_stages)
    bounds = []
    start = 0
    for s in range(num_stages):
        size = base + (1 if s < rem else 0)
        bounds.append((start, start + size))
        start += size
    return bounds


def shard_all_stages(
    model_path: str | Path,
    output_root: str | Path,
    num_stages: int,
    emit_native: bool = False,
) -> list[Path]:
    model_path = get_model_path(str(model_path))
    with open(model_path / "config.json") as f:
        num_layers = json.load(f)["num_hidden_layers"]
    dirs = []
    for i, (start, end) in enumerate(even_partition(num_layers, num_stages)):
        out = Path(output_root) / f"stage_{i:02d}"
        dirs.append(
            save_sharded_weights(model_path, out, start, end, emit_native=emit_native)
        )
    return dirs


def main(argv=None):
    import argparse

    from mlx_sharding_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(
        description="Partition a checkpoint into pipeline-stage checkpoints "
        "(TPU-native equivalent of the reference's sharding_weight.py)"
    )
    parser.add_argument("--model", required=True, help="source model path or HF repo")
    parser.add_argument("--output-dir", required=True)
    parser.add_argument("--start-layer", type=int)
    parser.add_argument("--end-layer", type=int)
    parser.add_argument("--total-layers", type=int, default=None)
    parser.add_argument(
        "--num-stages", type=int, default=None,
        help="emit all stages at once under output-dir/stage_NN/",
    )
    parser.add_argument(
        "--emit-native", action="store_true",
        help="also write each stage as a native (Orbax) checkpoint under "
        "<stage>/native/ — stacked and transposed, restore-ready",
    )
    args = parser.parse_args(argv)

    if args.num_stages:
        dirs = shard_all_stages(
            args.model, args.output_dir, args.num_stages, args.emit_native
        )
        for d in dirs:
            print(d)
    else:
        if args.start_layer is None or args.end_layer is None:
            parser.error("--start-layer/--end-layer required without --num-stages")
        print(
            save_sharded_weights(
                args.model, args.output_dir, args.start_layer, args.end_layer,
                args.total_layers, emit_native=args.emit_native,
            )
        )


if __name__ == "__main__":
    main()
