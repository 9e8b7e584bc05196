"""Offline KVSharer calibration (arXiv:2410.18517) — emit a share-map
artifact for ``--kv-share-map``.

One dense prefill per calibration prompt, per-layer KV signatures off the
resulting cache, every layer pair ranked by dissimilarity (1 − cosine;
KVSharer's counterintuitive finding is that the MOST dissimilar pairs are
the safe ones to share), then a greedy merge of the top ``--num-share``
pairs under the ``--max-group`` cap. The resulting
``mst-kv-share-map-v1`` JSON (kv_share.py) is what the server and the
CLI load with ``--kv-share-map PATH``; its ``share_hash`` joins the
``KVPageBlock`` export/import fingerprint so a pool can never scatter a
block laid out under a different map.

Calibration is OFFLINE by design: it runs dense prefills and marshals
whole KV buffers to host numpy — exactly the traffic mstcheck MST115
keeps out of the serving tick.

Usage::

    python -m mlx_sharding_tpu.cli.kv_share_calibrate \
        --model path/or/hf-repo --num-share 8 \
        --prompts-file calib.txt --output share_map.json
"""

from __future__ import annotations

import argparse
import sys


def calibrate_model(model, params, prompts_ids, *, num_share: int,
                    max_group: int = 2, cache_dtype=None, meta=None):
    """Core calibration over already-tokenized prompts: one dense prefill
    each, signatures concatenated along the sequence axis, one greedy
    share map out. Importable so tests (and notebooks) can calibrate a
    tiny model without the CLI's checkpoint loading."""
    import jax.numpy as jnp
    import numpy as np

    from mlx_sharding_tpu.kv_share import ShareMapError, calibrate_share_map

    if cache_dtype is None:
        cache_dtype = jnp.float32
    ks, vs = [], []
    total_tokens = 0
    for ids in prompts_ids:
        ids = np.asarray(ids, np.int32)
        if ids.ndim != 1 or ids.size < 2:
            raise ShareMapError(
                "calibration prompts need >= 2 tokens each"
            )
        n = int(ids.size)
        cache = model.make_cache(1, n, cache_dtype)
        _, cache = model(params, jnp.asarray(ids)[None, :], cache,
                         n_valid=jnp.asarray(n, jnp.int32))
        ks.append(np.asarray(cache.k, np.float32)[:, :, :n])
        vs.append(np.asarray(cache.v, np.float32)[:, :, :n])
        total_tokens += n
    k = np.concatenate(ks, axis=2)
    v = np.concatenate(vs, axis=2)
    info = dict(meta or {})
    info.update({
        "calibration_prompts": len(ks),
        "calibration_tokens": total_tokens,
    })
    return calibrate_share_map(
        k, v, num_share=num_share, max_group=max_group, meta=info
    )


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Calibrate a layer-wise KV share map (KVSharer)"
    )
    parser.add_argument("--model", required=True,
                        help="model path or HF repo (same as generate)")
    parser.add_argument("--num-share", type=int, required=True,
                        help="how many layer pairs to merge; each merged "
                        "pair removes one layer's KV pool bytes")
    parser.add_argument("--max-group", type=int, default=2,
                        help="cap on layers per shared group (the paper "
                        "shares pairs; >2 compounds quality loss)")
    parser.add_argument("--prompts-file", default=None,
                        help="calibration prompts, one per line (default: "
                        "a small built-in English mix)")
    parser.add_argument("--max-prompt-tokens", type=int, default=512)
    parser.add_argument("--output", required=True,
                        help="where to write the share-map JSON artifact")
    args = parser.parse_args(argv)

    from transformers import AutoTokenizer

    from mlx_sharding_tpu.loading import get_model_path, load_model

    if args.prompts_file:
        with open(args.prompts_file) as f:
            prompts = [ln.strip() for ln in f if ln.strip()]
    else:
        prompts = [
            "The quick brown fox jumps over the lazy dog.",
            "In a distant galaxy, explorers charted unknown worlds.",
            "Summarize the quarterly report in three bullet points.",
        ]
    if not prompts:
        print("no calibration prompts", file=sys.stderr)
        return 2

    model_path = get_model_path(args.model)
    model, params = load_model(model_path)
    tokenizer = AutoTokenizer.from_pretrained(str(model_path))
    ids = [
        tokenizer.encode(p)[: args.max_prompt_tokens] for p in prompts
    ]
    m = calibrate_model(
        model, params, [i for i in ids if len(i) >= 2],
        num_share=args.num_share, max_group=args.max_group,
        meta={"model": str(args.model)},
    )
    m.save(args.output)
    print(
        f"wrote {args.output}: {m.num_layers} layers -> {m.num_groups} "
        f"groups ({m.bytes_saved_fraction():.1%} KV pool bytes saved), "
        f"share_hash={m.share_hash}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
