"""CLI text generation — the reference's ``generate.py`` driver re-imagined.

Same operator surface (model path, prompt, sampling knobs, chat-template
application, streamed output, prompt/generation tok/s report —
ref: generate.py:12-20, 25-29, 90-122) but the execution underneath is the
TPU stack: single-chip jitted decode or the SPMD pipeline via
``--num-stages`` (which replaces the reference's ``--server-address`` list of
gRPC shard endpoints, ref generate.py:17 — stages are mesh slices here, not
remote processes). TTFT is reported explicitly, which the reference only
measures implicitly (SURVEY §6).
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None):
    from mlx_sharding_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description="Generate text with mlx_sharding_tpu")
    parser.add_argument("--model", required=True, help="model path or HF repo")
    parser.add_argument("--prompt", default="hello")
    parser.add_argument("--max-tokens", type=int, default=100)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--top-p", type=float, default=1.0)
    parser.add_argument("--repetition-penalty", type=float, default=None)
    parser.add_argument("--seed", type=int, default=None)
    parser.add_argument("--max-seq", type=int, default=4096)
    parser.add_argument("--prefill-chunk", type=int, default=256)
    parser.add_argument("--start-layer", type=int, default=None)
    parser.add_argument("--end-layer", type=int, default=None)
    parser.add_argument("--num-stages", type=int, default=None,
                        help="run the model as an N-stage fused SPMD pipeline on the local mesh")
    parser.add_argument("--stage-bounds", default=None,
                        help="pipeline stage bounds, e.g. '0-14,14-27' "
                        "(uneven splits and MoE/dense mixes allowed)")
    parser.add_argument("--engine", choices=("fused", "chained"), default="fused",
                        help="pipeline engine for --stage-bounds: 'fused' runs all "
                        "stages as one SPMD program per token (default); 'chained' "
                        "uses per-stage programs with D2D hand-off")
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel width within each pipeline "
                        "stage (Llama family)")
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel width within each pipeline "
                        "stage (MoE models)")
    parser.add_argument("--sp", type=int, default=None,
                        help="sequence-parallel prefill over N devices (ring "
                        "attention); prompts longer than one prefill chunk "
                        "shard their sequence dim")
    parser.add_argument("--sp-decode", action="store_true",
                        help="with --sp: keep the KV cache sequence-sharded "
                        "for the whole generation (distributed decode "
                        "attention) — capacity scales with the mesh instead "
                        "of one chip's HBM")
    parser.add_argument("--draft-model", default=None,
                        help="speculative decoding: a small draft model "
                        "proposes --spec-k tokens per round, the target "
                        "verifies them in one forward — greedy streams are "
                        "token-exact; sampled requests use rejection "
                        "sampling (distribution-exact)")
    parser.add_argument("--spec-k", type=int, default=4,
                        help="speculation window (with --draft-model)")
    parser.add_argument("--draft",
                        choices=("auto", "off", "ngram", "engine"),
                        default="auto",
                        help="draft source for speculative decoding: "
                        "'engine' is the two-model path (--draft-model), "
                        "'ngram' drafts by prompt-lookup — n-gram matches "
                        "against the prompt+history propose the window, no "
                        "second checkpoint and no draft KV; 'auto' picks "
                        "'engine' when --draft-model is given, else 'off'")
    parser.add_argument("--spec-window-max", type=int, default=None,
                        help="adaptive speculation ceiling (>= 2): per-round "
                        "acceptance (EWMA) resizes the window in {0,2,4,8} "
                        "up to this cap and disables drafting when it never "
                        "pays; with --draft ngram defaults to 8")
    parser.add_argument("--paged-attention",
                        choices=("auto", "ragged", "gather"), default="auto",
                        help="decode-attention path for paged pipeline "
                        "engines: 'ragged' attends over the KV page pool in "
                        "place (needs a pool — the engine validates), "
                        "'gather' keeps the contiguous per-slot view, 'auto' "
                        "picks ragged where supported; forwarded to the "
                        "engine, a no-op on dense single-stream runs")
    parser.add_argument("--async-sched",
                        choices=("on", "off", "auto"), default="auto",
                        help="tick pipelining for the continuous batcher: "
                        "dispatch decode block t+1 before harvesting block "
                        "t's tokens so host scheduling overlaps device "
                        "compute ('auto' enables it for plain decode, "
                        "disables it when a draft engine is attached); "
                        "accepted here for flag parity with the server — "
                        "the single-stream CLI path always harvests "
                        "synchronously, so this is a no-op")
    parser.add_argument("--kv-dtype", choices=("bf16", "int8"), default=None,
                        help="KV-pool storage dtype; accepted for flag "
                        "parity with the server. 'int8' needs a paged pool "
                        "(server --concurrent/--paged-pool) — the "
                        "single-stream CLI allocates dense caches, so only "
                        "'bf16' is valid here")
    parser.add_argument("--keep-quantized", action="store_true",
                        help="keep 4-bit decoder weights packed in HBM "
                        "(fused dequant-matmul) instead of dequantizing at "
                        "load")
    parser.add_argument("--no-chat-template", action="store_true")
    args = parser.parse_args(argv)
    if args.engine == "chained" and not args.stage_bounds:
        parser.error("--engine chained requires --stage-bounds")
    if (args.tp > 1 or args.ep > 1) and args.engine == "chained":
        parser.error("--tp/--ep require the fused engine")
    if args.sp and (args.stage_bounds or args.num_stages or args.tp > 1 or args.ep > 1):
        parser.error("--sp applies to the single-stage generator only")
    if args.sp_decode and not (args.sp and args.sp > 1):
        parser.error("--sp-decode requires --sp N (N > 1)")
    if args.draft_model and (args.sp or args.stage_bounds or args.num_stages
                             or args.tp > 1 or args.ep > 1):
        parser.error("--draft-model applies to the single-chip generator")
    if args.draft == "engine" and not args.draft_model:
        parser.error("--draft engine requires --draft-model")
    if args.draft in ("off", "ngram") and args.draft_model:
        parser.error(f"--draft {args.draft} conflicts with --draft-model "
                     "(drop one: 'engine' is the two-model path)")
    if args.draft == "ngram" and (args.sp or args.stage_bounds
                                  or args.num_stages or args.tp > 1
                                  or args.ep > 1):
        parser.error("--draft ngram applies to the single-chip generator")
    if args.spec_window_max is not None:
        if args.spec_window_max < 2:
            parser.error("--spec-window-max must be >= 2 (a 1-token window "
                         "is plain decode; use --draft off)")
        if args.draft not in ("ngram", "engine") and not args.draft_model:
            parser.error("--spec-window-max needs a draft source "
                         "(--draft ngram or --draft-model)")
    if args.kv_dtype == "int8":
        parser.error("--kv-dtype int8 requires a paged KV pool; serve with "
                     "--concurrent N --paged-pool P instead")

    import jax.numpy as jnp

    from mlx_sharding_tpu.generate import Generator, stream_generate
    from mlx_sharding_tpu.loading import get_model_path, load_model

    if args.stage_bounds and args.engine == "chained":
        from mlx_sharding_tpu.parallel.chained import load_chained_pipeline

        bounds = [
            tuple(int(x) for x in part.split("-"))
            for part in args.stage_bounds.split(",")
        ]
        generator = load_chained_pipeline(
            args.model, bounds, max_seq=args.max_seq,
            prefill_chunk=args.prefill_chunk,
            keep_quantized=args.keep_quantized,
        )
    elif args.stage_bounds or (args.num_stages and args.num_stages > 1) or args.tp > 1 or args.ep > 1:
        from mlx_sharding_tpu.parallel.mesh import make_mesh
        from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

        bounds = None
        if args.stage_bounds:
            bounds = [
                tuple(int(x) for x in part.split("-"))
                for part in args.stage_bounds.split(",")
            ]
        model, params = load_model(
            args.model, args.start_layer, args.end_layer,
            keep_quantized=args.keep_quantized,
        )
        generator = PipelineEngine(
            model, params,
            make_mesh(pp=len(bounds) if bounds else (args.num_stages or 1),
                      tp=args.tp, ep=args.ep),
            stage_bounds=bounds,
            max_seq=args.max_seq, prefill_chunk=args.prefill_chunk,
            paged_attention=args.paged_attention,
            kv_dtype=args.kv_dtype,
        )
    else:
        model, params = load_model(
            args.model, args.start_layer, args.end_layer,
            keep_quantized=args.keep_quantized,
        )
        sp_mesh = None
        if args.sp and args.sp > 1:
            from mlx_sharding_tpu.parallel.mesh import make_mesh

            sp_mesh = make_mesh(sp=args.sp)
        if args.draft == "ngram":
            from mlx_sharding_tpu.speculative import NgramSpeculativeGenerator

            generator = NgramSpeculativeGenerator(
                model, params,
                spec_window_max=args.spec_window_max or 8,
                max_seq=args.max_seq,
                prefill_chunk=args.prefill_chunk,
            )
        elif args.draft_model:
            from mlx_sharding_tpu.speculative import SpeculativeGenerator

            draft_model, draft_params = load_model(args.draft_model)
            generator = SpeculativeGenerator(
                model, params, draft_model, draft_params,
                spec_k=args.spec_k, max_seq=args.max_seq,
                prefill_chunk=args.prefill_chunk,
            )
        else:
            generator = Generator(
                model, params, max_seq=args.max_seq,
                prefill_chunk=args.prefill_chunk, sp_mesh=sp_mesh,
                sp_decode=args.sp_decode,
            )

    from transformers import AutoTokenizer

    tokenizer = AutoTokenizer.from_pretrained(str(get_model_path(args.model)))
    if getattr(tokenizer, "chat_template", None) and not args.no_chat_template:
        prompt_ids = tokenizer.apply_chat_template(
            [{"role": "user", "content": args.prompt}],
            tokenize=True, add_generation_prompt=True,
        )
    else:
        prompt_ids = tokenizer.encode(args.prompt)

    stats = None
    for chunk in stream_generate(
        generator, tokenizer, list(prompt_ids),
        max_tokens=args.max_tokens,
        temperature=args.temperature,
        top_p=args.top_p,
        repetition_penalty=args.repetition_penalty,
        seed=args.seed,
    ):
        if chunk.text:
            print(chunk.text, end="", flush=True)
        if chunk.finish_reason is not None:
            stats = chunk
    print()
    # same instrumentation the reference prints (ref generate.py:115-122)
    print("=" * 10, file=sys.stderr)
    print(
        f"Prompt: {stats.prompt_tokens} tokens, {stats.prompt_tps:.3f} tokens-per-sec",
        file=sys.stderr,
    )
    print(
        f"Generation: {stats.generation_tokens} tokens, "
        f"{stats.generation_tps:.3f} tokens-per-sec",
        file=sys.stderr,
    )
    print(f"TTFT: {stats.ttft * 1000:.1f} ms", file=sys.stderr)


if __name__ == "__main__":
    main()
