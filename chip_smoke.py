#!/usr/bin/env python3
"""Quickest proof that the served path still starts on the chip.

    python chip_smoke.py              one TPU chip: kernels, server, CLI
    python chip_smoke.py --chips 4    four chips: the pp=4 pipeline engine
                                      against pp=1, and nothing else
    python chip_smoke.py --rehearse   the same control flow at tiny widths
                                      on whatever backend is there (Pallas
                                      kernels in interpret mode)

The model is a Llama-3.2-3B-class config (``LLAMA_3B`` below) at full width
and depth in bf16, with random weights made from ``--seed``: a bring-up
vehicle, not a benchmark cell. Every time printed here is a smoke timing
(cold compiles and a checkpoint write included), never a metric.

One process per chip: this parent never imports JAX, nor anything under
mlx_sharding_tpu (whose ``__init__`` does); every phase is a child process
and the children run one after another. Without ``--rehearse`` each child
gets ``JAX_PLATFORMS=tpu``, so a missing chip is an error in JAX itself and
never a silent CPU run. A failed phase fails the run.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as a child's JAX reports it; the exit code is 0 only with
``"ok": true``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
#: git-ignored, and NOT the directory the chip tool copies back: the
#: checkpoint is ~6.4 GB. Input to nothing — rewritten from the seed each run.
WORK = ROOT / ".chip_smoke"
DEADLINE_S = 1150  # the driver allows 1200 s, compilation included

LLAMA_3B = dict(
    model_type="llama", architectures=["LlamaForCausalLM"],
    vocab_size=128256, hidden_size=3072, intermediate_size=8192,
    num_hidden_layers=28, num_attention_heads=24, num_key_value_heads=8,
    head_dim=128, tie_word_embeddings=True, max_position_embeddings=4096,
    rms_norm_eps=1e-5, rope_theta=500000.0, torch_dtype="bfloat16",
)
TINY = dict(
    LLAMA_3B, vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16,
)

# Tolerances. Each is a bound on |kernel − XLA path| (or |path A − path B|)
# for the SAME mathematical function, so what it has to absorb is rounding.
#
# Attention (flash, paged): inputs are bf16 N(0,1), outputs are softmax
# averages of V rounded to bf16 (8 significand bits → ≤ 2^-8 relative, and
# |out| ≲ 4), the two paths accumulate in f32 in different orders, and the
# XLA path rounds the probabilities to bf16 before the PV matmul where the
# kernels keep them in f32. int8 pools add nothing: both sides dequantize
# the same codes.
ATTN_ATOL = 3e-2
# 4-bit matmuls: |err| relative to max|ref|. The XLA fallback rounds the
# dequantized weight to bf16 (2^-9 relative per element) before a bf16
# matmul; the kernels keep scale·nibble+bias in f32. Over IN ≥ 2048 random
# terms that is a few 1e-3 of the output scale.
QUANT_RTOL = 2e-2
# bf16 routed experts, the walk over the picked experts (one kernel: float32
# activation and sum, cast once) against the gather path and against the
# loop: those round every product and the running sum to bf16 (2^-9
# relative) in their own order over up to 22 terms a row; a wrong expert or
# layer read is O(1) of the output scale.
EXPERTS_RTOL = 4e-2
# Served logprobs, one serving path against another (paged pool + ragged
# kernel + batched slots vs dense cache + single request; pp=4 vs pp=1):
# every layer rounds its activations to bf16, 28 layers compound that, and
# the head projects to logits of O(1) magnitude — differences of a few
# 1e-2 nats are rounding, a wrong page or a masked-in stale row is O(1).
LOGPROB_ATOL = 0.15
# of the two top-10 lists at one position, this many ids must coincide
# (ranks 9-11 swap under rounding; a different distribution shares ~none)
TOP10_MIN_COMMON = 7


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class PhaseFailed(Exception):
    pass


def need(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# --------------------------------------------------------------------------
# Checkpoint: HF layout, written tensor by tensor from a seeded generator.
# --------------------------------------------------------------------------

def _bf16_bits(x: np.ndarray) -> np.ndarray:
    """float32 → bfloat16 bit patterns (round to nearest even), as uint16."""
    u = x.view(np.uint32)
    return ((u + 0x7FFF + ((u >> 16) & 1)) >> 16).astype(np.uint16)


def _write_safetensors(path: Path, tensors: list[tuple[str, tuple, object]],
                       seed_words: list[int], std: float) -> None:
    """One safetensors file. ``tensors`` is (name, shape, fill) with fill
    either a float (constant) or None (N(0, std²) from the seeded stream).
    Rows are generated and written in blocks, so no tensor is ever whole in
    memory as float32."""
    header, offset = {"__metadata__": {"format": "pt"}}, 0
    for name, shape, _ in tensors:
        size = int(np.prod(shape)) * 2
        header[name] = {"dtype": "BF16", "shape": list(shape),
                        "data_offsets": [offset, offset + size]}
        offset += size
    blob = json.dumps(header, separators=(",", ":")).encode()
    with open(path, "wb") as f:
        f.write(len(blob).to_bytes(8, "little"))
        f.write(blob)
        for index, (_, shape, fill) in enumerate(tensors):
            rng = np.random.default_rng([*seed_words, index])
            rows, cols = (shape[0], int(np.prod(shape[1:])))
            for r0 in range(0, rows, 4096):
                n = min(4096, rows - r0)
                if fill is None:
                    block = rng.standard_normal((n, cols), np.float32) * std
                else:
                    block = np.full((n, cols), fill, np.float32)
                f.write(_bf16_bits(block).tobytes())


def _write_tokenizer(out: Path, vocab_size: int) -> None:
    """A word-level tokenizer whose vocabulary IS the id range: id i decodes
    to ``w<i>`` (0 is the unknown word), so any id the head can emit decodes,
    ``"w5 w9"`` encodes to exactly [5, 9], and there is no EOS to end a
    random-weight generation early."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = {"<unk>": 0, **{f"w{i}": i for i in range(1, vocab_size)}}
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(str(out / "tokenizer.json"))
    (out / "tokenizer_config.json").write_text(
        json.dumps({"tokenizer_class": "PreTrainedTokenizerFast"})
    )


def write_checkpoint(out: Path, cfg: dict, seed: int) -> None:
    if out.exists():
        shutil.rmtree(out)
    out.mkdir(parents=True)
    (out / "config.json").write_text(json.dumps(cfg, indent=1))
    _write_tokenizer(out, cfg["vocab_size"])
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n_layers = cfg["num_hidden_layers"]
    files = [[("model.embed_tokens.weight", (v, h), None),
              ("model.norm.weight", (h,), 1.0)]]
    for n in range(n_layers):
        p = f"model.layers.{n}."
        files.append([
            (p + "self_attn.q_proj.weight", (nq * hd, h), None),
            (p + "self_attn.k_proj.weight", (nkv * hd, h), None),
            (p + "self_attn.v_proj.weight", (nkv * hd, h), None),
            (p + "self_attn.o_proj.weight", (h, nq * hd), None),
            (p + "mlp.gate_proj.weight", (i, h), None),
            (p + "mlp.up_proj.weight", (i, h), None),
            (p + "mlp.down_proj.weight", (h, i), None),
            (p + "input_layernorm.weight", (h,), 1.0),
            (p + "post_attention_layernorm.weight", (h,), 1.0),
        ])
    names = [f"model-{k + 1:05d}-of-{len(files):05d}.safetensors"
             for k in range(len(files))]
    # hidden^-0.5 (0.018 at width 3072, the usual 0.02) keeps every layer's
    # output and the logits at O(1) at ANY width, so the tiny rehearsal
    # model attends and mixes too instead of echoing its last token.
    # The normal draws release the GIL, so threads scale; each file has its
    # own seeded stream, so the bytes do not depend on the schedule.
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as pool:
        jobs = [pool.submit(_write_safetensors, out / name, tensors,
                            [seed, k], h ** -0.5)
                for k, (name, tensors) in enumerate(zip(names, files))]
        for job in jobs:
            job.result()
    (out / "model.safetensors.index.json").write_text(json.dumps({
        "metadata": {"total_size": sum(f.stat().st_size for f in out.glob("*.safetensors"))},
        "weight_map": {t[0]: name for name, ts in zip(names, files) for t in ts},
    }))


def words(seed: int, tag: int, n: int, vocab_size: int) -> str:
    """An n-token prompt as text (see _write_tokenizer)."""
    ids = np.random.default_rng([seed, 1000 + tag]).integers(1, vocab_size, n)
    return " ".join(f"w{t}" for t in ids)


# --------------------------------------------------------------------------
# Children
# --------------------------------------------------------------------------

class Run:
    """What the phases share: the options, the child environment, the
    deadline, and the device line once a child has reported it."""

    def __init__(self, seed: int, rehearse: bool):
        self.seed, self.rehearse = seed, rehearse
        self.cfg = TINY if rehearse else LLAMA_3B
        self.ckpt = WORK / ("ckpt-rehearse" if rehearse else "ckpt")
        self.logs = WORK / "logs"
        self.t_end = time.monotonic() + DEADLINE_S
        self.device = None
        self.procs: list[subprocess.Popen] = []
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (str(ROOT), env.get("PYTHONPATH")) if p
        )
        env["PYTHONUNBUFFERED"] = "1"
        # WARNING-level "Persistent compilation cache hit" lines in the
        # child logs: how a later child shows it found an earlier one's work
        env["JAX_LOG_COMPILES"] = "1"
        if rehearse:
            # four virtual devices if the backend turns out to be the CPU
            # (the flag touches no other backend)
            flags = env.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                env["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=4"
                ).strip()
        else:
            env["JAX_PLATFORMS"] = "tpu"
        self.env = env

    def left(self) -> float:
        return max(1.0, self.t_end - time.monotonic())

    def spawn(self, name: str, argv: list[str], pipe_stdout: bool = False):
        """Start a child in its own process group; stderr (and stdout,
        unless piped to the caller) goes to its log file."""
        self.logs.mkdir(parents=True, exist_ok=True)
        log = self.logs / f"{name}.log"
        with open(log, "w") as f:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=ROOT, env=self.env,
                stdout=subprocess.PIPE if pipe_stdout else f, stderr=f,
                text=True, start_new_session=True,
            )
        self.procs.append(proc)
        return proc, log

    def run(self, name: str, argv: list[str]) -> tuple[str, Path]:
        """Run a child to its end; its stdout is echoed and returned."""
        proc, log = self.spawn(name, argv, pipe_stdout=True)
        out: list[str] = []

        def pump():
            for line in proc.stdout:
                out.append(line)
                print(f"  [{name}] {line}", end="", flush=True)
                if line.startswith('{"device":'):
                    # first line of a child that touches JAX: known from
                    # here on, even if a later check fails
                    self.device = json.loads(line)["device"]

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        try:
            rc = proc.wait(timeout=self.left())
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{name}: out of time") from None
        t.join(timeout=10)
        if rc != 0:
            raise PhaseFailed(f"{name}: exit code {rc}\n{tail(log)}")
        return "".join(out), log

    def call(self, func: str, *args) -> None:
        """Run ``chip_smoke.<func>(seed, rehearse, *args)`` in a child."""
        args = (self.seed, self.rehearse, *args)
        code = f"import chip_smoke; chip_smoke.{func}(*{args!r})"
        self.run(func.removeprefix("child_"), ["-c", code])

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()


def tail(log: Path, n: int = 40) -> str:
    lines = log.read_text(errors="replace").splitlines()
    keep = [ln for ln in lines if "Compiling " not in ln
            and "Finished " not in ln]
    return "\n".join(f"    | {ln}" for ln in keep[-n:])


def cache_hits(log: Path) -> int:
    return log.read_text(errors="replace").count(
        "Persistent compilation cache hit"
    )


def _device_line() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def child_kernels(seed: int, rehearse: bool) -> None:
    """Phases 1 and 2, in the one child that may touch the chip now: report
    the device, then run every Pallas kernel of ops/ against the XLA path it
    replaces. On the chip each call goes through the DISPATCHER the models
    use and the compiled text must hold the kernel (``tpu_custom_call``),
    so a predicate that quietly chose XLA fails here; the rehearsal calls
    the kernels directly in interpret mode."""
    import functools
    import itertools

    import jax
    import jax.numpy as jnp

    from mlx_sharding_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = _device_line()
    print(json.dumps({"device": device}), flush=True)
    if not rehearse and device["platform"] != "tpu":
        raise SystemExit(f"platform is {device['platform']!r}, not 'tpu'")

    from mlx_sharding_tpu.cache import quantize_kv_rows
    from mlx_sharding_tpu.ops import quant
    from mlx_sharding_tpu.ops.attention import (
        _causal_attention_xla,
        causal_attention,
    )
    from mlx_sharding_tpu.ops.flash_attention import flash_attention
    from mlx_sharding_tpu.ops.paged_attention import (
        _paged_attention_xla,
        paged_attention,
    )
    from mlx_sharding_tpu.ops.quant_matmul import quant_matmul_pallas

    def check(name, kernel_name, fn, ref_fn, args, tol, relative=False,
              temp_below=None):
        t0 = time.perf_counter()
        if rehearse:
            got = fn(*args)
            how = "interpret"
        else:
            compiled = jax.jit(fn).lower(*args).compile()
            text = compiled.as_text()
            how = "tpu_custom_call" if kernel_name else "xla"
            if kernel_name and (how not in text or kernel_name not in text):
                raise SystemExit(
                    f"{name}: the dispatcher did not select the Pallas "
                    f"kernel {kernel_name!r} for this shape on the chip"
                )
            temp = compiled.memory_analysis().temp_size_in_bytes
            if temp_below is not None:
                how += f", {temp} B of temporaries"
                if temp >= temp_below:
                    raise SystemExit(
                        f"{name}: {temp} B of temporaries, {temp_below} B "
                        "or more: the program copies what it should read in place"
                    )
            got = compiled(*args)
        want = jax.jit(ref_fn)(*args)
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        if not bool(jnp.isfinite(got).all()) or got.shape != want.shape:
            raise SystemExit(f"{name}: non-finite output or wrong shape")
        err = float(jnp.max(jnp.abs(got - want)))
        if relative:
            err /= float(jnp.max(jnp.abs(want))) + 1e-9
        print(f"kernel {name}: {how}, err {err:.2e} (tol {tol:g}), "
              f"{time.perf_counter() - t0:.1f} s smoke timing", flush=True)
        if err > tol:
            raise SystemExit(f"{name}: err {err:.3e} exceeds {tol:g}")

    key = jax.random.PRNGKey(seed)
    bf16 = jnp.bfloat16
    if rehearse:
        hq, hkv, d, s_len, t_len, pages = 4, 2, 16, 64, 16, (8, 16)
        quant_shapes, quant_rows = [(128, 256), (256, 128)], (16, 1, 8)
    else:
        hq, hkv, d, s_len, t_len, pages = 24, 8, 128, 4096, 256, (256, 128)
        # the three Llama-3B projections, and DeepSeek-V2-Lite's dense MLP,
        # the benchmark's own shapes: 10944 = 64 x 171 rows end in a ragged
        # OUT tile, and as an IN they are 1368 word lanes in one whole block
        quant_shapes = [(8192, 3072), (3072, 8192), (128256, 3072),
                        (10944, 2048), (2048, 10944)]
        quant_rows = (256, 16, 1, 8)
    scale = d ** -0.5

    # ---- flash attention: a prefill chunk deep in the cache, and T=1
    kq, kk, kv, key = jax.random.split(key, 4)
    k = jax.random.normal(kk, (1, s_len, hkv, d), bf16)
    v = jax.random.normal(kv, (1, s_len, hkv, d), bf16)
    for t, off in ((t_len, s_len - 2 * t_len), (1, s_len - 3)):
        q = jax.random.normal(kq, (1, t, hq, d), bf16)
        direct = functools.partial(flash_attention, scale=scale,
                                   interpret=rehearse)
        via_dispatch = functools.partial(causal_attention, scale=scale)
        # the dispatcher sends T=1 to the XLA path: the kernel's one-row
        # tile is called directly
        fn = direct if (rehearse or t == 1) else via_dispatch
        check(f"flash T={t} S={s_len}", "flash_attention", fn,
              functools.partial(_causal_attention_xla, scale=scale),
              (q, k, v, jnp.asarray(off, jnp.int32)), ATTN_ATOL)

    # ---- ragged paged attention: bf16 and int8 pools, two page sizes,
    # eight slots of uneven length (empty, page-boundary, full); the GQA
    # layout and MLA's latent one (one 576-lane latent head whose first 512
    # lanes are the values, V a (…, 1, 1) dummy: DeepSeek-V2-Lite's widths)
    max_seq = s_len
    layouts = {"": (hq, hkv, d, d, None),
               "latent ": (4, 1, 24, 1, 16) if rehearse else (16, 1, 576, 1, 512)}
    for page, (layout, (lhq, lhkv, dk, dv, rank)) in itertools.product(
        pages, layouts.items()
    ):
        spg = max_seq // page
        lengths = [0, 1, page, page + 1, 3 * page - 1, max_seq // 2 + 5,
                   max_seq - 1, max_seq]
        m = len(lengths)
        kq, kk, kv, kp, key = jax.random.split(key, 5)
        n_pages = m * spg
        k_pool = jax.random.normal(kk, (n_pages + 1, page, lhkv, dk), bf16)
        v_pool = jax.random.normal(kv, (n_pages + 1, page, lhkv, dv), bf16)
        # each slot owns a shuffled set of pages; past its length, scratch
        perm = np.asarray(jax.random.permutation(kp, n_pages)).reshape(m, spg)
        tables = np.full((m, spg), n_pages, np.int32)
        for i, ln in enumerate(lengths):
            used = -(-ln // page)
            tables[i, :used] = perm[i, :used]
        q = jax.random.normal(kq, (m, lhq, dk), bf16)
        tables, lens = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
        kq8, vq8 = quantize_kv_rows(k_pool), quantize_kv_rows(v_pool)
        for label, args in (
            ("bf16", (q, k_pool, v_pool, tables, lens)),
            ("int8", (q, kq8["d"], vq8["d"], tables, lens,
                      kq8["s"], vq8["s"])),
        ):
            def fn(q, kp_, vp_, tb, ln, ks=None, vs=None):
                return paged_attention(q, kp_, vp_, tb, ln, dk ** -0.5,
                                       values_from_k=rank, k_scale=ks,
                                       v_scale=vs, interpret=rehearse)

            def ref(q, kp_, vp_, tb, ln, ks=None, vs=None):
                return _paged_attention_xla(q, kp_, vp_, tb, ln, dk ** -0.5,
                                            None, None, rank, ks, vs)

            check(f"paged {layout}{label} page={page}", "paged_attention", fn,
                  ref, args, ATTN_ATOL)

    # ---- ... with a window, over a ring of window pages, and without one
    # over full-length pages, both at the trinity-large-bf16-ep16 cell's
    # shapes: 32 slots, 48 query heads on 8 K/V heads of 128 whose rows keep
    # the heads merged on the lane axis (kv_heads), 512-token pages, lengths
    # 8192-16000 (every slot past the 4096 window; the ring of 10 pages has
    # wrapped one to three times)
    if rehearse:
        w_slots, w_hq, w_page, w_seq, w_win, w_ring = 4, 12, 8, 64, 16, 4
    else:
        w_slots, w_hq, w_page, w_seq, w_win, w_ring = 32, 48, 512, 16384, 4096, 10
    w_spg = w_seq // w_page
    lens = jnp.asarray(np.linspace(w_seq // 2, w_seq - w_seq // 42, w_slots), jnp.int32)
    kq, kk, kv, key = jax.random.split(key, 4)
    q = jax.random.normal(kq, (w_slots, w_hq, d), bf16)
    slot = np.arange(w_slots)[:, None]
    for name, n_pages, table, window in (
        ("window ring", w_slots * w_ring, slot * w_ring + np.arange(w_spg)[None] % w_ring, w_win),
        ("full", w_slots * w_spg, slot * w_spg + np.arange(w_spg)[None], None),
    ):
        k_pool = jax.random.normal(kk, (n_pages + 1, w_page, 1, hkv * d), bf16)
        v_pool = jax.random.normal(kv, (n_pages + 1, w_page, 1, hkv * d), bf16)
        check(f"paged {name} merged heads page={w_page}", "paged_attention",
              functools.partial(paged_attention, scale=scale, sliding_window=window,
                                kv_heads=hkv, interpret=rehearse),
              lambda q_, k_, v_, tb, ln, w=window: _paged_attention_xla(
                  q_, k_, v_, tb, ln, scale, None, w, None, kv_heads=hkv),
              (q, k_pool, v_pool, jnp.asarray(table, jnp.int32), lens), ATTN_ATOL)
        del k_pool, v_pool

    # ---- the zaya1-8b-bf16-pp2ep2 cell's geometry: 24 slots, 8 query heads on
    # 2 K/V heads of 128 with merged rows, 512-token pages, every slot 8k-12k
    # tokens into a 12288-token table
    if rehearse:
        z_slots, z_hq, z_hkv, z_page, z_seq = 3, 4, 2, 8, 48
    else:
        z_slots, z_hq, z_hkv, z_page, z_seq = 24, 8, 2, 512, 12288
    z_spg = z_seq // z_page
    kq, kk, kv, key = jax.random.split(key, 4)
    k_pool = jax.random.normal(kk, (z_slots * z_spg + 1, z_page, 1, z_hkv * d), bf16)
    v_pool = jax.random.normal(kv, k_pool.shape, bf16)
    check(f"paged latent GQA {z_hq} on {z_hkv} merged heads page={z_page}", "paged_attention",
          functools.partial(paged_attention, scale=scale, kv_heads=z_hkv, interpret=rehearse),
          lambda q_, k_, v_, tb, ln: _paged_attention_xla(
              q_, k_, v_, tb, ln, scale, None, None, None, kv_heads=z_hkv),
          (jax.random.normal(kq, (z_slots, z_hq, d), bf16), k_pool, v_pool,
           jnp.asarray(np.arange(z_slots * z_spg).reshape(z_slots, z_spg), jnp.int32),
           jnp.asarray(np.linspace(2 * z_seq // 3, z_seq - z_seq // 42, z_slots), jnp.int32)),
          ATTN_ATOL)
    del k_pool, v_pool

    # ---- the same family's convolutions and value shift: a decode step that
    # starts from the per-slot state 63 rows left, against the full-sequence
    # form's last row (one layer at the published widths)
    from mlx_sharding_tpu.models import build_model

    zaya, _ = build_model(dict(
        model_type="zaya", vocab_size=256, num_hidden_layers=1, num_experts=1,
        **(dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=d,
                moe_intermediate_size=32, router_hidden_size=16) if rehearse else
           dict(hidden_size=2048, num_attention_heads=8, num_key_value_heads=2, head_dim=128,
                moe_intermediate_size=2048, router_hidden_size=256))))
    kp, kx, key = jax.random.split(key, 3)
    layer = jax.tree.map(lambda w: w[0], zaya.init_params(kp, bf16)["layers"])
    x = jax.random.normal(kx, (z_slots, 64, zaya.config.hidden_size), bf16)

    def cca(x, layer, steps):
        cache = zaya.make_cache(x.shape[0], 64, bf16)
        k_buf, v_buf = cache.k[0], cache.v[0]
        st = jax.tree.map(lambda a: a[0], cache.state)
        at = 0
        for n in steps:
            out, k_buf, v_buf, st = zaya._attn(
                layer, x[:, at:at + n], st, k_buf, v_buf, jnp.asarray(at, jnp.int32),
                None, None, None)
            at += n
        return out[:, -1]

    check("cca mix: a decode step from the slot state, against the full sequence", None,
          functools.partial(cca, steps=(63, 1)), functools.partial(cca, steps=(64,)),
          (x, layer), ATTN_ATOL)

    # ---- the granite4-h-micro-bf16 cell's attention: the first 64-wide heads
    # on the chip. 48 slots, 32 query heads (4 rows a head) on 8 K/V heads of
    # 64 merged on 512 lanes (the odd heads start half a lane tile in),
    # 512-token pages, a 4608-token table, scale attention_multiplier
    if rehearse:
        g_slots, g_hq, g_hkv, g_d, g_page, g_seq = 3, 8, 2, 16, 8, 48
    else:
        g_slots, g_hq, g_hkv, g_d, g_page, g_seq = 48, 32, 8, 64, 512, 4608
    g_spg, g_scale = g_seq // g_page, 0.015625
    kq, kk, kv, key = jax.random.split(key, 4)
    k_pool = jax.random.normal(kk, (g_slots * g_spg + 1, g_page, 1, g_hkv * g_d), bf16)
    v_pool = jax.random.normal(kv, k_pool.shape, bf16)
    check(f"paged GQA {g_hq} on {g_hkv} merged heads of {g_d} page={g_page}", "paged_attention",
          functools.partial(paged_attention, scale=g_scale, kv_heads=g_hkv, interpret=rehearse),
          lambda q_, k_, v_, tb, ln: _paged_attention_xla(
              q_, k_, v_, tb, ln, g_scale, None, None, None, kv_heads=g_hkv),
          (jax.random.normal(kq, (g_slots, g_hq, g_d), bf16), k_pool, v_pool,
           jnp.asarray(np.arange(g_slots * g_spg).reshape(g_slots, g_spg), jnp.int32),
           jnp.asarray(np.linspace(g_seq // 9, g_seq - 7, g_slots), jnp.int32)),
          ATTN_ATOL)
    del k_pool, v_pool

    # ---- the same family's Mamba-2 (ops/mamba2.py, nemotron_h's too) at the
    # published sizes: the chunked form at chunk 256 with a ragged last chunk
    # against the recurrence one position at a time, float32; then one layer's
    # mixer, a decode step from the slot state 600 rows left against the
    # chunked form over all 601
    from mlx_sharding_tpu.models.base import LayerRow
    from mlx_sharding_tpu.ops.mamba2 import ssd_chunked, ssm_sequential

    m_h, m_p, m_n, m_chunk, m_t = (4, 8, 16, 8, 21) if rehearse else (64, 64, 128, 256, 600)
    ks = jax.random.split(key, 7)
    key = ks[6]
    m_x = jax.random.normal(ks[0], (2, m_t, m_h, m_p), jnp.float32)
    m_dt = jax.nn.softplus(jax.random.normal(ks[1], (2, m_t, m_h)) - 3.0)
    m_a = -jnp.exp(jax.random.uniform(ks[2], (m_h,), jnp.float32, 0.0, 2.5))
    one_group = lambda k_: jnp.repeat(  # noqa: E731
        jax.random.normal(k_, (2, m_t, 1, m_n), jnp.float32), m_h, axis=2)
    m_s0 = jax.random.normal(ks[5], (2, m_h, m_p, m_n), jnp.float32)
    both = lambda ys: jnp.concatenate([ys[0].reshape(2, -1), ys[1].reshape(2, -1)], axis=1)  # noqa: E731
    check(f"mamba-2 chunked form, chunk {m_chunk} over {m_t} rows, against the sequential recurrence",
          None, lambda *a: both(ssd_chunked(*a, m_chunk)), lambda *a: both(ssm_sequential(*a)),
          (m_x, m_dt, m_a, one_group(ks[3]), one_group(ks[4]), m_s0), 2e-3, relative=True)
    del m_x, m_s0

    # ---- a decode step's recurrence in ONE pass over the state pool where it
    # lies (ops/mamba2.py:ssm_pool_step) against the XLA one-step formula, at
    # granite-4.0-h-micro's 48 slots x 64 heads on one group and nemotron3's
    # 32 x 128 heads on 8 groups, the middle layer of three, one slot frozen:
    # y, the layer's rows, and every row the step must not touch
    from mlx_sharding_tpu.ops.mamba2 import ssm_pool_step

    def pool_step_ref(pool, dt, x, bm, cm, a, active):
        nb, rep = x.shape[0], x.shape[1] // bm.shape[1]
        heads = lambda z: jnp.repeat(z, rep, axis=1)[:, None]  # noqa: E731
        y, s = ssm_sequential(x[:, None], dt[:, None], a, heads(bm), heads(cm), pool[1, :nb])
        s = jnp.where(active[:, None, None, None], s, pool[1, :nb])
        return jnp.concatenate([y.ravel(), pool.at[1, :nb].set(s).ravel()])

    def pool_step(pool, dt, x, bm, cm, a, active):
        y, pool = ssm_pool_step(pool, 1, dt, x, bm, cm, a, active, interpret=rehearse)
        return jnp.concatenate([y.ravel(), pool.ravel()])

    for s_b, s_h, s_g in ((3, 4, 1), (3, 8, 2)) if rehearse else ((48, 64, 1), (32, 128, 8)):
        ks = jax.random.split(key, 7)
        key = ks[6]
        check(f"mamba-2 decode step in one pass over the pool, {s_b} slots x {s_h} heads on {s_g} groups",
              "ssm_pool_step", pool_step, pool_step_ref,
              (jax.random.normal(ks[0], (3, s_b + 1, s_h, m_p, m_n), jnp.float32),
               jax.nn.softplus(jax.random.normal(ks[1], (s_b, s_h)) - 3.0),
               jax.random.normal(ks[2], (s_b, s_h, m_p), jnp.float32),
               jax.random.normal(ks[3], (s_b, s_g, m_n), jnp.float32),
               jax.random.normal(ks[4], (s_b, s_g, m_n), jnp.float32),
               -jnp.exp(jax.random.uniform(ks[5], (s_h,), jnp.float32, 0.0, 2.5)),
               jnp.ones((s_b,), bool).at[1].set(False)),
              1e-6, relative=True)

    granite, _ = build_model(dict(
        model_type="granitemoehybrid", vocab_size=256, num_hidden_layers=1,
        layer_types=["mamba"], mamba_chunk_size=m_chunk, mamba_d_state=m_n,
        mamba_n_heads=m_h, mamba_d_head=m_p,
        **(dict(hidden_size=16, num_attention_heads=2, shared_intermediate_size=16)
           if rehearse else
           dict(hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
                shared_intermediate_size=8192))))
    kp, kx, key = jax.random.split(key, 3)
    stack = granite.init_params(kp, bf16)["layers"]["mamba"]
    x = jax.random.normal(kx, (4, m_t + 1, granite.config.hidden_size), bf16)

    def mamba(x, stack, steps):
        state = granite.make_cache(x.shape[0], 8, bf16).state
        at = 0
        for n in steps:
            out, state = granite._mamba(
                LayerRow(stack, 0), x[:, at:at + n], state, 0, None, None)
            at += n
        return out[:, -1]

    check("mamba-2 mixer: a decode step from the slot state, against the chunked form", None,
          functools.partial(mamba, steps=(m_t, 1)), functools.partial(mamba, steps=(m_t + 1,)),
          (x, stack), ATTN_ATOL)

    # ---- the kimi-linear-48b-bf16-ep16 cell's gated delta rule (ops/kda.py)
    # at the published sizes, float32: the chunked (WY) form at block 64 with
    # a ragged last block and the strongest decay (exp(A_log) = 16 on head 0)
    # against the recurrence one position at a time; then a decode step in ONE
    # pass over the state pool where it lies (kda_pool_step: 40 slots x 32
    # heads of 128 x 128, the middle layer of three, one slot frozen) against
    # the same recurrence: o, the layer's rows, every row it must not touch
    from mlx_sharding_tpu.ops import kda

    k_b, k_h, k_d, k_t = (3, 4, 16, 21) if rehearse else (40, 32, 128, 200)
    ks = jax.random.split(key, 8)
    key = ks[7]

    def kda_inputs(b, t):
        q = kda._l2norm(jax.random.normal(ks[0], (b, t, k_h, k_d))) * k_d**-0.5
        k_ = kda._l2norm(jax.random.normal(ks[1], (b, t, k_h, k_d)))
        decay = -jnp.linspace(16.0, 0.01, k_h)[:, None] * jax.nn.softplus(
            jax.random.normal(ks[3], (b, t, k_h, k_d)) - 3.0)
        return (q, k_, jax.random.normal(ks[2], (b, t, k_h, k_d)), decay,
                jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, k_h))))

    check(f"KDA chunked form, block {kda.CHUNK if not rehearse else 8} over {k_t} rows, against the sequential recurrence",
          None, lambda *a: both(kda.kda_chunked(*a, 8 if rehearse else kda.CHUNK)),
          lambda *a: both(kda.kda_sequential(*a)),
          (*kda_inputs(2, k_t), jax.random.normal(ks[5], (2, k_h, k_d, k_d))), 1e-3,
          relative=True)
    # (1e-3: it is the SEQUENTIAL side that drifts on the chip, a product of
    # one exponential a row each off by up to 5e-6: 2.5e-4 over 256 rows
    # against float64 where the chunked form reads 4e-6; chip, PR 48)

    def kda_step_ref(pool, q, k_, v, g, beta, active):
        nb = q.shape[0]
        o, s_new = kda.kda_sequential(
            q[:, None], k_[:, None], v[:, None], g[:, None], beta[:, None], pool[1, :nb])
        s_new = jnp.where(active[:, None, None, None], s_new, pool[1, :nb])
        return jnp.concatenate([o.ravel(), pool.at[1, :nb].set(s_new).ravel()])

    def kda_step(pool, q, k_, v, g, beta, active):
        o, pool = kda.kda_pool_step(pool, 1, q, k_, v, g, beta, active, interpret=rehearse)
        return jnp.concatenate([o.ravel(), pool.ravel()])

    check(f"KDA decode step in one pass over the pool, {k_b} slots x {k_h} heads of {k_d} x {k_d}",
          "kda_pool_step", kda_step, kda_step_ref,
          (jax.random.normal(ks[6], (3, k_b + 1, k_h, k_d, k_d), jnp.float32),
           *(z[:, 0] for z in kda_inputs(k_b, 1)),
           jnp.ones((k_b,), bool).at[1].set(False)),
          1e-6, relative=True)

    # ---- the same cell's latent attention: 40 slots, 32 query heads on the
    # ONE shared head of a 576-wide row whose first 512 values are the values
    # (values_from_k), bf16, 512-token pages, a 6144-token table, every slot
    # 0.8k-6k tokens in, scale 192**-0.5 (dsv2-lite-q4's kernel at twice its
    # heads and ten times its contexts)
    if rehearse:
        l_slots, l_hq, l_row, l_lat, l_page, l_seq = 3, 4, 24, 16, 8, 48
    else:
        l_slots, l_hq, l_row, l_lat, l_page, l_seq = 40, 32, 576, 512, 512, 6144
    l_spg, l_scale = l_seq // l_page, 192**-0.5
    kq, kk, key = jax.random.split(key, 3)
    k_pool = jax.random.normal(kk, (l_slots * l_spg + 1, l_page, 1, l_row), bf16)
    v_pool = jnp.zeros((*k_pool.shape[:3], 1), bf16)
    check(f"paged latent MQA {l_hq} heads on a {l_row}-wide row page={l_page}", "paged_attention",
          functools.partial(paged_attention, scale=l_scale, values_from_k=l_lat,
                            interpret=rehearse),
          lambda q_, k_, v_, tb, ln: _paged_attention_xla(
              q_, k_, v_, tb, ln, l_scale, None, None, l_lat, None, None),
          (jax.random.normal(kq, (l_slots, l_hq, l_row), bf16), k_pool, v_pool,
           jnp.asarray(np.arange(l_slots * l_spg).reshape(l_slots, l_spg), jnp.int32),
           jnp.asarray(np.linspace(l_seq // 8, l_seq - 7, l_slots), jnp.int32)),
          ATTN_ATOL)
    del k_pool, v_pool

    # ---- 4-bit matmuls: the one kernel at a prefill chunk's rows, a
    # 16-slot decode step's, a single stream's one row and 8 slots'
    for out_dim, in_dim in quant_shapes:
        kw, key = jax.random.split(key)
        w = jax.random.normal(kw, (out_dim, in_dim), jnp.float32) * 0.02
        qw, sc, bi = jax.jit(quant.quantize_jax)(w)
        del w
        for m in quant_rows:
            kx, key = jax.random.split(key)
            x = jax.random.normal(kx, (m, in_dim), bf16)
            if rehearse:
                fn = functools.partial(quant_matmul_pallas, interpret=True)
            else:
                fn = functools.partial(quant._quant_matmul, group_size=64,
                                       bits=4)
            check(f"quant_matmul M={m} {in_dim}->{out_dim}", "quant_matmul", fn,
                  functools.partial(quant._quant_matmul_xla, group_size=64,
                                    bits=4),
                  (x, qw, sc, bi), QUANT_RTOL, relative=True)
    # ---- routed experts over 64 packed experts of DeepSeek-V2-Lite's widths
    # through ops.moe.apply_experts, against the same experts dequantized by
    # XLA one at a time (_quant_matmul_xla): a decode step's 16 rows x top-6
    # (all rows against each distinct expert) and a prefill chunk's 256 (the
    # (row, pick) pairs sorted by expert, each expert's own rows in tiles:
    # the grouped path), both on the expert-indexed kernel
    from mlx_sharding_tpu.ops import moe

    if rehearse:
        k, e, hidden, width = 2, 4, 128, 64
        cases = [(8, "kernel", moe._apply_packed_kernel),
                 (40, "grouped", functools.partial(moe._apply_grouped_kernel, tile=8))]
    else:
        k, e, hidden, width = 6, 64, 2048, 1408
        cases = [(16, "kernel", None), (256, "grouped", None)]
    stacks = []
    for out_dim, in_dim in ((width, hidden), (width, hidden), (hidden, width)):
        kw, key = jax.random.split(key)
        w = jax.random.normal(kw, (e, out_dim, in_dim), jnp.float32) * 0.02
        stacks.append(dict(zip(("q", "scales", "biases"),
                               jax.jit(quant.quantize_jax)(w))))
        del w

    def experts_ref(x, weights, idx, w_gate, w_up, w_down):
        mm = functools.partial(quant._quant_matmul_xla, group_size=64, bits=4)

        def one(carry, ws):
            wg, wu, wd, i = ws
            h = jax.nn.silu(mm(x, wg["q"], wg["scales"], wg["biases"])) * mm(
                x, wu["q"], wu["scales"], wu["biases"])
            y = mm(h, wd["q"], wd["scales"], wd["biases"]).astype(jnp.float32)
            coef = ((idx == i) * weights).sum(-1)
            return carry + coef[:, None] * y, None

        acc, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                              (w_gate, w_up, w_down, jnp.arange(e)))
        return acc

    for n, path, interpreted in cases:
        kx, kr, key = jax.random.split(key, 3)
        x = jax.random.normal(kx, (n, hidden), bf16)
        topv, idx = jax.lax.top_k(jax.random.uniform(kr, (n, e)), k)
        weights = topv / topv.sum(-1, keepdims=True)
        if rehearse:
            fn = functools.partial(interpreted, gs=64, bits=4, interpret=True)
        else:
            fn = moe.apply_experts
        taken = moe.dispatch_counts()[path]
        check(f"quant_matmul_experts ({path}) N={n} top-{k} of {e} {hidden}x{width}",
              "quant_matmul_experts", fn, experts_ref,
              (x, weights, idx, *stacks), QUANT_RTOL, relative=True)
        if not rehearse and moe.dispatch_counts()[path] != taken + 1:
            raise SystemExit(
                f"apply_experts did not take the {path} path for {n} rows "
                f"over packed stacks on the chip: {moe.dispatch_counts()}"
            )
    # ---- routed experts under a resident range, a decode step's 32 rows at
    # three bf16 cells' expert widths: bf16 (L, E, ...) stacks read in place
    # by the dense expert-indexed kernel (ops/dense_experts.py: the step's
    # distinct picks a scalar-prefetched table, one call for the layer),
    # against the gather path over the same layer's experts, four rows at a
    # time, and against the LOOP it replaces (plain XLA, an expert an
    # iteration), which the same rows take as part of a chunk: tiled to 160
    # rows they pick the same experts and every row's answer is its own
    if rehearse:
        cases = [("gated", True, 8, 2, 4, 16, 128, 64),
                 ("un-gated", False, 8, 3, 8, 32, 64, 128)]
    else:  # Qwen3-Next: top-10 of 512, 128 held; Nemotron-3: top-22 of 512,
        # 128 held; Trinity-Large: top-4 of 256, 16 held
        cases = [("gated", True, 32, 10, 128, 512, 2048, 512),
                 ("un-gated", False, 32, 22, 128, 512, 1024, 2688),
                 ("gated", True, 32, 4, 16, 256, 3072, 3072)]
    from mlx_sharding_tpu.ops.dense_experts import MAX_ROWS

    for tag, gated, n, k, held, routed, hidden, width in cases:
        kg, ku, kd, kx, kr, key = jax.random.split(key, 6)
        w_up = jax.random.normal(ku, (2, held, hidden, width), bf16) * 0.02
        w_gate = jax.random.normal(kg, w_up.shape, bf16) * 0.02 if gated else None
        w_down = jax.random.normal(kd, (2, held, width, hidden), bf16) * 0.02
        x = jax.random.normal(kx, (n, hidden), bf16)
        topv, idx = jax.lax.top_k(jax.random.uniform(kr, (n, routed)), k)
        weights = topv / topv.sum(-1, keepdims=True)
        base = held  # the layer's second holder: picks fall below, inside, above
        inside = (idx >= base) & (idx < base + held)
        need(bool(inside.any() & (idx < base).any() & (idx >= base + held).any()),
             f"held experts {tag}: the picks do not straddle the held range")

        def held_ref(x, weights, idx, w_gate, w_up, w_down):
            local = jnp.clip(idx - base, 0, held - 1)
            wg, wu, wd = jax.tree.map(lambda w: w[1], (w_gate, w_up, w_down))
            return jnp.concatenate([
                moe._apply_gather(x[r:r + 4], (weights * inside)[r:r + 4],
                                  local[r:r + 4], wg, wu, wd)
                for r in range(0, n, 4)
            ])

        def rows(x, weights, idx, *stacks):
            if rehearse:  # the walk as a TPU backend takes it, interpreted
                return moe._distinct_walk(held, 64, 4, True)(
                    x, weights, idx - base, moe._flat_layers(*stacks),
                    jnp.asarray(held, jnp.int32))
            return moe.apply_experts(x, weights, idx, *stacks, expert_base=base, layer=1)

        def lanes(x, weights, idx, *stacks):
            # the engine's vectorized decode step (--ep, --paged-attention
            # gather): vmap over the slots, one row a lane
            return jax.vmap(lambda *row: rows(*row, *stacks))(
                x[:, None], weights[:, None], idx[:, None])[:, 0]

        def loop_ref(x, weights, idx, *stacks):
            reps = MAX_ROWS // n + 1  # more rows than the kernel takes
            chunk = (jnp.tile(a, (reps, 1)) for a in (x, weights, idx))
            return moe.apply_experts(*chunk, *stacks, expert_base=base, layer=1)[:n]

        # no copy of an expert: less in temporaries than ONE matrix of one
        taken = moe.dispatch_counts()
        for how, fn, ref in (("", rows, held_ref), (", one row a lane", lanes, held_ref),
                             (" against the loop", rows, loop_ref)):
            check(f"held experts {tag}{how} N={n} top-{k} of {routed}, {held} held "
                  f"{hidden}x{width}", "dense_experts", fn, ref,
                  (x, weights, idx, w_gate, w_up, w_down), EXPERTS_RTOL,
                  relative=True, temp_below=hidden * width * 2)
        counts = moe.dispatch_counts()
        if not rehearse and (
            counts["dense_kernel"] < taken["dense_kernel"] + 2
            or counts["scan"] != taken["scan"] + 1
        ):
            raise SystemExit(
                f"apply_experts did not take the dense kernel for {n} rows and "
                f"the loop for {(MAX_ROWS // n + 1) * n} over bf16 stacks on the "
                f"chip: {taken} -> {counts}"
            )
        del w_gate, w_up, w_down
    print(json.dumps({"ok": True, "device": device}), flush=True)


def _compare_top10(where: str, a: dict, b: dict) -> float:
    """Two top-10 {token id: logprob} maps for the same position and the
    same context. Returns the largest difference over the shared ids."""
    a = {int(k): float(v) for k, v in a.items()}
    b = {int(k): float(v) for k, v in b.items()}
    common = set(a) & set(b)
    need(len(common) >= TOP10_MIN_COMMON,
         f"{where}: only {len(common)} of the top-10 ids coincide")
    worst = max(abs(a[t] - b[t]) for t in common)
    need(worst <= LOGPROB_ATOL,
         f"{where}: logprobs differ by {worst:.3f} (tol {LOGPROB_ATOL})")
    return worst


def child_pipeline(seed: int, rehearse: bool, ckpt: str) -> None:
    """``--chips 4``: the fused SPMD pipeline at pp=4 (as ``--num-stages 4``
    builds it) against the pp=1 engine on one of the four chips — prefill
    plus 32 decode steps, compared on logprobs — and where the bytes sit."""
    import jax
    import jax.numpy as jnp

    from mlx_sharding_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = _device_line()
    print(json.dumps({"device": device}), flush=True)
    if not rehearse and device["platform"] != "tpu":
        raise SystemExit(f"platform is {device['platform']!r}, not 'tpu'")
    if device["count"] < 4:
        raise SystemExit(f"--chips 4 needs four devices, JAX has {device['count']}")

    from mlx_sharding_tpu.loading import load_model
    from mlx_sharding_tpu.parallel.mesh import make_mesh
    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

    devices = jax.devices()
    t0 = time.perf_counter()
    write_checkpoint(Path(ckpt), TINY if rehearse else LLAMA_3B, seed)
    print(f"checkpoint written in {time.perf_counter() - t0:.1f} s smoke "
          "timing", flush=True)
    t0 = time.perf_counter()
    model, params = load_model(ckpt)
    cfg = model.config
    kw = dict(max_seq=1024, prefill_chunk=256)
    # the reference sits on chip 1, so chip 0 — where the loader put the
    # checkpoint — never holds the loaded tree and two engines' copies at once
    one = PipelineEngine(model, params, make_mesh(pp=1, devices=devices[1:2]), **kw)
    four = PipelineEngine(model, params, make_mesh(pp=4, devices=devices[:4]), **kw)
    del params
    print(f"engines built in {time.perf_counter() - t0:.1f} s smoke timing",
          flush=True)

    # ---- placement: code that has only seen virtual devices may leave
    # everything on device 0
    def shares(tree):
        per = {d.id: 0 for d in devices[:4]}
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                per[sh.device.id] += sh.data.nbytes
        total = sum(per.values())
        return {k: v / total for k, v in per.items()}, total

    cache = four.init_cache()
    for what, tree in (("layer parameters", four.layer_params),
                       ("embedding/head", four.vocab_parts),
                       ("KV cache", (cache.k, cache.v))):
        share, total = shares(tree)
        print(f"placement {what}: {total / 2**20:.0f} MiB, per-device share "
              + ", ".join(f"{s:.3f}" for s in share.values()), flush=True)
        if any(abs(s - 0.25) > 0.02 for s in share.values()):
            raise SystemExit(f"{what} are not spread a quarter per device")
    del cache
    if not rehearse:
        for d in devices[:4]:
            print(f"device {d.id}: {d.memory_stats()['bytes_in_use'] / 2**30:.2f}"
                  " GiB in use", flush=True)

    # ---- prefill (two chunks) plus 32 decode steps, pp=4 against pp=1.
    # Sampled ids are compared only to keep the two contexts equal: where
    # rounding flips an argmax the run restarts from the reference's prefix.
    def run(engine, prompt, n):
        toks, tops = [], []
        for tok, lp in engine.generate_step(prompt, max_tokens=n,
                                            want_logprobs=True):
            toks.append(int(tok))
            tops.append(dict(zip(lp.top_indices.tolist(), lp.top_values.tolist())))
        return toks, tops

    rng = np.random.default_rng([seed, 4])
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 300)]
    total, done, worst, restarts = 33, 0, 0.0, 0
    t0 = time.perf_counter()
    try:
        while done < total:
            ref_t, ref_p = run(one, prompt, total - done)
            got_t, got_p = run(four, prompt, total - done)
            for i in range(total - done):
                worst = max(worst, _compare_top10(
                    f"pp=4 vs pp=1 at generated position {done + i}",
                    got_p[i], ref_p[i]))
                if got_t[i] != ref_t[i]:
                    break
            done += i + 1
            prompt = prompt + ref_t[: i + 1]
            restarts += done < total
            if restarts > 8:
                raise SystemExit("pp=4 and pp=1 keep choosing different tokens")
    except PhaseFailed as e:
        raise SystemExit(str(e)) from None
    print(f"pp=4 vs pp=1: {total} positions, worst logprob difference "
          f"{worst:.4f} (tol {LOGPROB_ATOL}), {restarts} restarts, "
          f"{time.perf_counter() - t0:.1f} s smoke timing", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


# --------------------------------------------------------------------------
# The server, through its normal entry point
# --------------------------------------------------------------------------

class Server:
    def __init__(self, run: Run, name: str, extra: list[str]):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.run, self.name = run, name
        self.proc, self.log = run.spawn(name, [
            "-m", "mlx_sharding_tpu.server.openai_api",
            "--model", str(run.ckpt), "--max-seq", "4096",
            "--port", str(self.port), *extra,
        ])

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.port}{path}"

    def get(self, path: str) -> str:
        with urllib.request.urlopen(self.url(path), timeout=30) as r:
            need(r.status == 200, f"{self.name} {path}: HTTP {r.status}")
            return r.read().decode()

    def post(self, path: str, body: dict):
        req = urllib.request.Request(
            self.url(path), json.dumps(body).encode(),
            {"Content-Type": "application/json"},
        )
        return urllib.request.urlopen(req, timeout=self.run.left())

    def wait_healthy(self) -> None:
        while True:
            need(self.proc.poll() is None,
                 f"{self.name} exited during start-up\n{tail(self.log)}")
            need(self.run.left() > 1, f"{self.name}: out of time starting")
            try:
                self.get("/health")
                return
            except (OSError, PhaseFailed):
                time.sleep(1.0)

    def complete(self, prompt: str, max_tokens: int, **kw) -> dict:
        with self.post("/v1/completions", dict(
            prompt=prompt, max_tokens=max_tokens, temperature=0.0, **kw
        )) as r:
            need(r.status == 200, f"{self.name} completion: HTTP {r.status}")
            body = json.loads(r.read())
        got = body["usage"]["completion_tokens"]
        need(got == max_tokens,
             f"{self.name}: asked for {max_tokens} tokens, got {got}")
        return body

    def stop(self) -> None:
        """SIGTERM, then a clean exit with code 0 — which also releases the
        chip for the next child."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=min(120, self.run.left()))
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{self.name} did not stop on SIGTERM") from None
        need(rc == 0, f"{self.name} exited with code {rc}\n{tail(self.log)}")


def metric(text: str, name: str):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    return None


def phase_server(run: Run) -> None:
    v = run.cfg["vocab_size"]
    t0 = time.monotonic()
    srv = Server(run, "server-batched",
                 ["--concurrent", "8", "--paged-pool", "128"])
    srv.wait_healthy()
    say(f"server up (checkpoint load + placement) in {time.monotonic() - t0:.1f} s"
        " smoke timing")

    # one non-streamed completion, with logprobs: the comparison's left side
    t0 = time.monotonic()
    short = words(run.seed, 0, 64, v)
    a = srv.complete(short, 32, logprobs=10)
    say(f"non-streamed completion: 64 in, 32 out, {time.monotonic() - t0:.1f} s"
        " smoke timing (cold compiles)")

    # one streamed chat completion
    t0 = time.monotonic()
    with srv.post("/v1/chat/completions", dict(
        messages=[{"role": "user", "content": words(run.seed, 1, 100, v)}],
        max_tokens=48, temperature=0.0, stream=True,
    )) as r:
        need(r.status == 200, f"streamed chat: HTTP {r.status}")
        events = [ln[6:].strip() for ln in r.read().decode().splitlines()
                  if ln.startswith("data: ")]
    need(events and events[-1] == "[DONE]", "SSE stream did not end in [DONE]")
    chunks = [json.loads(e) for e in events[:-1]]
    need(all("error" not in c for c in chunks), "SSE stream carried an error")
    text = "".join(c["choices"][0].get("delta", {}).get("content") or ""
                   for c in chunks)
    need(len(text.split()) == 48, f"streamed {len(text.split())} tokens, not 48")
    need(chunks[-1]["choices"][0]["finish_reason"] == "length",
         "stream did not finish on length")
    say(f"streamed chat completion: 48 tokens, terminated, "
        f"{time.monotonic() - t0:.1f} s smoke timing")

    # eight at once: prompts of 64–2048 tokens, 64–256 out — chunked
    # prefill, slot interleaving and decode across page boundaries
    t0 = time.monotonic()
    mix = [(64, 256), (2048, 64), (300, 128), (1000, 96),
           (128, 200), (1500, 64), (700, 160), (256, 256)]
    prompts = [words(run.seed, 10 + j, n_in, v)
               for j, (n_in, _) in enumerate(mix)]
    with ThreadPoolExecutor(max_workers=len(mix)) as pool:
        jobs = [pool.submit(srv.complete, prompt, n_out, logprobs=10)
                for prompt, (_, n_out) in zip(prompts, mix)]
        bodies = [job.result() for job in jobs]
    long_prompt, b = prompts[6], bodies[6]  # 700 in (three chunks), 160 out
    say(f"eight concurrent completions in {time.monotonic() - t0:.1f} s"
        " smoke timing")

    metrics = srv.get("/metrics")
    for _ in range(20):  # a finished slot frees its pages on the next tick
        if metric(metrics, "mst_kv_pool_pages_in_use") == 0:
            break
        time.sleep(0.5)
        metrics = srv.get("/metrics")
    need(metric(metrics, "mst_paged_attention_ragged") == 1,
         "/metrics does not report the ragged paged-attention path")
    # every ragged attention call of the served programs took the kernel
    # (the rehearsal has no chip: there every one takes the XLA path)
    took = {path: metric(metrics, f'mst_paged_attention_dispatch_total{{path="{path}"}}')
            for path in ("kernel", "xla")}
    need(took["xla" if run.rehearse else "kernel"]
         and not took["kernel" if run.rehearse else "xla"],
         f"/metrics: ragged paged attention dispatched {took}")
    need(metric(metrics, "mst_requests_failed_total") == 0,
         "/metrics reports failed requests")
    need(not metric(metrics, "mst_preemptions_total"),
         "/metrics reports preemptions")
    need(metric(metrics, "mst_requests_total") == 10, "request count is off")
    need(metric(metrics, "mst_kv_pool_pages") == 128
         and metric(metrics, "mst_kv_pool_pages_in_use") == 0,
         "page pool is not 128 pages, all free again")
    health = json.loads(srv.get("/health"))
    need(health.get("status") == "ok", f"/health says {health}")
    srv.stop()
    say("ragged path in /metrics, no failures, pool drained, /health ok, "
        "clean exit 0")

    # The same server without --concurrent serves through generate.Generator
    # and the dense cache: the plain single-request path. Positions of the
    # batched server's greedy runs are re-asked here with the context forced
    # to be the same (the prompt plus the batched server's own tokens), so
    # an argmax that rounding flipped cannot derail the comparison.
    t0 = time.monotonic()
    ref = Server(run, "server-single", [])
    ref.wait_healthy()
    worst, n = 0.0, 0
    for label, prompt, body, positions in (
        ("64-token prompt", short, a, (0, 1, 9, 17, 31)),
        ("700-token prompt", long_prompt, b, (0, 16, 159)),
    ):
        lp = body["choices"][0]["logprobs"]
        for i in positions:
            forced = " ".join([prompt] + [f"w{t}" for t in lp["tokens"][:i]])
            got = ref.complete(forced, 1, logprobs=10)
            worst = max(worst, _compare_top10(
                f"{label}, generated position {i}",
                lp["top_logprobs"][i],
                got["choices"][0]["logprobs"]["top_logprobs"][0]))
            n += 1
    # and once unforced, without logprobs: the decode program the CLI shares
    ref.complete(short, 20)
    ref.stop()
    say(f"batched server vs single-request path: {n} positions, worst "
        f"logprob difference {worst:.4f} (tol {LOGPROB_ATOL}), "
        f"{time.monotonic() - t0:.1f} s smoke timing; "
        f"{cache_hits(ref.log)} persistent-cache hits in the second server")


def phase_cli(run: Run) -> None:
    t0 = time.monotonic()
    out, log = run.run("cli", [
        "-m", "mlx_sharding_tpu.cli.generate", "--model", str(run.ckpt),
        "--prompt", words(run.seed, 2, 32, run.cfg["vocab_size"]),
        "--max-tokens", "64",
    ])
    err = log.read_text(errors="replace")
    need("tokens-per-sec" in err and "TTFT" in err,
         "the CLI did not print its tok/s and TTFT lines")
    need(len(out.split()) == 64, f"the CLI printed {len(out.split())} tokens")
    hits = cache_hits(log)
    say(f"cli: 64 tokens, {hits} persistent-cache hits for programs the "
        f"single-request server compiled, {time.monotonic() - t0:.1f} s "
        "smoke timing")
    # tiny programs compile in under the cache's one-second floor
    need(hits > 0 or run.rehearse,
         "the CLI found nothing in the compile cache the server shares")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    run = Run(args.seed, args.rehearse)
    t_start = time.monotonic()
    ok = False

    def phase(name, fn):
        t0 = time.monotonic()
        say(f"phase {name} ...")
        result = fn()
        say(f"phase {name}: ok, {time.monotonic() - t0:.1f} s smoke timing")
        return result

    def checkpoint():
        write_checkpoint(run.ckpt, run.cfg, run.seed)
        size = sum(f.stat().st_size for f in run.ckpt.iterdir())
        say(f"wrote {size / 2**30:.2f} GiB to {run.ckpt}")

    # In either mode no model is built before a child has seen the device.
    try:
        if args.chips == 4:
            phase("pipeline pp=4 vs pp=1",
                  lambda: run.call("child_pipeline", str(run.ckpt)))
        else:
            phase("device + kernels", lambda: run.call("child_kernels"))
            phase("checkpoint", checkpoint)
            phase("server", lambda: phase_server(run))
            phase("cli", lambda: phase_cli(run))
        ok = True
    except PhaseFailed as e:
        say(f"FAILED: {e}")
    except Exception:  # the last line and the exit code still have to say so
        say("FAILED:\n" + traceback.format_exc())
        for log in sorted(run.logs.glob("*.log")):
            say(f"end of {log.name}:\n{tail(log, 25)}")
    finally:
        run.stop_all()
        if run.ckpt.exists():
            shutil.rmtree(run.ckpt)
    say(f"total {time.monotonic() - t_start:.1f} s smoke timing")
    print(json.dumps({"ok": ok, "device": run.device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
