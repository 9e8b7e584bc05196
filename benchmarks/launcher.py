"""The process that owns the chip(s): the program's own server, started
in-process with weights made on the device from ``--seed``.

What it replaces, and why: ``mlx_sharding_tpu.loading.load_model`` — for
this process only — with a function that builds the model from the
directory's ``config.json`` exactly as the loader does and returns a
parameter tree generated from the seed (``benchmarks.weights``) instead of
one read from ``*.safetensors``. A 10 GB checkpoint written and read in
every run would be most of every check, and the loader's resident tree plus
the engine's stacked copy would not fit one 16 GB chip. Provider, engine,
batcher, pool, HTTP and SSE are the program's, untouched: after the patch
this file calls ``server.openai_api.main([...])`` with the configuration's
flags.

Beside the server it runs a small control endpoint for the runner
(``benchmarks/run.py``, which never imports JAX): the device as JAX reports
it, peak memory, the count of XLA compilations so far, the plain
reference's log-probabilities for a token sequence (the correctness check),
and start/stop of ``jax.profiler`` (only this process can trace its chip).
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.config import family, program_config, published_config  # noqa: E402

#: exit code when JAX finds another platform or device count than the cell's
EXIT_WRONG_DEVICE = 3


def write_model_dir(out: Path, program_cfg: dict) -> None:
    """``config.json`` and a word-level tokenizer whose vocabulary is the id
    range (id i is the word ``w<i>``, 0 the unknown word): any id the head
    can emit decodes, ``"w5 w9"`` encodes to exactly [5, 9], and there is no
    EOS to end a random-weight generation early. (The trick is
    ``chip_smoke.py``'s.) No weights are written."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    out.mkdir(parents=True, exist_ok=True)
    (out / "config.json").write_text(json.dumps(program_cfg, indent=1))
    v = program_cfg["vocab_size"]
    vocab = {"<unk>": 0, **{f"w{i}": i for i in range(1, v)}}
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(str(out / "tokenizer.json"))
    (out / "tokenizer_config.json").write_text(
        json.dumps({"tokenizer_class": "PreTrainedTokenizerFast"})
    )


class CompileCounter:
    """Counts XLA backend compilations through ``jax.monitoring``: one event
    per program that was not served from a cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration


class Control:
    """State behind the control endpoint."""

    def __init__(self, config: dict, seed: int, compiles: CompileCounter,
                 profile_dir: Path):
        self.config, self.seed, self.compiles = config, seed, compiles
        self.profile_dir = profile_dir
        self.lock = threading.Lock()  # one reference pass / profiler op at a time

    def device(self) -> dict:
        import jax

        devs = jax.devices()
        peaks = []
        for d in devs:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return {
            "platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks),
            "memory_peak_bytes_per_device": peaks,
        }

    def reference(self, body: dict) -> dict:
        with self.lock:
            top_i, top_v, at = family(self.config).forward(
                published_config(self.config),
                self.config["bench"]["weight_format"], self.seed, body["ids"],
                body["rows"], body["ids_wanted"], fault=body.get("fault"),
                pad_to=body.get("pad_to", 0),
            )
        return {"top_ids": top_i.tolist(), "top_logprobs": top_v.tolist(),
                "logprobs_at_wanted": at.tolist()}

    def profile(self, action: str) -> dict:
        import jax

        with self.lock:
            if action == "start":
                # no Python call tracing: it slows the scheduler thread
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(str(self.profile_dir),
                                         profiler_options=opts)
            else:
                jax.profiler.stop_trace()
        return {"dir": str(self.profile_dir)}


def make_handler(ctl: Control):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):  # quiet
            pass

        def _send(self, code: int, payload: dict) -> None:
            data = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path == "/device":
                self._send(200, ctl.device())
            elif self.path == "/compiles":
                self._send(200, {"count": ctl.compiles.count,
                                 "seconds": ctl.compiles.seconds})
            else:
                self._send(404, {"error": "unknown path"})

        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            body = json.loads(self.rfile.read(n) or b"{}")
            try:
                if self.path == "/reference":
                    self._send(200, ctl.reference(body))
                elif self.path in ("/profile/start", "/profile/stop"):
                    self._send(200, ctl.profile(self.path.rsplit("/", 1)[1]))
                else:
                    self._send(404, {"error": "unknown path"})
            except Exception as e:  # noqa: BLE001 — report, keep serving
                import traceback

                traceback.print_exc()
                self._send(500, {"error": f"{type(e).__name__}: {e}"})

    return Handler


def patch_loader(config: dict, seed: int) -> None:
    import jax.numpy as jnp

    import mlx_sharding_tpu.loading as loading
    from mlx_sharding_tpu.models import build_model

    published = published_config(config)
    fmt = config["bench"]["weight_format"]

    def load_model(path_or_repo, start_layer=None, end_layer=None,
                   dtype=jnp.bfloat16, keep_quantized=False):
        if start_layer is not None or end_layer is not None:
            raise ValueError("the seeded loader serves the whole model only")
        if keep_quantized != (fmt == "q4"):
            raise ValueError(
                "--keep-quantized and the configuration's weight_format disagree"
            )
        cfg_dict = loading.load_config(loading.get_model_path(path_or_repo))
        model, _cfg = build_model(cfg_dict)
        model.compute_dtype = dtype  # as loading.load_model sets it
        return model, family(config).program_params(published, fmt, seed)

    loading.load_model = load_model


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True, help="configuration file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--control-port", type=int, required=True)
    ap.add_argument("--work", required=True, help="scratch directory")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    config = json.loads(Path(args.config).read_text())
    work = Path(args.work)

    # the program's rule for the compile cache (JAX_COMPILATION_CACHE_DIR if
    # set, else <checkout>/.jax_cache), and every program cached however
    # quickly it compiled, so that a warm run compiles nothing
    from mlx_sharding_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    want_platform = config["bench"].get("platform", "tpu")
    chips = config["bench"]["chips"]
    devs = jax.devices()
    if devs[0].platform != want_platform or len(devs) != chips:
        print(
            f"launcher: the cell wants {chips} {want_platform} "
            f"device(s); JAX reports {len(devs)} x {devs[0].platform}",
            file=sys.stderr,
        )
        return EXIT_WRONG_DEVICE

    compiles = CompileCounter()
    compiles.install()
    model_dir = work / "model"
    write_model_dir(model_dir, program_config(config))
    patch_loader(config, args.seed)

    profile_dir = work / "profile"
    shutil.rmtree(profile_dir, ignore_errors=True)  # one run's trace at a time
    ctl = Control(config, args.seed, compiles, profile_dir)
    control = ThreadingHTTPServer(("127.0.0.1", args.control_port), make_handler(ctl))
    threading.Thread(target=control.serve_forever, name="bench-control",
                     daemon=True).start()

    from mlx_sharding_tpu.server import openai_api

    flags = [str(f) for f in config["bench"]["server_flags"]]
    flags += ["--trace", "on", "--trace-profile"] if args.trace else ["--trace", "off"]
    try:
        openai_api.main(["--model", str(model_dir), "--port", str(args.port),
                         "--log-level", "INFO", *flags])
    finally:
        control.shutdown()
        control.server_close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
