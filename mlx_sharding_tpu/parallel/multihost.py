"""Multi-host serving: rank-0 driver + worker protocol over jax.distributed.

This is the reference's per-machine deployment reborn (one shard process per
machine: /root/reference/shard/main.py:4-14, driven over gRPC from the
primary at /root/reference/generate.py:17, shard/utils.py:162-164) on the
TPU-native substrate. Differences, by design:

- The reference ships ACTIVATIONS over the wire every token (serialize →
  TCP → deserialize per stage, SURVEY §3.5). Here the model math runs as
  multi-controller SPMD over one global mesh: every process executes the
  SAME jitted step, and activations cross host boundaries inside XLA
  collectives (ICI/DCN), never through Python.
- The only thing rank 0 broadcasts is CONTROL: request admission (prompt
  tokens + sampler params) and per-token step ops. Sampling is
  replicated-deterministic — same PRNG key chain on every process — so
  sampled tokens never need to be sent anywhere; every process computes
  them identically.
- Rank 0 is the reference's "primary": it owns the tokenizer, the HTTP
  server and the decode loop. Ranks > 0 run :func:`serve_worker`, the
  equivalent of `mlx-sharding-server` (shard/main.py): load the same
  checkpoint, build the same engine, mirror the step sequence.

Wire format: fixed-shape int32/float32 buffers through
``multihost_utils.broadcast_one_to_all`` (a tiny psum over the global mesh),
so the control plane itself is just another XLA collective — no sockets, no
serde code, no message framing.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.sample import (
    init_recent_tokens,
    make_sampler_params,
    sampler_params_host,
)
from mlx_sharding_tpu.testing.faults import inject
from mlx_sharding_tpu.utils.clock import MONOTONIC, Clock


class WorkerTimeoutError(RuntimeError):
    """A control-plane collective did not complete in time — a peer rank is
    dead or wedged. The plane is marked down: every later exchange fails
    fast instead of stranding another thread in the collective, so rank 0
    keeps answering (5xx + degraded /health) and can be restarted."""

# control ops
OP_IDLE = 0
OP_REQUEST = 1
OP_DECODE = 2
OP_STOP_REQUEST = 3
OP_SHUTDOWN = 4
# continuous-batching ops (the batched protocol below)
OP_B_ASSIGN = 10
OP_B_PREFILL = 11
OP_B_DECODE = 12
OP_B_CANCEL = 13
OP_B_FAIL = 14

# matches the scheduler's per-slot width (scheduler.py make_sampler_params
# min_bias_slots=512) and the HTTP-layer validation cap, so a request that
# works single-host never fails multi-host (covers OpenAI's documented 300)
_BIAS_SLOTS = 512


class _Shutdown(Exception):
    pass


# Shared wire encoding — the single-stream protocol (_request_msg /
# _start_request) and the batched one (_assign_msg / _req_from_msg) must
# never drift apart on these.

def _pack_seed(seed: int) -> tuple[int, int]:
    """62-bit seed into two int32-safe halves — a full user seed round-trips
    so multi-host reproduces the single-host stream for the same request."""
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def _unpack_seed(lo, hi) -> int:
    return int(lo) | (int(hi) << 31)


def _pack_bias(logit_bias) -> tuple[np.ndarray, np.ndarray, int]:
    bias_idx = np.zeros((_BIAS_SLOTS,), np.int32)
    bias_val = np.zeros((_BIAS_SLOTS,), np.float32)
    n_bias = 0
    if logit_bias:
        if len(logit_bias) > _BIAS_SLOTS:
            # silent truncation would make multi-host output diverge from
            # the same request served single-host
            raise ValueError(
                f"logit_bias with {len(logit_bias)} entries exceeds the "
                f"multi-host control-plane width {_BIAS_SLOTS}"
            )
        items = list(logit_bias.items())
        n_bias = len(items)
        bias_idx[:n_bias] = [int(k) for k, _ in items]
        bias_val[:n_bias] = [float(v) for _, v in items]
    return bias_idx, bias_val, n_bias


def _unpack_bias(bias_idx, bias_val, n_bias: int):
    return {
        int(i): float(v)
        for i, v in zip(bias_idx[:n_bias], bias_val[:n_bias])
    } or None


class ControlPlane:
    """Fixed-shape broadcast buffers; rank 0 publishes, all ranks receive the
    same pytree (broadcast_one_to_all ignores non-zero ranks' inputs).

    Liveness (rank 0 only): a collective completes only when EVERY rank
    arrives, so a SIGKILLed worker would block rank 0 in the broadcast
    forever, invisible to /health. Rank 0 therefore runs each exchange on a
    dedicated thread and bounds the wait (``MST_MULTIHOST_TIMEOUT_S``,
    default 600s — generous enough for a worker's slowest compile between
    two exchanges; 0 disables). On timeout the plane is marked ``dead``:
    the in-flight request errors to its client, later exchanges fail fast,
    and /health flips to degraded. Workers keep unbounded waits — an idle
    deployment broadcasts nothing, and their liveness is rank 0's concern."""

    header_size = 8

    def __init__(self, max_prompt: int, timeout_s: Optional[float] = None,
                 clock: Clock = MONOTONIC):
        self.max_prompt = max_prompt
        self.clock = clock  # liveness stamps read the injectable source
        if timeout_s is None:
            try:
                timeout_s = float(os.environ.get("MST_MULTIHOST_TIMEOUT_S", "600"))
            except ValueError:
                timeout_s = 600.0
        if jax.process_index() != 0 or timeout_s <= 0:
            timeout_s = None  # workers (and 0 = disabled) wait unbounded
        self.timeout_s = timeout_s
        self.dead = False
        self.last_ok: Optional[float] = None  # monotonic stamp of the last
        # completed collective — proof every rank was alive at that moment
        self._thread = None  # lazy daemon worker (timed exchanges only)
        from mlx_sharding_tpu.analysis.runtime import make_lock

        # serializes the timed path: two callers racing the lazy init would
        # spawn duplicate broadcast threads, and interleaved _work/_out
        # queue traffic could hand one caller the other's reply
        self._lock = make_lock("ControlPlane._lock")

    @staticmethod
    def _broadcast(buf):
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(buf)

    def _zeros(self):
        return {
            "header": np.zeros((self.header_size,), np.int32),
            "floats": np.zeros((4,), np.float32),
            "tokens": np.zeros((self.max_prompt,), np.int32),
            "bias_idx": np.zeros((_BIAS_SLOTS,), np.int32),
            "bias_val": np.zeros((_BIAS_SLOTS,), np.float32),
        }

    def exchange(self, msg: Optional[dict] = None) -> dict:
        """Collective: rank 0 passes ``msg`` (padded in), workers pass None.
        Everyone gets rank 0's message back as host numpy. Raises
        :class:`WorkerTimeoutError` (rank 0) when a peer doesn't show up
        within the liveness budget, and instantly once the plane is dead."""
        try:
            # fault harness: a raise here simulates a collective whose peer
            # never arrives (faults.DropExchange) — same conclusion as a
            # timeout, detected instantly
            inject("multihost.exchange")
        except Exception as e:  # noqa: BLE001 — any injected failure means
            # the plane can no longer be trusted; normalize like a timeout
            with self._lock:  # exchange's dead-check reads under this lock
                self.dead = True
            raise WorkerTimeoutError(
                "multi-host collective dropped (injected fault) — marking "
                "the control plane down (restart the deployment)"
            ) from e
        buf = self._zeros()
        if msg is not None:
            for k, v in msg.items():
                arr = np.asarray(v).reshape(-1)
                buf[k][: arr.size] = arr
        if self.timeout_s is None:
            out = self._broadcast(buf)
        else:
            # the whole timed path holds the lock: dead-check, lazy init,
            # submit and reply must be one atomic unit or a concurrent
            # caller could collect this caller's broadcast result
            with self._lock:
                if self.dead:
                    raise WorkerTimeoutError(
                        "multi-host control plane is down (a peer rank "
                        "previously failed to respond) — restart the deployment"
                    )
                import queue as _q

                if self._thread is None:
                    # one DAEMON thread issuing collectives in program order:
                    # a timed-out broadcast stays blocked in it forever, and
                    # a daemon can be abandoned at interpreter exit — a
                    # ThreadPoolExecutor worker would be joined by the
                    # concurrent.futures atexit hook and wedge process
                    # shutdown
                    self._work: _q.Queue = _q.Queue()
                    self._out: _q.Queue = _q.Queue()

                    def run():
                        while True:
                            b = self._work.get()
                            try:
                                self._out.put(("ok", self._broadcast(b)))
                            except BaseException as e:  # noqa: BLE001
                                self._out.put(("err", e))

                    import threading

                    self._thread = threading.Thread(
                        target=run, name="mst-ctrl", daemon=True
                    )
                    self._thread.start()
                self._work.put(buf)
                try:
                    kind, val = self._out.get(timeout=self.timeout_s)
                except _q.Empty:
                    self.dead = True  # the broadcast thread stays stuck in
                    # the collective; being a daemon, it is abandoned, never
                    # joined
                    raise WorkerTimeoutError(
                        f"multi-host collective did not complete within "
                        f"{self.timeout_s:.0f}s — a worker rank is dead or "
                        "wedged; failing the request and marking the control "
                        "plane down (restart the deployment)"
                    ) from None
                if kind == "err":
                    # the distributed runtime itself noticed the dead peer
                    # and errored the collective — same conclusion, better
                    # latency. Normalized to WorkerTimeoutError (cause
                    # chained) so every dead-plane swallow site (STOP /
                    # SHUTDOWN / batcher close) behaves identically on both
                    # detection paths.
                    self.dead = True
                    raise WorkerTimeoutError(
                        "multi-host collective failed — the distributed "
                        "runtime reported a dead or unreachable peer rank; "
                        "marking the control plane down (restart the "
                        "deployment)"
                    ) from val
                out = val
        self.last_ok = self.clock()
        return {k: np.asarray(v) for k, v in out.items()}


def _request_msg(prompt, temperature, top_p, repetition_penalty,
                 repetition_context_size, logit_bias, seed, max_tokens):
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    bias_idx, bias_val, n_bias = _pack_bias(logit_bias)
    seed_lo, seed_hi = _pack_seed(seed)
    return {
        "header": np.asarray(
            [OP_REQUEST, prompt.size, max_tokens, seed_lo,
             repetition_context_size,
             0 if repetition_penalty is None else 1, n_bias, seed_hi],
            np.int32,
        ),
        "floats": np.asarray(
            # None-ness rides ONLY in the has_pen header flag: `or 1.0`
            # would mangle an explicit penalty of 0.0 on the wire
            [temperature, top_p,
             1.0 if repetition_penalty is None else repetition_penalty, 0.0],
            np.float32,
        ),
        "tokens": prompt,
        "bias_idx": bias_idx,
        "bias_val": bias_val,
    }


def _start_request(engine, msg):
    """Identical on every rank: prefill the broadcast prompt and sample the
    first token. Returns the rolling decode state."""
    hdr = msg["header"]
    n_prompt = int(hdr[1])
    seed = _unpack_seed(hdr[3], hdr[7])
    rep_ctx = int(hdr[4])
    n_bias = int(hdr[6])
    temperature, top_p, rep_pen = (float(x) for x in msg["floats"][:3])
    bias = _unpack_bias(msg["bias_idx"], msg["bias_val"], n_bias)
    sp = make_sampler_params(
        temperature, top_p, rep_pen if hdr[5] else None, bias
    )
    prompt = msg["tokens"][:n_prompt]

    M, B = engine.microbatches, engine.batch
    arr = np.broadcast_to(prompt.reshape(1, 1, -1), (M, B, n_prompt))
    cache = engine.init_cache()

    # every host-built input must be explicitly committed as a REPLICATED
    # global array: under multi-controller JAX, mixing plain host arrays
    # with global-mesh arrays in one jit is not well-defined
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mlx_sharding_tpu.parallel.pipeline import put_global

    rep = NamedSharding(engine.mesh, P())
    # put_global, not device_put: every rank builds the same value from the
    # broadcast request, so device_put's assert-equal broadcast is overhead
    put = lambda x: put_global(x, rep)  # noqa: E731
    recent = put(init_recent_tokens(M * B, rep_ctx, arr.reshape(M * B, -1)))
    key = put(jax.random.PRNGKey(seed))
    sp = jax.tree.map(put, sp)

    c = engine.prefill_chunk
    logits = None
    for start in range(0, n_prompt, c):
        chunk = arr[..., start : start + c]
        n_valid = chunk.shape[-1]
        if n_valid < c:
            chunk = np.pad(chunk, ((0, 0), (0, 0), (0, c - n_valid)))
        logits, cache = engine._prefill(
            engine.layer_params, engine.layer_masks, engine.vocab_parts,
            engine.shared_params, put(jnp.asarray(chunk)), cache,
            put(jnp.asarray(n_valid, jnp.int32)),
        )
    tok, logprobs, recent, key = engine._sample(logits, recent, key, sp)
    return dict(cache=cache, recent=recent, key=key, sp=sp, tok=tok,
                logprobs=logprobs, _put=put)


def _decode_step(engine, state):
    one = state["_put"](jnp.asarray(1, jnp.int32))
    tok, logprobs, cache, recent, key = engine._decode(
        engine.layer_params, engine.layer_masks, engine.vocab_parts,
        engine.shared_params, state["tok"][..., None], state["cache"],
        state["recent"], state["key"], state["sp"], one,
    )
    state.update(cache=cache, recent=recent, key=key, tok=tok, logprobs=logprobs)
    return state


class MultiHostPipeline:
    """Rank-0 driver with the ``generate_step`` contract. Each yielded token
    was computed redundantly by every process; the broadcasts only carry
    \"take another step\" (one tiny collective per token — the reference pays
    a full activation serialize/RPC per STAGE per token here)."""

    concurrent = False  # requests serialize through the server's gen lock

    def __init__(self, engine):
        self.engine = engine
        self.ctrl = ControlPlane(max_prompt=engine.max_seq)

    def generate_step(
        self,
        prompt_tokens,
        *,
        temperature: float = 0.0,
        top_p: float = 1.0,
        repetition_penalty: Optional[float] = None,
        repetition_context_size: int = 20,
        logit_bias: Optional[dict[int, float]] = None,
        seed: Optional[int] = None,
        max_tokens: int = 256,
        want_logprobs: bool = False,  # full (B, V) rows are always yielded
    ):
        import time as _time

        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if prompt.size + max_tokens > self.engine.max_seq:
            raise ValueError(
                f"prompt ({prompt.size}) + max_tokens ({max_tokens}) exceeds "
                f"KV capacity {self.engine.max_seq}"
            )
        if seed is not None and not 0 <= int(seed) < (1 << 62):
            raise ValueError("seed must fit in 62 bits for multi-host serving")
        msg = _request_msg(
            prompt, temperature, top_p, repetition_penalty,
            repetition_context_size, logit_bias,
            (int(_time.time_ns()) if seed is None else int(seed)),
            max_tokens,
        )
        self.ctrl.exchange(msg)
        # everything after the OP_REQUEST broadcast sits inside the try:
        # if prefill raises on rank 0, the finally still broadcasts STOP so
        # workers leave the request loop instead of hanging the collective
        try:
            state = _start_request(self.engine, msg)
            n = 0
            while True:
                yield int(np.asarray(state["tok"]).reshape(-1)[0]), state["logprobs"]
                n += 1
                if n >= max_tokens:
                    break
                self.ctrl.exchange({"header": np.asarray([OP_DECODE], np.int32)})
                state = _decode_step(self.engine, state)
        finally:
            # exactly one STOP per request, whether it ran to max_tokens or
            # the consumer closed early (stop sequence / disconnect). A dead
            # control plane (worker timeout mid-request) must not let this
            # raise over the original error — there is no one left to resync.
            try:
                self.ctrl.exchange(
                    {"header": np.asarray([OP_STOP_REQUEST], np.int32)}
                )
            except WorkerTimeoutError:
                pass

    def shutdown(self):
        try:
            self.ctrl.exchange({"header": np.asarray([OP_SHUTDOWN], np.int32)})
        except WorkerTimeoutError:
            pass  # nobody is listening; the plane is already down

    close = shutdown


def _drain_to_stop(ctrl) -> bool:
    """After a local step failure, consume broadcasts until rank 0's
    per-request STOP (its generator ``finally`` always sends exactly one) so
    the collective protocol stays aligned. Returns True on OP_SHUTDOWN."""
    while True:
        step = ctrl.exchange()
        op = int(step["header"][0])
        if op == OP_STOP_REQUEST:
            return False
        if op == OP_SHUTDOWN:
            return True
        if op != OP_DECODE:
            raise RuntimeError(f"worker protocol desync while draining: op {op}")


# --------------------------------------------------------------------------
# Continuous batching over the multi-host control plane.
#
# The scheduler's HOST decisions (which request gets which slot, when a
# prefill chunk runs, when a decode block runs, when a consumer cancels) are
# the only non-deterministic inputs — everything downstream of the op stream
# is deterministic: page allocation pops a mirrored free list, max_tokens
# finishes count mirrored emit loops, sampling is replicated PRNG. So rank 0
# runs the real ContinuousBatcher and broadcasts one tiny op message before
# each DEVICE op; every worker applies the same op to an identical mirror
# batcher and stays in lockstep. (The reference cannot express any of this —
# its serving is one request at a time over RPC-chained shards.)


class BatchControlPlane(ControlPlane):
    """ControlPlane with room for the batched ops' header fields."""

    header_size = 12


def _assign_msg(req, slot: int) -> dict:
    """OP_B_ASSIGN message: the request verbatim, so a worker rebuilds an
    identical _Request (sampler params, seed chain, page need)."""
    prompt = np.asarray(req.prompt, np.int32).reshape(-1)
    bias_idx, bias_val, n_bias = _pack_bias(req.logit_bias)
    seed_lo, seed_hi = _pack_seed(int(req.seed))
    return {
        "header": np.asarray(
            [OP_B_ASSIGN, slot, prompt.size, req.max_tokens,
             seed_lo, seed_hi, req.rep_context,
             0 if req.repetition_penalty is None else 1, n_bias,
             1 if req.want_logprobs else 0, 0, 0],
            np.int32,
        ),
        "floats": np.asarray(
            # see _request_msg: None-ness rides only in the has_pen flag
            [req.temperature, req.top_p,
             1.0 if req.repetition_penalty is None
             else req.repetition_penalty, 0.0],
            np.float32,
        ),
        "tokens": prompt,
        "bias_idx": bias_idx,
        "bias_val": bias_val,
    }


class _DiscardQueue:
    """Worker-side _Request.out: tokens are computed redundantly on every
    rank; only rank 0 has consumers. Dropping keeps device rows from
    accumulating."""

    def put(self, item):
        pass


def _req_from_msg(msg):
    from mlx_sharding_tpu.scheduler import _Request

    hdr = msg["header"]
    n_prompt, max_tokens = int(hdr[2]), int(hdr[3])
    seed = _unpack_seed(hdr[4], hdr[5])
    rep_ctx, has_pen, n_bias = int(hdr[6]), int(hdr[7]), int(hdr[8])
    temperature, top_p, rep_pen = (float(x) for x in msg["floats"][:3])
    bias = _unpack_bias(msg["bias_idx"], msg["bias_val"], n_bias)
    return _Request(
        prompt=np.asarray(msg["tokens"][:n_prompt], np.int32),
        # the row a slot claim writes: numpy, as wide as the batch's
        sp=sampler_params_host(
            temperature, top_p, rep_pen if has_pen else None, bias,
            slots=_BIAS_SLOTS,
        ),
        seed=seed,
        max_tokens=max_tokens,
        rep_context=rep_ctx,
        want_logprobs=bool(hdr[9]),
        out=_DiscardQueue(),
        temperature=temperature,
        top_p=top_p,
        repetition_penalty=rep_pen if has_pen else None,
        logit_bias=bias,
    )


def _make_multihost_batcher():
    """Deferred subclassing keeps scheduler import out of this module's
    import time (the class is only needed on serving ranks)."""
    from mlx_sharding_tpu.scheduler import ContinuousBatcher

    class MultiHostBatcher(ContinuousBatcher):
        """Rank-0 continuous batcher that broadcasts each device op before
        applying it, so `serve_worker_batched` mirrors stay in lockstep.
        `--concurrent N` under `--coordinator` builds this."""

        def __init__(self, engine, **kw):
            super().__init__(engine, **kw)
            self.ctrl = BatchControlPlane(max_prompt=engine.max_seq)
            self._shut = False

        def generate_step(self, prompt_tokens, *, seed=None, **kw):
            if seed is not None and not 0 <= int(seed) < (1 << 62):
                raise ValueError(
                    "seed must fit in 62 bits for multi-host serving"
                )
            return super().generate_step(prompt_tokens, seed=seed, **kw)

        def _bcast(self, *header):
            self.ctrl.exchange({"header": np.asarray(header, np.int32)})

        def _assign_slot(self, req, slot):
            self.ctrl.exchange(_assign_msg(req, slot))
            super()._assign_slot(req, slot)

        def _prefill_one_chunk(self, req):
            self._bcast(OP_B_PREFILL, req.slot)
            super()._prefill_one_chunk(req)

        def _decode_once(self):
            self._bcast(OP_B_DECODE)
            super()._decode_once()

        def _reap_cancelled(self):
            # cancellation is the one finish the workers cannot derive
            # (max_tokens finishes they count themselves)
            for req in list(self._slots):
                if req is not None and req.cancelled:
                    self._bcast(OP_B_CANCEL, req.slot)
                    self._finish(req)

        def _fail_all(self, exc):
            import logging

            try:
                self._bcast(OP_B_FAIL)
            except Exception:
                logging.getLogger(__name__).exception(
                    "failed to broadcast scheduler failure"
                )
            super()._fail_all(exc)

        def close(self):
            super().close()  # joins the scheduler thread first: no
            # broadcast can race the shutdown one
            if self._thread is not None and self._thread.is_alive():
                # join timed out (e.g. mid-compile tick): the scheduler
                # thread may still broadcast ops — a SHUTDOWN from here
                # would interleave with them and strand a worker collective.
                # Skip it; process teardown is the backstop.
                return
            if not self._shut:
                self._shut = True  # workers exit on the first SHUTDOWN; a
                # second broadcast would hang awaiting departed peers
                try:
                    self._bcast(OP_SHUTDOWN)
                except WorkerTimeoutError:
                    pass  # plane already down; nothing to shut down

        shutdown = close

    return MultiHostBatcher


def make_multihost_batcher(engine, **kw):
    """Build the rank-0 batcher for multi-host continuous batching."""
    return _make_multihost_batcher()(engine, **kw)


def serve_worker_batched(engine, *, decode_block: int = 8,
                         repetition_window: int = 64,
                         prefix_cache: bool = False) -> None:
    """Rank>0 loop for multi-host continuous batching: apply rank 0's op
    stream to a mirror ContinuousBatcher. ``decode_block`` and
    ``prefix_cache`` must match rank 0's (the block sets the scanned
    program length; the cache changes the page-allocation sequence).

    Prefix caching mirrors deterministically: every index mutation lives
    inside a mirrored op — registration in OP_B_PREFILL, eviction +
    move-to-end during OP_B_ASSIGN, releases in the counted max_tokens
    finishes and OP_B_CANCEL — and rank 0's _fits polls are read-only, so
    identical op streams yield identical page tables on every rank.

    Failure discipline matches :func:`serve_worker`: device-op failures are
    deterministic, so rank 0 hits the same error, fails its consumers and
    broadcasts OP_B_FAIL — which resets this mirror too. An op code outside
    the protocol is a desync and raises."""
    import logging

    from mlx_sharding_tpu.scheduler import ContinuousBatcher

    logger = logging.getLogger(__name__)
    batcher = ContinuousBatcher(
        engine, decode_block=decode_block,
        repetition_window=repetition_window, prefix_cache=prefix_cache,
    )
    ctrl = BatchControlPlane(max_prompt=engine.max_seq)
    while True:
        msg = ctrl.exchange()
        hdr = msg["header"]
        op = int(hdr[0])
        if op == OP_SHUTDOWN:
            return
        if op == OP_B_FAIL:
            batcher._fail_all(RuntimeError("rank 0 scheduler failure"))
            continue
        if op not in (OP_B_ASSIGN, OP_B_PREFILL, OP_B_DECODE, OP_B_CANCEL):
            raise RuntimeError(f"worker protocol desync: unexpected op {op}")
        try:
            if op == OP_B_ASSIGN:
                batcher._assign_slot(_req_from_msg(msg), int(hdr[1]))
            elif op == OP_B_PREFILL:
                batcher._prefill_one_chunk(batcher._slots[int(hdr[1])])
            elif op == OP_B_DECODE:
                batcher._decode_once()
            else:  # OP_B_CANCEL
                req = batcher._slots[int(hdr[1])]
                if req is not None:
                    batcher._finish(req)
        except Exception:
            # deterministic failure: rank 0's identical op fails the same
            # way and OP_B_FAIL arrives next to reset this mirror
            logger.exception("worker batched op %d failed", op)


def serve_worker(engine) -> None:
    """Rank>0 main loop — the reference's shard-server process
    (shard/server/server.py:74-93) with the RPC surface replaced by the
    broadcast control plane. Blocks until rank 0 publishes OP_SHUTDOWN.

    Failure discipline: step failures are DETERMINISTIC (every rank runs the
    identical program on identical inputs), so when a local step raises this
    worker logs it and drains to the request's STOP instead of dying — rank 0
    raises the same error to the client and its ``finally`` broadcasts that
    STOP, leaving all ranks aligned for the next request. Rank-0-only host
    failures reach us as a bare STOP (handled at top level). Genuinely
    asymmetric failures cannot be resynced over a lockstep collective plane
    and surface as the loud desync RuntimeErrors."""
    import logging

    logger = logging.getLogger(__name__)
    ctrl = ControlPlane(max_prompt=engine.max_seq)
    while True:
        msg = ctrl.exchange()
        op = int(msg["header"][0])
        if op == OP_SHUTDOWN:
            return
        if op == OP_STOP_REQUEST:
            # rank 0's prefill failed after OP_REQUEST but before issuing
            # device work — its unconditional STOP resyncs us
            continue
        if op != OP_REQUEST:
            # a silent skip here would desync the collective protocol one
            # exchange at a time; fail loudly instead
            raise RuntimeError(f"worker protocol desync: unexpected op {op}")
        try:
            state = _start_request(engine, msg)
        except Exception:
            logger.exception("worker prefill failed; draining to STOP")
            if _drain_to_stop(ctrl):
                return
            continue
        while True:
            step = ctrl.exchange()
            op = int(step["header"][0])
            if op == OP_DECODE:
                try:
                    state = _decode_step(engine, state)
                except Exception:
                    logger.exception("worker decode failed; draining to STOP")
                    if _drain_to_stop(ctrl):
                        return
                    break
            elif op == OP_STOP_REQUEST:
                break
            elif op == OP_SHUTDOWN:
                return
            else:
                raise RuntimeError(
                    f"worker protocol desync: unexpected op {op} mid-request"
                )


# --------------------------------------------------------------------------
# Pod control plane: the symmetric (every-host-publishes) variant of the
# exchange, for the pod fleet subsystem (pod.py). Where ControlPlane is
# rank-0-publishes / workers-mirror (SPMD lockstep over ONE engine), the pod
# plane stitches N *independent* host fleets together: each host contributes
# its own fixed-shape buffer every pod tick and receives everyone's —
# heartbeats, weight-store registrations, autoscaler pressure, and chunked
# KV-block shipments all ride the same allgather.

# pod header slots (int32[POD_HEADER]): [seq, host_id, n_msgs, blob_used,
# epoch, flags, reserved, reserved]
POD_HEADER = 8


class PodControlPlane:
    """Fixed-shape symmetric exchange over ``process_allgather``.

    Every pod tick, every host calls :meth:`pod_exchange` with its header
    and message blob; the collective returns all hosts' buffers. Because a
    collective only completes when EVERY rank arrives, each host bounds the
    wait with the same timed daemon-thread discipline ControlPlane uses on
    rank 0 (``MST_POD_TIMEOUT_S``, default 60s) — a SIGKILLed peer turns
    into a :class:`WorkerTimeoutError` here, which the pod transport
    surfaces as "all peers dead" so the local fleet degrades to single-host
    serving instead of wedging its pod thread in the collective forever.

    The blob is an opaque uint8 payload (default 256 KiB,
    ``MST_POD_BLOB_BYTES``); framing/chunking is the transport's job
    (pod.CollectiveTransport), keeping this class a pure collective."""

    def __init__(self, blob_bytes: Optional[int] = None,
                 timeout_s: Optional[float] = None,
                 clock: Clock = MONOTONIC):
        if blob_bytes is None:
            try:
                blob_bytes = int(
                    os.environ.get("MST_POD_BLOB_BYTES", str(256 << 10))
                )
            except ValueError:
                blob_bytes = 256 << 10
        self.blob_bytes = max(4096, int(blob_bytes))
        self.clock = clock
        if timeout_s is None:
            try:
                timeout_s = float(os.environ.get("MST_POD_TIMEOUT_S", "60"))
            except ValueError:
                timeout_s = 60.0
        # unlike ControlPlane, EVERY host times its collectives: each host
        # drives its own pod tick loop, so each must detect dead peers
        self.timeout_s = timeout_s if timeout_s > 0 else None
        self.dead = False
        self.last_ok: Optional[float] = None
        self._thread = None
        from mlx_sharding_tpu.analysis.runtime import make_lock

        self._lock = make_lock("PodControlPlane._lock")

    @staticmethod
    def _allgather(buf):
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(buf)

    def pod_exchange(self, header: np.ndarray, blob: np.ndarray) -> tuple:
        """One pod tick's collective: contribute ``(header, blob)``, get
        back ``(headers, blobs)`` stacked over hosts (shape ``[n_hosts,
        ...]``). Raises :class:`WorkerTimeoutError` when a peer doesn't
        arrive within the budget, and instantly once the plane is dead —
        the same fail-fast contract as ControlPlane.exchange."""
        try:
            # same fault site as the SPMD plane: a dropped pod collective
            # and a dropped broadcast have identical liveness semantics
            inject("multihost.exchange", plane="pod")
        except Exception as e:  # noqa: BLE001 — injected drop == dead plane
            with self._lock:
                self.dead = True
            raise WorkerTimeoutError(
                "pod collective dropped (injected fault) — marking the pod "
                "control plane down"
            ) from e
        hdr = np.zeros((POD_HEADER,), np.int32)
        hdr[: min(POD_HEADER, np.asarray(header).size)] = \
            np.asarray(header, np.int32).reshape(-1)[:POD_HEADER]
        buf = np.zeros((self.blob_bytes,), np.uint8)
        b = np.asarray(blob, np.uint8).reshape(-1)
        if b.size > self.blob_bytes:
            raise ValueError(
                f"pod blob of {b.size} bytes exceeds the plane width "
                f"{self.blob_bytes} — chunk it (transport bug)"
            )
        buf[: b.size] = b
        tree = {"header": hdr, "blob": buf}
        if self.timeout_s is None:
            out = self._allgather(tree)
        else:
            with self._lock:
                if self.dead:
                    raise WorkerTimeoutError(
                        "pod control plane is down (a peer host previously "
                        "failed to respond)"
                    )
                import queue as _q

                if self._thread is None:
                    # same rationale as ControlPlane: one daemon thread
                    # issuing collectives in program order; a timed-out
                    # allgather strands the thread, not the pod loop
                    self._work: _q.Queue = _q.Queue()
                    self._out: _q.Queue = _q.Queue()

                    def run():
                        while True:
                            t = self._work.get()
                            try:
                                self._out.put(("ok", self._allgather(t)))
                            except BaseException as e:  # noqa: BLE001
                                self._out.put(("err", e))

                    import threading

                    self._thread = threading.Thread(
                        target=run, name="mst-pod-ctrl", daemon=True
                    )
                    self._thread.start()
                self._work.put(tree)
                try:
                    kind, val = self._out.get(timeout=self.timeout_s)
                except _q.Empty:
                    self.dead = True
                    raise WorkerTimeoutError(
                        f"pod collective did not complete within "
                        f"{self.timeout_s:.0f}s — a peer host is dead or "
                        "wedged; marking the pod control plane down"
                    ) from None
                if kind == "err":
                    self.dead = True
                    raise WorkerTimeoutError(
                        "pod collective failed — the distributed runtime "
                        "reported a dead or unreachable peer host"
                    ) from val
                out = val
        self.last_ok = self.clock()
        return np.asarray(out["header"]), np.asarray(out["blob"])
