"""Per-request span tracing: flight recorder + Chrome ``trace_event`` export.

Aggregate counters (``/metrics``) say *that* p99 TTFT regressed; they
cannot say *which hop* cost what for *which request*. This module is the
per-request instrument: a :class:`RequestTrace` records typed spans —
``queue_wait``, ``prefix_lookup``, ``prefill``, ``handoff_export`` /
``handoff_transfer`` / ``handoff_import``, ``decode_tick``, ``spill``,
``wake``, ``prefetch``, ``migration``, ``sse_write`` — into a bounded,
lock-correct structure, and finished traces land in a ring buffer (the
"flight recorder", ``--trace-buffer N`` requests) that serves
``GET /admin/trace/{request_id}`` and ``GET /admin/trace/dump`` as Chrome
``chrome://tracing`` JSON.

Cost contract: with ``--trace off`` (the default — the module-level tracer
starts unconfigured) every instrumentation site is one attribute load and
one ``is None`` branch; no span object, no timestamp, no lock is ever
touched. The mstcheck rule MST112 enforces exactly this shape inside
tick-hot scheduler functions: any ``tracing.``/span call there must sit
under an ``if tr is not None:``-style guard.

Sampling: ``--trace sample`` traces one request in ``sample_n`` (counter-
based, deterministic — no wall clock, no RNG); ``--trace on`` traces all.

Post-mortems: :func:`auto_snapshot` freezes the live + ring traces into a
bounded snapshot list. It is called on breaker trip
(``ReplicaSet._record_failure``), wedge detection
(``ContinuousBatcher.close`` join timeout), and every fault-site firing
(``testing.faults.inject``), so the victim request's timeline survives the
incident even after the ring cycles.

Timebase: ``time.perf_counter()`` throughout (never ``time.time()`` —
wall clock steps under NTP and is banned from hot paths by MST107/MST112).
Chrome ``ts`` values are microseconds relative to the tracer's epoch, so
every trace in a dump shares one timeline.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Optional

from mlx_sharding_tpu.analysis.runtime import make_lock, note_acquire, note_release

# the typed span vocabulary — one lane per type in the Chrome export
SPAN_TYPES = (
    "queue_wait",
    "prefix_lookup",
    "prefill",
    "handoff_export",
    "handoff_transfer",
    "handoff_import",
    "decode_tick",
    # diffusion over blocks: a harvested decode program of such a family, in
    # decode_tick's place (``forwards`` it ran, ``blocks``: blocks of the
    # request its forwards finished and handed on)
    "denoise",
    "spill",
    "wake",
    "prefetch",
    "migration",
    "sse_write",
)

# the scheduler tick's phases: they partition the tick thread's wall time
# (``other`` is what no named phase covers). Each is a key of
# ``ContinuousBatcher.tick_phase_stats()`` / ``mst_tick_phase_seconds_total``
# and, with ``--trace-profile``, a ``mst.<phase>`` span on the profiler's
# clock — except ``dispatch``, whose span keeps the name ``mst.decode_block``
TICK_PHASES = (
    "housekeeping",
    "admit",
    # the slot claim of one admitted request (pages, table row, offset and
    # sampler rows), nested in ``admit``
    "assign_slot",
    "prefill_chunk",
    # the read of a join's first token (the wait on its last chunk) and its
    # emit, with the flush of the drain's held tokens in front of it
    "first_token",
    "handoff",
    "dispatch",
    "harvest_wait",
    "emit",
    "kv_import",
    "idle_wait",
    "other",
)
TICK_SPAN = "mst.tick"


def phase_span_name(phase: str) -> str:
    return "mst.decode_block" if phase == "dispatch" else f"mst.{phase}"


_PHASE_SPAN = {phase: phase_span_name(phase) for phase in TICK_PHASES}
_NO_SPAN = contextlib.nullcontext()


# ``jax.named_scope`` names inside the served programs: one flat vocabulary
# shared by the models, tests/test_program_names.py and the benchmark's
# scope reduction. An operation belongs to the DEEPEST ``mst.*`` component
# of its ``op_name``, so the nested ``mst.moe.experts.*`` refine their parent
MODEL_SCOPES = (
    "mst.embed",
    "mst.attn.qkv",
    "mst.attn.kv_write",
    "mst.attn.core",
    # a model that names its attention layer kinds (models/afmoe.py): the
    # attention call of a window / a full layer in place of mst.attn.core's,
    # the output gate, the QK-norm, and the window layers' ring pool
    "mst.attn.window",
    "mst.attn.full",
    "mst.attn.gate",
    "mst.attn.qk_norm",
    "mst.kv_ring.regroup",
    # a model whose attention runs in a compressed latent (models/zaya.py):
    # the convolutions over time, the q-k mean, the norms and the value shift
    # between the projections and the attention call
    "mst.attn.cca_mix",
    "mst.moe.router",
    "mst.moe.experts",
    "mst.moe.experts.gather_dequant",
    "mst.moe.experts.matmul",
    "mst.moe.experts.scan",
    "mst.moe.shared",
    # a shared expert behind a gate of its own (models/qwen3_next.py): the
    # sigmoid of one scalar a row and its product with the expert's output
    "mst.moe.shared_gate",
    "mst.moe.latent",
    "mst.ssm.in_proj",
    "mst.ssm.conv",
    "mst.ssm.scan",
    "mst.ssm.step",
    "mst.ssm.out_proj",
    # a gated delta-rule layer (ops/kda.py): the q, k, v projection, the
    # convolution, the two low-rank gates with beta and the L2 norms, a
    # chunk's WY form or a decode step's recurrence, the gated norm and o_proj
    "mst.kda.proj",
    "mst.kda.conv",
    "mst.kda.gate",
    "mst.kda.scan",
    "mst.kda.step",
    "mst.kda.out",
    "mst.state_pool.regroup",
    "mst.mlp.dense",
    "mst.norm",
    "mst.kv_pool.regroup",
    "mst.head",
    "mst.sample",
    # a decode forward's epilogue where the family generates by diffusion
    # over blocks (diffusion.py): which masked positions take their sampled
    # token, the block's next ids and mask, the commit (behind mst.sample,
    # which samples every position of the block)
    "mst.diffusion.unmask",
)

# hard bound per trace: a runaway stream degrades to a truncated timeline
# (with a drop counter), never to unbounded memory
MAX_SPANS_PER_TRACE = 4096
# snapshots kept (each is a frozen copy of live+ring at incident time)
MAX_SNAPSHOTS = 8


class RequestTrace:
    """One request's span timeline. All mutation is under a leaf lock —
    spans arrive from the scheduler tick thread while the server thread
    may be exporting — and every recording method is cheap enough that
    call sites only need the ``if tr is not None:`` no-op guard."""

    __slots__ = ("request_id", "t0", "_lock", "_spans", "_marks", "_meta",
                 "_dropped", "done")

    def __init__(self, request_id: str, t0: Optional[float] = None):
        self.request_id = str(request_id)
        self.t0 = time.perf_counter() if t0 is None else float(t0)
        self._lock = make_lock("RequestTrace._lock")
        self._spans: list = []   # (name, t0, t1, args) perf_counter seconds
        self._marks: list = []   # (name, t, args) instant events
        self._meta: dict = {}
        self._dropped = 0
        self.done = False

    # ------------------------------------------------------------ recording
    def add(self, name: str, t0: float, t1: float, **args):
        """Record a completed span with caller-measured endpoints. The
        caller takes the two ``perf_counter()`` stamps so the lock is held
        for the append only, never across the timed work."""
        with self._lock:
            if len(self._spans) >= MAX_SPANS_PER_TRACE:
                self._dropped += 1
                return
            self._spans.append((name, float(t0), float(t1), args or None))

    def point(self, name: str, **args):
        """Record an instant event (first token, fault firing, failover)."""
        t = time.perf_counter()
        with self._lock:
            if len(self._marks) >= MAX_SPANS_PER_TRACE:
                self._dropped += 1
                return
            self._marks.append((name, t, args or None))

    @contextlib.contextmanager
    def timed(self, name: str, **args):
        """Span context manager for non-hot call sites (store lookups,
        handoff phases, SSE writes). Hot paths use :meth:`add` directly."""
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.add(name, t0, time.perf_counter(), **args)

    def note(self, **meta):
        """Attach request metadata (prompt tokens, replica, role...)."""
        with self._lock:
            self._meta.update(meta)

    # ------------------------------------------------------------- reading
    def freeze(self) -> dict:
        """A consistent, immutable copy for snapshots and export."""
        with self._lock:
            return {
                "request_id": self.request_id,
                "t0": self.t0,
                "spans": list(self._spans),
                "marks": list(self._marks),
                "meta": dict(self._meta),
                "dropped": self._dropped,
                "done": self.done,
            }

    def span_names(self) -> list:
        with self._lock:
            return [s[0] for s in self._spans]

    def mark_names(self) -> list:
        with self._lock:
            return [m[0] for m in self._marks]


class Tracer:
    """The flight recorder: live traces by request id, a bounded ring of
    finished traces, and frozen incident snapshots."""

    def __init__(self, *, mode: str = "off", buffer: int = 256,
                 sample_n: int = 8, profile: bool = False):
        if mode not in ("off", "sample", "on"):
            raise ValueError(f"trace mode must be off/sample/on, got {mode!r}")
        if buffer < 1:
            raise ValueError(f"trace buffer must be >= 1, got {buffer}")
        if sample_n < 1:
            raise ValueError(f"sample_n must be >= 1, got {sample_n}")
        self.mode = mode
        self.buffer = int(buffer)
        self.sample_n = int(sample_n)
        self.profile = bool(profile)
        self.epoch = time.perf_counter()  # shared timebase for dumps
        self._lock = make_lock("Tracer._lock")
        self._live: dict = {}                 # request_id -> RequestTrace
        self._ring: deque = deque(maxlen=self.buffer)
        self._snapshots: list = []            # (reason, [frozen trace, ...])
        self._seq = 0                         # begin() calls (sampling base)
        self._started = 0                     # traces actually created

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

    # ----------------------------------------------------------- lifecycle
    def begin(self, request_id: Optional[str] = None) -> Optional[RequestTrace]:
        """Start tracing one request. Returns None when off or unsampled —
        every downstream site then short-circuits on the None check."""
        if self.mode == "off":
            return None
        with self._lock:
            self._seq += 1
            if self.mode == "sample" and (self._seq - 1) % self.sample_n:
                return None
            if request_id is None:
                request_id = f"req-{self._seq}"
            tr = RequestTrace(request_id)
            self._live[tr.request_id] = tr
            self._started += 1
            return tr

    def finish(self, tr: Optional[RequestTrace]):
        """Retire a trace into the ring. Accepts None so call sites don't
        need their own guard at request teardown."""
        if tr is None:
            return
        with tr._lock:
            tr.done = True
        with self._lock:
            self._live.pop(tr.request_id, None)
            self._ring.append(tr)

    # ------------------------------------------------------------- reading
    def get(self, request_id: str) -> Optional[dict]:
        """Frozen trace for ``request_id`` from live, ring, or snapshots
        (newest first)."""
        with self._lock:
            tr = self._live.get(request_id)
            ring = list(self._ring)
            snaps = list(self._snapshots)
        if tr is not None:
            return tr.freeze()
        for cand in reversed(ring):
            if cand.request_id == request_id:
                return cand.freeze()
        for _, frozen, _camp in reversed(snaps):
            for f in frozen:
                if f["request_id"] == request_id:
                    return f
        return None

    def dump(self) -> list:
        """Frozen copies of every live + ring trace (oldest first)."""
        with self._lock:
            traces = list(self._ring) + list(self._live.values())
        return [t.freeze() for t in traces]

    def snapshot(self, reason: str) -> dict:
        """Freeze the recorder for a post-mortem: live and ring traces are
        copied (the originals keep recording) into a bounded snapshot list
        keyed by ``reason`` (``fault:<site>``, ``breaker_open``, ``wedge``)."""
        frozen = self.dump()
        # campaign provenance: when a chaos campaign is active (sim/chaos
        # sets it), the snapshot carries the campaign's seed and the VIRTUAL
        # timestamp of the incident — enough to link a production-shaped
        # post-mortem back to its replayable repro file
        camp = campaign_stamp()
        with self._lock:
            self._snapshots.append((reason, frozen, camp))
            while len(self._snapshots) > MAX_SNAPSHOTS:
                self._snapshots.pop(0)
        out = {"reason": reason, "traces": frozen}
        if camp is not None:
            out["campaign"] = camp
        return out

    def snapshots(self) -> list:
        with self._lock:
            snaps = list(self._snapshots)
        out = []
        for r, f, camp in snaps:
            entry = {"reason": r, "traces": f}
            if camp is not None:
                entry["campaign"] = camp
            out.append(entry)
        return out

    def stats(self) -> dict:
        with self._lock:
            return {
                "mode": self.mode,
                "buffer": self.buffer,
                "sample_n": self.sample_n,
                "profile": self.profile,
                "live": len(self._live),
                "ring": len(self._ring),
                "snapshots": len(self._snapshots),
                "begun": self._seq,
                "sampled": self._started,
            }

    # -------------------------------------------------------------- export
    def export_request(self, request_id: str) -> Optional[dict]:
        frozen = self.get(request_id)
        if frozen is None:
            return None
        return chrome_trace([frozen], epoch=self.epoch)

    def export_dump(self) -> dict:
        out = chrome_trace(self.dump(), epoch=self.epoch)
        with self._lock:
            snaps = list(self._snapshots)
        out["snapshots"] = [
            dict(
                {"reason": r,
                 "requests": [f["request_id"] for f in frozen]},
                **({"campaign": camp} if camp is not None else {}),
            )
            for r, frozen, camp in snaps
        ]
        return out


# --------------------------------------------------------- chrome export
def _lane(name: str) -> int:
    """Stable tid per span type so every request renders the same lanes."""
    try:
        return SPAN_TYPES.index(name) + 1
    except ValueError:
        return len(SPAN_TYPES) + 1


def chrome_trace(frozen_traces: list, *, epoch: float) -> dict:
    """Chrome ``trace_event`` JSON (the ``chrome://tracing`` / Perfetto
    format): one process per request, one thread lane per span type,
    ``ts``/``dur`` in microseconds relative to ``epoch``."""
    events = []
    for pid, f in enumerate(frozen_traces, start=1):
        rid = f["request_id"]
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"request {rid}"},
        })
        for lane_name in SPAN_TYPES:
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid,
                "tid": _lane(lane_name), "args": {"name": lane_name},
            })
        for name, t0, t1, args in f["spans"]:
            events.append({
                "name": name, "ph": "X", "cat": "request",
                "ts": round((t0 - epoch) * 1e6, 1),
                "dur": round(max(0.0, t1 - t0) * 1e6, 1),
                "pid": pid, "tid": _lane(name),
                "args": dict(args or {}, request_id=rid),
            })
        for name, t, args in f["marks"]:
            events.append({
                "name": name, "ph": "i", "s": "p", "cat": "request",
                "ts": round((t - epoch) * 1e6, 1),
                "pid": pid, "tid": _lane(name.split(":", 1)[0]),
                "args": dict(args or {}, request_id=rid),
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ----------------------------------------------------- module-level wiring
_TRACER: Optional[Tracer] = None
_TRACER_LOCK = threading.Lock()
_TLS = threading.local()


def configure(mode: str = "off", *, buffer: int = 256, sample_n: int = 8,
              profile: bool = False) -> Tracer:
    """Install the process-wide tracer (``--trace``/``--trace-buffer``/
    ``--trace-profile``). Replaces any previous tracer wholesale so tests
    can reconfigure; serving configures once at startup."""
    global _TRACER
    t = Tracer(mode=mode, buffer=buffer, sample_n=sample_n, profile=profile)
    with _TRACER_LOCK:
        _TRACER = t
    return t


def get_tracer() -> Optional[Tracer]:
    return _TRACER


def begin(request_id: Optional[str] = None) -> Optional[RequestTrace]:
    """Convenience: start a trace on the process tracer (None when off)."""
    t = _TRACER
    if t is None:
        return None
    return t.begin(request_id)


def finish(tr: Optional[RequestTrace]):
    t = _TRACER
    if t is not None:
        t.finish(tr)


# ------------------------------------------------------ thread-local bind
def current() -> Optional[RequestTrace]:
    """The trace bound to the calling thread (see :class:`bind`) — how
    leaf modules (prefix_store, kv_transfer) and the fault harness stamp
    the right request without signature changes."""
    return getattr(_TLS, "trace", None)


class bind:
    """Bind ``tr`` (possibly None) to the calling thread for a region::

        with tracing.bind(req._trace):
            store.lookup(owner, digests)   # lookup self-instruments
    """

    __slots__ = ("_tr", "_prev")

    def __init__(self, tr: Optional[RequestTrace]):
        self._tr = tr

    def __enter__(self):
        self._prev = getattr(_TLS, "trace", None)
        _TLS.trace = self._tr
        note_acquire("tracing.bind", id(self))
        return self._tr

    def __exit__(self, *exc):
        _TLS.trace = self._prev
        note_release("tracing.bind", id(self))
        return False


# ------------------------------------------------------------ post-mortem
# chaos-campaign provenance: while a seeded campaign is running, every
# flight-recorder snapshot is stamped with the campaign's identity and the
# VIRTUAL time of the incident, so a production-shaped post-mortem links
# straight back to the repro file that replays it bit-identically.
_CAMPAIGN: Optional[dict] = None


def set_campaign(name: Optional[str], seed: Optional[int] = None,
                 clock=None):
    """Install (or, with ``name=None``, clear) the active chaos-campaign
    context. ``clock`` is the campaign's virtual clock; it is read at each
    snapshot to stamp ``t_virtual``."""
    global _CAMPAIGN
    if name is None:
        _CAMPAIGN = None
    else:
        _CAMPAIGN = {"name": str(name), "seed": int(seed or 0),
                     "clock": clock}


def campaign_stamp() -> Optional[dict]:
    """The JSON-safe provenance dict for the active campaign (None when no
    campaign is running)."""
    camp = _CAMPAIGN
    if camp is None:
        return None
    out = {"name": camp["name"], "seed": camp["seed"]}
    clock = camp.get("clock")
    if clock is not None:
        try:
            out["t_virtual"] = float(clock())
        except Exception:  # noqa: BLE001 — provenance never breaks a snapshot
            pass
    return out


def auto_snapshot(reason: str):
    """Freeze the flight recorder on an incident (breaker trip, wedge,
    fault firing). Near-free no-op when tracing is off."""
    t = _TRACER
    if t is not None and t.enabled:
        try:
            t.snapshot(reason)
        except Exception:  # a sick recorder must never worsen an incident
            pass


def record_fault(site: str):
    """Called by ``testing.faults.inject`` when an armed fault actually
    fires: stamp the bound request's timeline with the degradation event,
    then snapshot so the victim's trace survives the ring."""
    tr = current()
    if tr is not None:
        tr.point(f"fault:{site}", site=site)
    auto_snapshot(f"fault:{site}")


# -------------------------------------------------- XLA profiler bridging
def profile_enabled() -> bool:
    t = _TRACER
    return bool(t is not None and t.enabled and t.profile)


def profile_span(name: str, **args):
    """``jax.profiler.TraceAnnotation`` context (``--trace-profile``), so
    the scheduler's host spans sit on the same clock as the XLA timeline in
    a profiler capture; ``args`` become the event's stats. Null context
    when jax's profiler is unavailable — tracing must not create a jax
    dependency."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name, **args)
    except Exception:
        return contextlib.nullcontext()


# what the tick thread dispatches on the device, as its account tells them
# apart: ``block`` (a plain decode block), ``chunk`` (a target prefill chunk,
# with the first-token program behind a join's last one) and ``other`` (a
# speculative round, a draft's chunk or replay, a block import's resume)
PROGRAM_KINDS = ("block", "chunk", "other")


class TickPhases:
    """Where the scheduler tick thread's wall time goes: cumulative seconds
    and entry counts per phase of :data:`TICK_PHASES`, always on, and — with
    ``--trace-profile`` — one profiler span per phase, from the same place.

    A phase opened inside another SUSPENDS the outer one (a drain's
    ``harvest_wait`` inside ``admit`` is harvest time, not admission time)
    and time no named phase covers is charged to ``other``, so the phases
    partition the thread's wall time since :meth:`start`. Entering or
    leaving a phase is one ``perf_counter()`` read. Only the tick thread
    writes; :meth:`snapshot` may be called from any thread.

    Beside the phases stands the device's timeline as this thread's waits
    show it: the FIFO of served programs dispatched and not yet known to
    have ended, each with its kind (:data:`PROGRAM_KINDS`) and the stamps of
    its dispatch call (:meth:`dispatched` before it, :meth:`returned` after
    it). A blocking read's return is the end of the program it waited on
    (:meth:`ready`) and closes every program dispatched before that one too.
    The device runs what it is handed in order, so a program began at the
    later of its call and the end before it, and the account adds, by kind:
    the seconds the device had it (``device_seconds``), the part of its
    dispatch call made with the device empty (``exposed_seconds``), runs,
    and the runs whose end was learned ``late`` (found ended when asked:
    their seconds are an upper bound). A program whose end nobody observed
    is taken to have ended when the next one was called, or to have had no
    time yet where the device was still behind; programs nobody will read
    are dropped (:meth:`drop`) and their time kept apart (``unread_seconds``).

    The device is ``busy`` while that queue holds anything, and beside each
    phase's seconds stands the part of them it was not (``empty_seconds``):
    host work under a lookahead block costs nothing; the same work with the
    queue empty is capacity lost. Busy and empty partition the thread's
    clock: device seconds, unread seconds, empty seconds and what the
    programs still queued have had add up to the time since :meth:`start`."""

    def __init__(self, profile: bool = False):
        self.profile = bool(profile)
        self.seconds = dict.fromkeys(TICK_PHASES, 0.0)
        self.empty_seconds = dict.fromkeys(TICK_PHASES, 0.0)
        self.entries = dict.fromkeys(TICK_PHASES, 0)
        self.ticks = 0
        # [kind, call, ret, ticket] of every program dispatched and not yet
        # known to have ended, oldest first
        self._queue: deque = deque()
        self._tickets = 0
        self._device_end = 0.0  # the last end the account knows of
        self.device_seconds = dict.fromkeys(PROGRAM_KINDS, 0.0)
        self.exposed_seconds = dict.fromkeys(PROGRAM_KINDS, 0.0)
        self.runs = dict.fromkeys(PROGRAM_KINDS, 0)
        self.late = dict.fromkeys(PROGRAM_KINDS, 0)
        self.unread_seconds = 0.0
        # (start, end) of the span that closed last, perf_counter seconds:
        # the harvest reuses its wait's stamps for the per-request spans
        # and for the end of the block it waited on
        self.last = (0.0, 0.0)
        # (phase being charged, since when, whether the device was busy
        # meanwhile); a fresh tuple at every switch, which is what lets
        # snapshot() detect a switch under its feet
        self._open = None

    @property
    def busy(self) -> bool:
        """The device has a served program dispatched and unread."""
        return bool(self._queue)

    def _switch(self, phase, now: float):
        cur = self._open
        if cur is not None:
            dt = now - cur[1]
            self.seconds[cur[0]] += dt
            if not cur[2]:
                self.empty_seconds[cur[0]] += dt
        self._open = None if phase is None else (phase, now, bool(self._queue))

    def _busy_changed(self, now: float):
        """The queue went from empty to not or back inside a phase: close
        the open interval first, as a phase switch does."""
        cur = self._open
        if cur is not None:
            self._switch(cur[0], now)

    def dispatched(self, kind: str) -> int:
        """Right before the dispatch call of a served program (its arguments
        are made first: they are host work): the ``call`` stamp. Returns the
        program's ticket, which a later :meth:`ready` names it by."""
        now = time.perf_counter()
        queue = self._queue
        was_empty = not queue
        self._tickets += 1
        queue.append([kind, now, now, self._tickets])
        if was_empty:
            self._busy_changed(now)
        return self._tickets

    def returned(self) -> float:
        """The dispatch call of the program dispatched last came back: the
        ``ret`` stamp, which is also returned."""
        now = time.perf_counter()
        self._queue[-1][2] = now
        return now

    def ready(self, ticket: Optional[int] = None, at: Optional[float] = None,
              late: bool = False):
        """The host learned that program ``ticket`` had ended (none: the one
        dispatched last), at ``at`` (none: now; a caller that holds the stamp
        of the read's return passes it and saves the clock read — it may not
        lie before the open phase began). Closes it and every program
        dispatched before it; ``late`` says it was found ended when asked,
        not waited for. A ticket closed or dropped already changes nothing."""
        queue = self._queue
        if ticket is None:
            ticket = self._tickets
        if not queue or queue[0][3] > ticket:
            return
        now = time.perf_counter() if at is None else at
        end, end_known = self._device_end, True
        while queue and queue[0][3] <= ticket:
            kind, call, ret, _ = queue.popleft()
            begin = call if call > end else end
            # only a call behind an end the host learned is known exposed
            if end_known and ret > begin:
                self.exposed_seconds[kind] += ret - begin
            if queue and queue[0][3] <= ticket:
                # its end went unobserved: the next program's call, if the
                # device had got to this one by then
                end = queue[0][1] if queue[0][1] > begin else begin
                end_known = False
            else:
                end = now
            self.device_seconds[kind] += end - begin
            self.runs[kind] += 1
        if late:
            self.late[kind] += 1
        self._device_end = now
        if not queue:
            self._busy_changed(now)

    def drop(self):
        """Whatever is still dispatched nobody will read (an abandoned
        block, a cancelled joiner's chunk at the idle wait, a failure): the
        device is as good as empty from here. The queue's time so far is
        kept apart from the programs' seconds, and no run is counted."""
        queue = self._queue
        if not queue:
            return
        now = time.perf_counter()
        self.unread_seconds += now - max(queue[0][1], self._device_end)
        self._device_end = now
        queue.clear()
        self._busy_changed(now)

    def start(self):
        """The tick thread's loop begins: the clock runs from here."""
        self._switch("other", time.perf_counter())

    def stop(self):
        """The loop ended: close the open phase, stop the clock."""
        self._switch(None, time.perf_counter())

    def _annotation(self, name: str, **args):
        return profile_span(name, **args) if self.profile else _NO_SPAN

    @contextlib.contextmanager
    def tick(self):
        """One loop iteration: the ``mst.tick`` span, whose ``pc`` is this
        process's ``perf_counter()`` at entry — the flight recorder's
        timebase, so a ``/admin/trace/dump`` lines up with a profiler
        capture by one subtraction — and which carries the account's
        cumulative seconds at entry: ``empty`` (all phases), ``dev_block``
        and ``dev_chunk`` (the device's seconds on blocks and on chunks)
        and ``exposed`` (dispatch calls made with the device empty, all
        kinds). The difference between two ticks of a capture is what the
        host says the device idled and ran between them, beside the gaps on
        the capture's ``XLA Ops`` line and the programs on its ``XLA
        Modules`` line."""
        if self.profile:
            now = time.perf_counter()
            ann = profile_span(
                TICK_SPAN, pc=now, empty=self._empty_at(now),
                dev_block=self.device_seconds["block"],
                dev_chunk=self.device_seconds["chunk"],
                exposed=sum(self.exposed_seconds.values()),
            )
        else:
            ann = _NO_SPAN
        with ann:
            self.ticks += 1
            yield

    def _empty_at(self, now: float) -> float:
        """Empty seconds of all phases up to ``now`` (tick thread only)."""
        cur = self._open
        total = sum(self.empty_seconds.values())
        if cur is not None and not cur[2]:
            total += now - cur[1]
        return total

    @contextlib.contextmanager
    def span(self, phase: str, **args):
        """Charge the enclosed time to ``phase``; ``args`` (cause, shared
        identifiers) go on the profiler span and cost nothing otherwise."""
        with self._annotation(_PHASE_SPAN[phase], **args):
            cur = self._open
            outer = cur[0] if cur is not None else "other"
            t_in = time.perf_counter()
            self._switch(phase, t_in)
            self.entries[phase] += 1
            try:
                yield
            finally:
                t_out = time.perf_counter()
                self._switch(outer, t_out)
                self.last = (t_in, t_out)

    def snapshot(self) -> dict:
        """``{"ticks", "seconds": {phase: s}, "empty_seconds": {phase: s},
        "entries": {phase: n}}`` with the open phase's elapsed part
        included, so two snapshots bracket a window exactly, and the
        programs' account by kind (``device_seconds``, ``exposed_seconds``,
        ``runs``, ``late``; ``unread_seconds``) as of the last program
        closed. Lock-free: re-read when the tick thread switched phases
        meanwhile."""
        for _ in range(8):
            cur = self._open
            seconds = dict(self.seconds)
            empty = dict(self.empty_seconds)
            programs = {
                "device_seconds": dict(self.device_seconds),
                "exposed_seconds": dict(self.exposed_seconds),
                "runs": dict(self.runs),
                "late": dict(self.late),
                "unread_seconds": self.unread_seconds,
            }
            if self._open is cur:
                break
        if cur is not None:
            dt = max(0.0, time.perf_counter() - cur[1])
            seconds[cur[0]] += dt
            if not cur[2]:
                empty[cur[0]] += dt
        return {"ticks": self.ticks, "seconds": seconds,
                "empty_seconds": empty, "entries": dict(self.entries),
                **programs}
