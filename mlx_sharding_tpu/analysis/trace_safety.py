"""Trace-safety rules (MST10x): hazards inside or around jit-traced code.

- **MST101 trace-host-effect** — a host side effect inside a function that
  is (transitively) traced by ``jax.jit`` / ``jax.vmap`` / ``jax.lax.scan``
  etc.: wall clocks (``time.time``/``time_ns``/…), ``print``, the stdlib
  ``random`` module or ``np.random``, and ``global``-statement mutation.
  These run once at trace time and silently freeze into the compiled
  program (or recompile it), the classic "my timestamp never changes" bug.
- **MST102 sync-in-hot-path** — a blocking device synchronization
  (``.item()``, ``.block_until_ready()``, ``jax.device_get``,
  ``np.asarray``/``np.array``) inside a
  serving hot path: the continuous-batching scheduler tick and its helpers,
  plus any function annotated ``# mst: hot-path``. Every such call stalls
  the dispatch pipeline for a full device round trip; intentional,
  amortized sync points carry an inline ``# mst: allow(MST102): …``.
- **MST103 recompile-hazard** — a call to a jit-compiled callable passing a
  freshly built array whose shape derives from request data (``len(...)``,
  ``.size``, ``.shape[...]``) without going through a recognized bucketing
  helper. Data-dependent shapes recompile the program per distinct value —
  the scheduler's chunked prefill (``_chunk_at``) and the page-rounded pool
  exist precisely to avoid this.
- **MST104 double-harvest** — a SECOND ``jax.device_get`` inside one
  tick-hot function. The async scheduler pipeline is built around a single
  consolidated harvest point per tick (pass a tuple pytree and unpack);
  each extra ``device_get`` is an extra serialization of the dispatch
  stream that silently re-introduces the host-blocked gap the pipeline
  exists to hide. An MST102 suppression on the sync does NOT cover this
  rule — a second harvest needs its own justification.
- **MST105 dense-dequant-in-decode** — a ``dequantize(...)`` result bound
  to a name inside a decode-hot function (the packed-matmul dispatchers in
  ``quant.py``, plus anything annotated ``# mst: decode-hot``) or anything
  it transitively calls in the same file. Materializing the dense bf16
  weight tile in HBM re-pays the full 4x weight traffic the packed path
  exists to delete, once per decode step. Fused-kernel dequant is invisible
  to this rule (Pallas kernel bodies are passed to ``pallas_call``, never
  called by name, so the call-closure walk never enters them); a guarded
  fallback whose dense tile is transient carries an inline
  ``# mst: allow(MST105): …``.
- **MST106 sync-spill-in-tick** — a synchronous full-block pull
  (``jax.device_get`` / ``np.asarray`` / ``.to_host()``) of an exported KV
  page block (the result of ``export_block``/``export_pool_pages``) inside
  a tick-hot function. A spilled block is the largest single transfer the
  scheduler ever touches (a request's whole page chain); pulling it inline
  stalls every live slot's decode for the full device→host copy. The spill
  path must only DISPATCH the gather on the tick thread and leave the
  blocking copy to the spill tier's flusher thread (see
  ``kv_transfer.KVSpillTier``). An MST102 suppression on the same call does
  NOT cover this rule — a full-block pull needs its own justification.
- **MST108 block-migration-in-tick** — a KV page-block migration call
  (``export_block``/``import_block``) inside a tick-hot function. These are
  the disaggregation/spill handoff primitives: an export gathers a
  request's whole page chain and stamps sampler state, an import allocates
  pages, scatters the payload and verifies the checksum — each is a
  whole-request unit of work that belongs on the non-hot helpers
  (``_handoff_out``, ``_import_block`` at admission) or a flusher thread,
  never inline in the per-decode-block tick. MST106 catches the
  synchronous *pull* of an exported block; this rule catches the migration
  call itself, which stalls the tick even when dispatch-only (tree flatten
  + jit argument marshalling per page chain).
- **MST109 demand-paged-import-in-tick** — an upload call
  (``jax.device_put`` / ``jnp.asarray`` / ``jnp.array``) inside a tick-hot
  function whose argument touches a spilled block's host pages
  (``.k_pages``/``.v_pages``, or a name fetched from a spill tier via
  ``.take()``/``.peek()``). That is the demand-paged resume: the tick
  blocks while a request's whole page chain marshals host→device, stalling
  every live slot's decode for a copy that could have been in flight
  already. The residency discipline is PRESERVE-style: stage the block
  with ``KVPageBlock.prefetch()`` from the (non-hot) wake/admission policy
  pass when the slot is scheduled to rejoin — the copy overlaps the
  current decode block's compute — and keep demand import as a counted
  off-tick fallback. An MST102/MST106 suppression nearby does NOT cover
  this rule.
- **MST111 store-import-in-tick** — an upload call inside a tick-hot
  function whose argument touches a result fetched from a prefix KV store
  (a name assigned from ``<...store...>.lookup()`` / ``.host_block()``).
  The fleet-wide prefix store's host tier holds whole page-chain payloads;
  marshaling one host→device inline in the tick stalls every live slot's
  decode exactly like the MST109 demand-paged resume. The admission
  discipline is the same PRESERVE shape: the (non-hot) waiting-queue pass
  stages the block with ``KVPageBlock.prefetch()`` while decode runs
  (``_prefetch_store_waiting``), admission scatters the staged copy, and
  demand import stays a counted off-tick fallback. MST109 tracks the spill
  tier's ``take``/``peek``; this rule tracks the store's lookup surface —
  a suppression on one does NOT cover the other.
- **MST110 weight-upload-in-spawn** — a full param-tree placement
  (``jax.device_put`` / ``put_global`` / ``place_weights``) inside a
  spawn-hot function: the replica-spawn factories the autoscaler calls
  (``replica_factory``/``pool_factory``/``spawn_replica``, ``fleet._spawn``,
  plus anything annotated ``# mst: spawn-hot``). A spawn that re-uploads or
  re-shards the checkpoint stalls scale-out on checkpoint I/O and costs a
  second W of HBM the fleet was sized not to have — the spawn path must
  alias the host's resident tree through ``weights.WeightStore.acquire``
  (the store's builder does the one real upload, off the per-spawn path).
  Only a call whose argument subtree names param-ish data (param / weight /
  state_dict / checkpoint) fires, so KV staging in a factory stays clean.
- **MST112 unguarded-trace-in-tick** — request-lifecycle tracing work
  (span construction / serialization: a call through a trace-ish receiver
  such as ``tr.add(...)``, ``req._trace.point(...)``, ``tracing.bind(...)``)
  or ``time.time()`` timestamping inside a tick-hot function, outside the
  tracing no-op guard. The tracing contract is near-zero cost when off:
  hot paths bind the handle once (``tr = req._trace``) and gate every span
  on ``if tr is not None:`` (an attribute/None test that branches on a
  trace-ish identifier counts as the guard; ``time.perf_counter()`` is the
  sanctioned timestamp and is never flagged). An unguarded call runs its
  argument marshalling and lock traffic on every decode block even with
  ``--trace off``; the rule catches it statically.
- **MST113 control-plane-in-tick** — a blocking control-plane collective
  (``<plane>.exchange(...)``, ``<plane>.heartbeat(...)``,
  ``<plane>.pod_exchange(...)``) inside a tick-hot function. A collective
  is a cross-host rendezvous: it completes when the slowest host arrives
  or after the plane timeout when one never does, so inline in the tick it
  wedges every live slot's decode behind the slowest peer — and a dead
  peer freezes the fleet for the full timeout. Collectives belong on the
  dedicated transport/heartbeat threads; the tick reads the gossiped
  snapshot. An intentional inline rendezvous carries its own
  ``# mst: allow(MST113): …``.
- **MST114 sync-in-spec-policy** — a blocking device sync
  (``jax.device_get`` / ``.item()``) inside the speculation policy surface:
  the per-round draft proposal and acceptance-tracker functions
  (``_dispatch_spec``/``_spec_plan`` in the scheduler,
  ``propose``/``observe``/``window`` on the proposer/tracker, plus anything
  annotated ``# mst: spec-hot``). These run once per speculative round on
  the tick thread and are host-side numpy BY DESIGN — the n-gram match
  reads the request's host history, the tracker's EWMA is a float — so
  they are deliberately NOT in the MST102 hot set (``np.asarray`` is their
  bread and butter). But a ``device_get``/``.item()`` there drains the
  dispatch pipe once per round to read a value the round's single
  consolidated harvest (``_harvest_spec``) already returns — exactly the
  per-round stall adaptive speculation exists to amortize away. An
  MST102 suppression nearby does NOT cover this rule.
- **MST115 prefix-federation-in-tick** — a pod prefix-federation call
  (``<...federation/prefix...>.fetch(...)`` / ``.local_info(...)``,
  ``host_inventory(...)``) or share-map calibration I/O
  (``calibrate_share_map`` / ``rank_layer_pairs`` /
  ``layer_kv_signatures`` / ``load_share_map``) inside a tick-hot
  function. A federation fetch blocks on a cross-host blob transfer
  bounded only by its timeout, and an inventory walk serializes against
  the store's flusher lock — either inline in the tick stalls every live
  slot's decode behind a peer. Calibration is worse still: dense
  prefills plus whole-KV host marshalling. The discipline: the
  (non-hot) waiting-queue pass ``_pod_fetch_waiting`` starts the fetch
  on its own daemon thread and admission only reads the per-request
  flag; calibration is OFFLINE (``cli/kv_share_calibrate.py``) and
  serving loads the saved artifact once at startup. An intentional
  inline consult carries its own ``# mst: allow(MST115): …``.
- **MST116 latent-reconstruct-in-tick** — a compressed-latent KV codec
  call (``reconstruct_block`` / ``reconstruct_pages`` /
  ``compress_pages``, kv_compress.py) inside a tick-hot function.
  Reconstruction materializes the dense per-head pages from rank-r
  latents — a ``(tokens, r) @ (r, H*D)`` up-projection over every page
  of every layer, in host numpy — and compression is its transpose;
  either inline in the tick stalls every live slot's decode behind one
  block's matmul. The discipline: compression runs inside
  ``KVPageBlock.to_host`` on the spill flusher / handoff threads, and
  reconstruction runs in ``prefetch``'s overlapped host→device stage or
  the consumer's (non-hot) import path — the tick only ever touches
  already-dense pages. An intentional inline reconstruction carries its
  own ``# mst: allow(MST116): …``.
- **MST107 wall-clock-deadline** — ``time.time()`` feeding deadline or
  timeout arithmetic (an expression whose identifiers mention deadline /
  timeout / expiry / until / budget / ttft / retry_after / lease). The wall
  clock steps and slews under NTP; a deadline computed from it can fire
  years early or never. Every serving deadline — request_timeout, TTFT,
  breaker half-open ETA, autoscaler cooldown, lease expiry — must be a
  ``time.monotonic()`` difference. Timestamps for humans (log lines, the
  OpenAI ``created`` field) are fine: they carry no deadline identifiers.
  The rule also covers the reverse drift: inside a class that carries an
  INJECTABLE clock (``self.clock`` / ``self._clock``, see
  ``utils/clock.py``), a raw ``time.monotonic()`` in deadline arithmetic
  is flagged too — it silently bypasses the injected time source, so
  virtual-clock tests and the fleet simulator pass against one clock while
  the shipped binary runs on another.
"""

from __future__ import annotations

import ast

from mlx_sharding_tpu.analysis.core import (
    Finding,
    ModuleInfo,
    dotted_name,
    qualname_for_line,
)

# functions that register their callable argument(s) for tracing
TRACING_ENTRY_POINTS = {
    "jax.jit", "jit", "pjit", "jax.pjit",
    "jax.vmap", "vmap", "jax.pmap",
    "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat",
    "jax.lax.scan", "lax.scan",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.cond", "lax.cond",
    "jax.lax.switch", "lax.switch",
    "jax.lax.map", "lax.map",
}

HOST_CLOCKS = {
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns",
}
HOST_RANDOM_ROOTS = ("random.", "np.random.", "numpy.random.")

# serving hot paths checked by MST102 (beyond '# mst: hot-path' annotations):
# the scheduler tick and everything it runs per decode block
HOT_PATH_FUNCS = {
    "scheduler.py": {
        # the per-tick path only: _preempt/_release_pages etc. run on rare
        # events (pool pressure), not every decode block
        "_tick", "_tick_async", "_decode_once", "_dispatch_block",
        "_harvest", "_quiesce", "_decoding", "_growth_fits", "_spec_once",
        "_prefill_one_chunk", "_grow_for_decode", "_emit",
        # the speculative round's harvest side runs on every spec tick;
        # _dispatch_spec/_spec_plan are deliberately NOT here (host numpy
        # proposal work — np.asarray is their job) and are covered by the
        # stricter MST114 device-sync rule instead
        "_harvest_spec", "_spec_tick", "_harvest_any",
        # the wait on a join's middle chunk, in front of both harvests
        "_chunk_ended",
        # the read of a join's first token, behind the block or before it
        "_read_first_tokens", "_block_leads",
    },
}

SYNC_CALLS = {"jax.device_get", "np.asarray", "np.array", "numpy.asarray",
              "numpy.array"}

# calls whose result is an exported KV page block (or its raw page pytrees):
# the payload MST106 forbids pulling synchronously on the tick thread
SPILL_PRODUCER_PREFIXES = ("export_block", "export_pool_pages")

# the block-migration primitives MST108 keeps out of tick-hot functions:
# whole-request page-chain gathers/scatters (kv_transfer.py)
MIGRATION_CALLS = {"export_block", "import_block"}

# the blocking control-plane collectives MST113 keeps out of tick-hot
# functions: each is a cross-host rendezvous bounded only by the plane's
# timeout (multihost.py ControlPlane.exchange / PodControlPlane.pod_exchange,
# and the heartbeat wrappers over them)
CONTROL_PLANE_CALLS = {"exchange", "heartbeat", "pod_exchange"}

# the pod prefix-federation surface MST115 keeps out of tick-hot
# functions: fetch() blocks on a cross-host blob transfer (pod.py
# PodPrefixFederation), local_info()/host_inventory() walk the store's
# host tier under its lock. fetch/local_info only fire through a
# federation-ish receiver (dotted name mentioning "federation"/"prefix");
# host_inventory is distinctive enough to fire anywhere
PREFIX_FEDERATION_CALLS = {"fetch", "local_info"}
PREFIX_FEDERATION_HINTS = ("federation", "prefix")
PREFIX_INVENTORY_CALLS = {"host_inventory"}

# share-map calibration I/O MST115 also forbids in tick-hot functions:
# each runs dense prefills and/or whole-KV host marshalling (kv_share.py)
# — calibration is offline (cli/kv_share_calibrate.py); serving loads the
# saved artifact once at startup
SHARE_CALIBRATION_CALLS = {"calibrate_share_map", "rank_layer_pairs",
                           "layer_kv_signatures", "load_share_map"}

# the compressed-latent codec surface MST116 keeps out of tick-hot
# functions: each call is a dense (tokens, r) x (r, H*D) projection over
# every page of every layer in host numpy (kv_compress.KVCompressCodec)
LATENT_RECONSTRUCT_CALLS = {"reconstruct_block", "reconstruct_pages",
                            "compress_pages"}

# host→device upload calls MST109 polices in tick-hot functions when their
# argument is a spilled block's page payload (the demand-paged resume)
UPLOAD_CALLS = {"jax.device_put", "jnp.asarray", "jnp.array",
                "jax.numpy.asarray", "jax.numpy.array"}
# attribute names that identify a KVPageBlock's page payload, and the spill
# tier lookups whose results MST109 tracks as block-bearing names
BLOCK_PAGE_ATTRS = {"k_pages", "v_pages"}
TIER_LOOKUP_ATTRS = {"take", "peek"}

# prefix-store lookup surface MST111 tracks: a call ``<recv>.<attr>(...)``
# where the receiver's dotted name mentions "store" and the attr is one of
# these marks the assigned name as (potentially) host-block-bearing
STORE_LOOKUP_ATTRS = {"lookup", "host_block"}

# spawn-hot roots checked by MST110 (beyond '# mst: spawn-hot'
# annotations): the replica-spawn factories the fleet autoscaler calls
SPAWN_HOT_FUNCS = {
    "openai_api.py": {"replica_factory", "pool_factory", "spawn_replica"},
    "fleet.py": {"_spawn"},
}
# calls that place a param tree on device — the one real upload belongs in
# the WeightStore builder, never on the per-spawn path
WEIGHT_UPLOAD_CALLS = {"device_put", "put_global", "place_weights"}
# identifier fragments that mark a call's argument as a param tree (vs the
# KV staging a spawn legitimately does)
PARAM_TREE_HINTS = ("param", "weight", "state_dict", "checkpoint")

# speculation-policy roots checked by MST114 (beyond '# mst: spec-hot'
# annotations): the per-round draft proposal and acceptance-tracker surface.
# Host numpy is expected here (so MST102 does not apply); a device sync is
# the one thing that must never appear — it stalls the dispatch pipe once
# per draft round for a value the round's consolidated harvest already pulls
SPEC_HOT_FUNCS = {
    "scheduler.py": {"_dispatch_spec", "_spec_plan"},
    "speculative.py": {"propose", "observe", "window"},
}

# decode-hot roots checked by MST105 (beyond '# mst: decode-hot'
# annotations): every packed decode matmul funnels through these
DECODE_HOT_FUNCS = {
    "quant.py": {"linear", "_quant_matmul"},
}

# call names that materialize a dense weight tile from a packed triple
DEQUANT_CALLS = {"dequantize", "dequant"}

# shape expressions routed through these calls are considered bucketed
BUCKETING_FUNCS = {"_chunk_at", "_pages_needed", "round_up", "bucket",
                   "next_power_of_two"}

ARRAY_BUILDERS = {"zeros", "ones", "full", "empty", "arange"}


def _collect_functions(tree: ast.Module) -> dict[str, list[ast.AST]]:
    """name -> every FunctionDef/Lambda-holding def in the file (any scope)."""
    table: dict[str, list[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            table.setdefault(node.name, []).append(node)
    return table


def _callable_args(call: ast.Call) -> list[ast.AST]:
    """Positional args of a tracing entry point that name/define callables."""
    out = []
    for arg in call.args:
        if isinstance(arg, (ast.Lambda, ast.Name, ast.Attribute)):
            out.append(arg)
    return out


def _traced_roots(tree: ast.Module, table: dict) -> list[ast.AST]:
    """Function nodes handed to a tracing entry point anywhere in the file."""
    roots: list[ast.AST] = []

    def note(arg: ast.AST):
        if isinstance(arg, ast.Lambda):
            roots.append(arg)
        else:
            name = dotted_name(arg)
            if name is None:
                return
            bare = name.split(".")[-1]  # self.first_sample -> method name
            roots.extend(table.get(bare, ()))

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fname = dotted_name(node.func)
            if fname in TRACING_ENTRY_POINTS:
                for arg in _callable_args(node):
                    note(arg)
            # functools.partial(jax.jit, ...) decorator form
            if fname in ("functools.partial", "partial") and node.args:
                inner = dotted_name(node.args[0])
                if inner in TRACING_ENTRY_POINTS:
                    pass  # the decorated function is traced; handled below
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                dname = dotted_name(dec)
                if dname in TRACING_ENTRY_POINTS:
                    roots.append(node)
                elif isinstance(dec, ast.Call):
                    cname = dotted_name(dec.func)
                    if cname in TRACING_ENTRY_POINTS:
                        roots.append(node)
                    elif cname in ("functools.partial", "partial") and dec.args:
                        if dotted_name(dec.args[0]) in TRACING_ENTRY_POINTS:
                            roots.append(node)
    return roots


def _traced_closure(roots: list[ast.AST], table: dict) -> list[ast.AST]:
    """Roots plus every same-file function they (transitively) call or
    define — host effects two frames down still run at trace time."""
    seen: list[ast.AST] = []
    work = list(roots)
    while work:
        fn = work.pop()
        if any(fn is s for s in seen):
            continue
        seen.append(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = dotted_name(node.func)
                if name is None:
                    continue
                bare = name.split(".")[-1]
                if bare in table:
                    work.extend(table[bare])
    return seen


def _check_host_effects(mod: ModuleInfo, traced: list[ast.AST]) -> list[Finding]:
    findings = []

    def flag(node, what):
        findings.append(Finding(
            "MST101", mod.display_path, node.lineno, node.col_offset,
            f"host effect in jit-traced code: {what} runs once at trace "
            "time, not per step",
            context=qualname_for_line(mod.tree, node.lineno),
        ))

    for fn in traced:
        globals_declared: set[str] = set()
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for node in ast.walk(ast.Module(body=list(body), type_ignores=[])):
            if isinstance(node, ast.Global):
                globals_declared.update(node.names)
            if isinstance(node, ast.Call):
                name = dotted_name(node.func)
                if name is None:
                    continue
                if name in HOST_CLOCKS:
                    flag(node, f"{name}()")
                elif name == "print":
                    flag(node, "print() (use jax.debug.print for traced "
                         "values)")
                elif any(name.startswith(root) for root in HOST_RANDOM_ROOTS):
                    flag(node, f"{name}() (use jax.random with an explicit "
                         "key)")
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for t in targets:
                    if isinstance(t, ast.Name) and t.id in globals_declared:
                        flag(node, f"mutation of global {t.id!r}")
    return findings


def _hot_functions(mod: ModuleInfo) -> list[ast.FunctionDef]:
    configured = HOT_PATH_FUNCS.get(mod.basename, set())
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        annotated = any(
            line in mod.hot_lines
            for line in (node.lineno, node.lineno - 1)
        )
        if node.name in configured or annotated:
            out.append(node)
    return out


def _check_hot_syncs(mod: ModuleInfo) -> list[Finding]:
    findings = []
    for fn in _hot_functions(mod):
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host hot-path code
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            what = None
            if name in SYNC_CALLS:
                what = f"{name}()"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in ("item", "block_until_ready")
                and not node.args
            ):
                what = f".{node.func.attr}()"
            if what:
                findings.append(Finding(
                    "MST102", mod.display_path, node.lineno, node.col_offset,
                    f"blocking device sync in hot path {fn.name}(): {what} "
                    "stalls the tick for a device round trip",
                    context=qualname_for_line(mod.tree, node.lineno),
                ))
    return findings


def _spec_hot_functions(mod: ModuleInfo) -> list[ast.FunctionDef]:
    configured = SPEC_HOT_FUNCS.get(mod.basename, set())
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        annotated = any(
            line in mod.spec_hot_lines
            for line in (node.lineno, node.lineno - 1)
        )
        if node.name in configured or annotated:
            out.append(node)
    return out


def _check_spec_policy_syncs(mod: ModuleInfo) -> list[Finding]:
    """MST114: a blocking device sync inside the speculation policy
    surface. Narrower than MST102 on purpose — proposal/tracker code is
    host numpy by design (``np.asarray`` over the request's history IS the
    n-gram match), so only the true device round trips fire:
    ``jax.device_get`` and argless ``.item()``."""
    findings = []
    for fn in _spec_hot_functions(mod):
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host policy code
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            what = None
            if name is not None and name.split(".")[-1] == "device_get":
                what = f"{name}()"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "item" and not node.args
            ):
                what = ".item()"
            if what:
                findings.append(Finding(
                    "MST114", mod.display_path, node.lineno, node.col_offset,
                    f"device sync in speculation policy {fn.name}(): {what} "
                    "drains the dispatch pipe once per draft round — the "
                    "proposal/tracker surface reads host state only; device "
                    "results arrive at the round's consolidated harvest",
                    context=qualname_for_line(mod.tree, node.lineno),
                ))
    return findings


def _check_double_harvest(mod: ModuleInfo) -> list[Finding]:
    """MST104: more than one ``jax.device_get`` in a tick-hot function.
    The pipelined scheduler loop must keep exactly one harvest point —
    consolidate extra pulls into the first one's tuple pytree."""
    findings = []
    for fn in _hot_functions(mod):
        first = None
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host hot-path code
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) != "jax.device_get":
                continue
            if first is None:
                first = node
                continue
            findings.append(Finding(
                "MST104", mod.display_path, node.lineno, node.col_offset,
                f"second device_get in hot path {fn.name}() (first at line "
                f"{first.lineno}): consolidate into one harvest — pass a "
                "tuple pytree and unpack host-side",
                context=qualname_for_line(mod.tree, node.lineno),
            ))
    return findings


def _is_spill_producer(node: ast.Call) -> bool:
    name = dotted_name(node.func)
    return bool(name) and name.split(".")[-1].startswith(
        SPILL_PRODUCER_PREFIXES
    )


def _check_sync_spill(mod: ModuleInfo) -> list[Finding]:
    """MST106: a synchronous pull of an exported KV page block inside a
    tick-hot function. Matches a ``SYNC_CALLS`` call (or ``.to_host()``)
    whose argument/receiver subtree is a spill-producer call or a name
    assigned from one earlier in the same function — the spill discipline
    is dispatch-the-gather-on-tick, copy-on-flusher (kv_transfer)."""
    findings = []
    for fn in _hot_functions(mod):
        block_names: set[str] = set()
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and _is_spill_producer(node.value)):
                for t in node.targets:
                    tname = dotted_name(t)
                    if tname:
                        block_names.add(tname.split(".")[-1])
                    elif isinstance(t, ast.Tuple):
                        for elt in t.elts:
                            ename = dotted_name(elt)
                            if ename:
                                block_names.add(ename.split(".")[-1])
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host hot-path code
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in SYNC_CALLS:
                subjects = list(node.args)
                what = f"{name}()"
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "to_host" and not node.args):
                subjects = [node.func.value]
                what = ".to_host()"
            else:
                continue
            touches_block = any(
                (isinstance(sub, ast.Call) and _is_spill_producer(sub))
                or (isinstance(sub, ast.Name) and sub.id in block_names)
                for subject in subjects
                for sub in ast.walk(subject)
            )
            if touches_block:
                findings.append(Finding(
                    "MST106", mod.display_path, node.lineno, node.col_offset,
                    f"synchronous spill copy in hot path {fn.name}(): "
                    f"{what} pulls a full exported KV page block, stalling "
                    "every live slot's decode — dispatch the gather here "
                    "and leave the device→host copy to the spill tier's "
                    "flusher thread",
                    context=qualname_for_line(mod.tree, node.lineno),
                ))
    return findings


def _check_block_migration(mod: ModuleInfo) -> list[Finding]:
    """MST108: an ``export_block``/``import_block`` call inside a tick-hot
    function. The handoff/spill discipline parks the request on the tick
    and runs the migration from a non-hot helper (``_handoff_out``,
    admission-side ``_import_block``) or the spill flusher — a page-chain
    gather/scatter inline in the tick stalls every live slot's decode.
    An MST102/MST106 suppression on a nearby sync does NOT cover this
    rule; an intentional inline migration carries its own
    ``# mst: allow(MST108): …``."""
    findings = []
    for fn in _hot_functions(mod):
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host hot-path code
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is None or name.split(".")[-1] not in MIGRATION_CALLS:
                continue
            findings.append(Finding(
                "MST108", mod.display_path, node.lineno, node.col_offset,
                f"KV block migration in hot path {fn.name}(): "
                f"{name.split('.')[-1]}() gathers/scatters a whole page "
                "chain per request — park the request on the tick and run "
                "the migration from a non-hot helper or the flusher thread",
                context=qualname_for_line(mod.tree, node.lineno),
            ))
    return findings


def _check_control_plane_in_tick(mod: ModuleInfo) -> list[Finding]:
    """MST113: a blocking control-plane collective (``exchange`` /
    ``heartbeat`` / ``pod_exchange``) inside a tick-hot function. A
    collective is a cross-host rendezvous: it returns when the SLOWEST
    host arrives, or after the plane's timeout (seconds to minutes) when
    one never does — so one call inline in the tick wedges every live
    slot's decode behind a peer's GC pause, and a dead peer freezes the
    whole fleet for the full timeout instead of one heartbeat thread. The
    pod discipline runs every collective on its own daemon thread
    (``mst-pod-transport``) and lets the tick read the gossiped snapshot;
    an intentional inline rendezvous carries its own
    ``# mst: allow(MST113): …``."""
    findings = []
    for fn in _hot_functions(mod):
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host hot-path code
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is None or "." not in name:
                continue  # bare exchange()/heartbeat() locals are not the
                # plane surface — the collective always rides a plane object
            if name.split(".")[-1] not in CONTROL_PLANE_CALLS:
                continue
            findings.append(Finding(
                "MST113", mod.display_path, node.lineno, node.col_offset,
                f"blocking control-plane collective in hot path "
                f"{fn.name}(): {name}() is a cross-host rendezvous bounded "
                "only by the plane timeout — run it on the pod transport "
                "thread and let the tick read the gossiped snapshot",
                context=qualname_for_line(mod.tree, node.lineno),
            ))
    return findings


def _check_prefix_federation_in_tick(mod: ModuleInfo) -> list[Finding]:
    """MST115: a pod prefix-federation consult or share-map calibration
    I/O inside a tick-hot function. ``federation.fetch()`` blocks on a
    cross-host blob transfer bounded only by its timeout; an inventory
    walk serializes against the store's flusher lock; calibration runs
    dense prefills plus whole-KV host marshalling. The discipline: the
    non-hot waiting-queue pass (``_pod_fetch_waiting``) starts the fetch
    on its own daemon thread and admission only reads the per-request
    flag; calibration is offline (``cli/kv_share_calibrate.py``)."""
    findings = []
    for fn in _hot_functions(mod):
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host hot-path code
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is None:
                continue
            last = name.split(".")[-1]
            if last in SHARE_CALIBRATION_CALLS:
                why = (f"share-map calibration I/O in hot path {fn.name}(): "
                       f"{name}() runs dense prefills / whole-KV host "
                       "marshalling — calibrate offline "
                       "(cli/kv_share_calibrate.py) and load the saved "
                       "artifact once at startup")
            elif last in PREFIX_INVENTORY_CALLS or (
                "." in name
                and last in PREFIX_FEDERATION_CALLS
                and any(h in seg for seg in name.split(".")[:-1]
                        for h in PREFIX_FEDERATION_HINTS)
            ):
                why = (f"pod prefix-federation call in hot path {fn.name}(): "
                       f"{name}() blocks on a cross-host blob fetch / "
                       "store-lock inventory walk — start the fetch from the "
                       "waiting-queue pass on its own thread and let "
                       "admission read the per-request flag")
            else:
                continue
            findings.append(Finding(
                "MST115", mod.display_path, node.lineno, node.col_offset,
                why, context=qualname_for_line(mod.tree, node.lineno),
            ))
    return findings


def _check_latent_reconstruct_in_tick(mod: ModuleInfo) -> list[Finding]:
    """MST116: a compressed-latent KV codec call inside a tick-hot
    function. ``reconstruct_block()``/``reconstruct_pages()`` materialize
    the dense per-head pages from rank-r latents — a ``(tokens, r) @
    (r, H*D)`` host-numpy up-projection over every page of every layer —
    and ``compress_pages()`` is its transpose. The discipline: compress
    in ``to_host`` on the flusher/handoff threads, reconstruct in
    ``prefetch``'s overlapped stage or the consumer's non-hot import
    path; the tick only ever touches already-dense pages."""
    findings = []
    for fn in _hot_functions(mod):
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host hot-path code
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is None:
                continue
            if name.split(".")[-1] not in LATENT_RECONSTRUCT_CALLS:
                continue
            findings.append(Finding(
                "MST116", mod.display_path, node.lineno, node.col_offset,
                f"latent reconstruction in hot path {fn.name}(): {name}() "
                "materializes dense per-head pages from rank-r latents in "
                "host numpy — compress in to_host on the flusher/handoff "
                "threads, reconstruct in prefetch's overlapped stage or "
                "the consumer's import path, never on the tick thread",
                context=qualname_for_line(mod.tree, node.lineno),
            ))
    return findings


def _spawn_hot_functions(mod: ModuleInfo) -> list[ast.FunctionDef]:
    configured = SPAWN_HOT_FUNCS.get(mod.basename, set())
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        annotated = any(
            line in mod.spawn_hot_lines
            for line in (node.lineno, node.lineno - 1)
        )
        if node.name in configured or annotated:
            out.append(node)
    return out


def _check_spawn_weight_upload(mod: ModuleInfo) -> list[Finding]:
    """MST110: a full param-tree placement inside a spawn-hot function.
    Non-transitive by design — the sanctioned path hands a builder callable
    to ``WeightStore.acquire`` (the upload runs once, inside the store, not
    per spawn), and that callable's own body is where ``place_weights``
    belongs. Only the factory's DIRECT body is scanned, and only calls
    whose arguments name param-ish data fire, so a factory staging KV or
    slot state stays clean."""
    findings = []
    for fn in _spawn_hot_functions(mod):
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs (incl. the store's builder) are exempt
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is None or name.split(".")[-1] not in WEIGHT_UPLOAD_CALLS:
                continue
            idents: set[str] = set()
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name):
                        idents.add(sub.id.lower())
                    elif isinstance(sub, ast.Attribute):
                        idents.add(sub.attr.lower())
            if not any(h in ident for ident in idents
                       for h in PARAM_TREE_HINTS):
                continue
            findings.append(Finding(
                "MST110", mod.display_path, node.lineno, node.col_offset,
                f"param-tree upload in spawn-hot {fn.name}(): "
                f"{name.split('.')[-1]}(...) re-places the checkpoint on "
                "every spawn — alias the host's resident tree through "
                "WeightStore.acquire and leave the one real upload to the "
                "store's builder",
                context=qualname_for_line(mod.tree, node.lineno),
            ))
    return findings


def _check_dense_dequant(mod: ModuleInfo, table: dict) -> list[Finding]:
    """MST105: a dense dequantized-weight materialization reachable from a
    decode-hot function. Roots come from ``DECODE_HOT_FUNCS`` (by basename)
    and ``# mst: decode-hot`` annotations; reachability is the same
    same-file call closure the trace rules use. Only a dequant call bound
    by an assignment fires — a dequant expression consumed in place inside
    a kernel body never appears here, because kernel bodies are passed to
    ``pallas_call`` rather than called by name."""
    roots: list[ast.AST] = []
    configured = DECODE_HOT_FUNCS.get(mod.basename, set())
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        annotated = any(
            line in mod.decode_hot_lines
            for line in (node.lineno, node.lineno - 1)
        )
        if node.name in configured or annotated:
            roots.append(node)
    findings = []
    for fn in _traced_closure(roots, table):
        for node in ast.walk(fn):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            if not isinstance(node.value, ast.Call):
                continue
            name = dotted_name(node.value.func)
            if name is None or name.split(".")[-1] not in DEQUANT_CALLS:
                continue
            fname = getattr(fn, "name", "<lambda>")
            findings.append(Finding(
                "MST105", mod.display_path, node.lineno, node.col_offset,
                f"dense dequantized weight materialized in decode-hot "
                f"{fname}(): {name}(...) rebuilds the full-precision tile "
                "in HBM every step — fuse the dequant into the kernel or "
                "justify the guarded fallback",
                context=qualname_for_line(mod.tree, node.lineno),
            ))
    return findings


def _jitted_names(tree: ast.Module) -> set[str]:
    """Names (locals and self.attrs) bound to a jax.jit(...) result."""
    names: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if dotted_name(node.value.func) in ("jax.jit", "jit", "pjit",
                                                "jax.pjit"):
                for t in node.targets:
                    name = dotted_name(t)
                    if name:
                        names.add(name)
    return names


def _dynamic_shape(expr: ast.AST) -> bool:
    """Does ``expr`` derive from request data sizes (len/.size/.shape[..])
    without passing through a bucketing helper?"""
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            name = dotted_name(node.func)
            if name == "len":
                return True
            if name and name.split(".")[-1] in BUCKETING_FUNCS:
                return False  # routed through bucketing: fine
        if isinstance(node, ast.Attribute) and node.attr == "size":
            return True
        if (
            isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Attribute)
            and node.value.attr == "shape"
        ):
            return True
    return False


def _check_sync_import(mod: ModuleInfo) -> list[Finding]:
    """MST109: a demand-paged KV block upload inside a tick-hot function.
    Matches an ``UPLOAD_CALLS`` call whose argument subtree touches a
    block's page payload (``.k_pages``/``.v_pages``) or a name assigned
    from a spill-tier lookup (``.take()``/``.peek()``) earlier in the same
    function — the resume discipline is prefetch-on-schedule (overlapped
    with decode), demand import only as a counted off-tick fallback."""
    findings = []
    for fn in _hot_functions(mod):
        block_names: set[str] = set()
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr in TIER_LOOKUP_ATTRS):
                for t in node.targets:
                    tname = dotted_name(t)
                    if tname:
                        block_names.add(tname.split(".")[-1])
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host hot-path code
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name not in UPLOAD_CALLS:
                continue
            touches_block = any(
                (isinstance(sub, ast.Attribute)
                 and sub.attr in BLOCK_PAGE_ATTRS)
                or (isinstance(sub, ast.Name) and sub.id in block_names)
                for arg in node.args
                for sub in ast.walk(arg)
            )
            if touches_block:
                findings.append(Finding(
                    "MST109", mod.display_path, node.lineno, node.col_offset,
                    f"demand-paged KV import in hot path {fn.name}(): "
                    f"{name}() marshals a spilled block's host pages inline, "
                    "stalling every live slot's decode for the full "
                    "host→device copy — stage the block with "
                    "KVPageBlock.prefetch() when the slot is scheduled to "
                    "rejoin (the copy overlaps the current block's compute) "
                    "and keep demand import off the tick as a counted "
                    "fallback",
                    context=qualname_for_line(mod.tree, node.lineno),
                ))
    return findings


def _check_store_import(mod: ModuleInfo) -> list[Finding]:
    """MST111: a prefix-store host block uploaded inside a tick-hot
    function. MST109-shaped, but tracking the store's lookup surface
    (``<...store...>.lookup()`` / ``.host_block()``) instead of the spill
    tier's ``take``/``peek`` — the admission discipline stages the block
    via the non-hot waiting-queue prefetch pass and keeps demand import
    off the tick as a counted fallback."""
    findings = []
    for fn in _hot_functions(mod):
        block_names: set[str] = set()
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr in STORE_LOOKUP_ATTRS):
                continue
            recv = dotted_name(node.value.func.value)
            if recv is None or "store" not in recv.lower():
                continue
            for t in node.targets:
                tname = dotted_name(t)
                if tname:
                    block_names.add(tname.split(".")[-1])
        if not block_names:
            continue
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                break  # nested defs are jit bodies; not host hot-path code
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name not in UPLOAD_CALLS:
                continue
            touches_block = any(
                isinstance(sub, ast.Name) and sub.id in block_names
                for arg in node.args
                for sub in ast.walk(arg)
            )
            if touches_block:
                findings.append(Finding(
                    "MST111", mod.display_path, node.lineno, node.col_offset,
                    f"prefix-store import in hot path {fn.name}(): "
                    f"{name}() marshals a store-held host block inline, "
                    "stalling every live slot's decode for the full "
                    "host→device copy — stage it with KVPageBlock.prefetch() "
                    "from the waiting-queue pass (the copy overlaps decode) "
                    "and keep demand import off the tick as a counted "
                    "fallback",
                    context=qualname_for_line(mod.tree, node.lineno),
                ))
    return findings


def _check_recompile_hazards(mod: ModuleInfo) -> list[Finding]:
    jitted = _jitted_names(mod.tree)
    if not jitted:
        return []
    findings = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = dotted_name(node.func)
        if callee not in jitted:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in ast.walk(arg):
                if not isinstance(sub, ast.Call):
                    continue
                bname = dotted_name(sub.func)
                if bname is None:
                    continue
                parts = bname.split(".")
                if parts[-1] not in ARRAY_BUILDERS or len(parts) < 2:
                    continue
                if sub.args and _dynamic_shape(sub.args[0]):
                    findings.append(Finding(
                        "MST103", mod.display_path, sub.lineno,
                        sub.col_offset,
                        f"data-dependent shape at jitted call site "
                        f"{callee}(): {bname} sized from request data "
                        "recompiles per distinct length — route through a "
                        "bucketing helper",
                        context=qualname_for_line(mod.tree, sub.lineno),
                    ))
    return findings


# MST112: receivers that mark a call as tracing work, and the guard test —
# a hot function may touch the tracer only behind a no-op check that
# branches on one of these identifiers (the `if tr is not None:` pattern)
TRACE_RECEIVER_NAMES = {"tr", "_tr", "tracer", "_tracer", "tracing"}


def _trace_ident(ident: str) -> bool:
    low = ident.lower()
    return low in TRACE_RECEIVER_NAMES or "trace" in low


def _is_trace_guard(test: ast.AST) -> bool:
    """Does this If/IfExp test branch on a trace-ish identifier?"""
    for n in ast.walk(test):
        if isinstance(n, ast.Name) and _trace_ident(n.id):
            return True
        if isinstance(n, ast.Attribute) and _trace_ident(n.attr):
            return True
    return False


def _is_trace_call(node: ast.Call) -> bool:
    """A call whose RECEIVER path is trace-ish: ``tr.add(...)``,
    ``req._trace.point(...)``, ``tracing.bind(...)`` — but not a bare
    function that merely mentions trace in its own name."""
    name = dotted_name(node.func)
    if name is None:
        return False
    parts = name.split(".")
    return len(parts) > 1 and any(_trace_ident(p) for p in parts[:-1])


def _check_hot_trace_overhead(mod: ModuleInfo) -> list[Finding]:
    """MST112: tracing work in a tick-hot function outside the no-op
    guard. Walks each hot function with a guarded flag that turns on
    inside any If/IfExp whose test branches on a trace-ish identifier
    (both branches count — ``if tr is None: ... else: record`` is as valid
    as the positive form). ``time.perf_counter()`` is never flagged; the
    wall clock (``time.time()``) is, as hot-path timestamping."""
    findings = []

    def flag(node: ast.Call, what: str, fname: str):
        findings.append(Finding(
            "MST112", mod.display_path, node.lineno, node.col_offset,
            f"unguarded trace work in hot path {fname}(): {what} runs its "
            "marshalling and lock traffic on every decode block even with "
            "tracing off — bind the handle once (tr = req._trace) and gate "
            "span construction behind its `if tr is not None:` no-op check "
            "(timestamp with time.perf_counter, not time.time)",
            context=qualname_for_line(mod.tree, node.lineno),
        ))

    def scan(node: ast.AST, fn: ast.AST, guarded: bool):
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node is not fn):
            return  # nested defs are jit bodies; not host hot-path code
        if isinstance(node, ast.Call) and not guarded:
            name = dotted_name(node.func)
            if name == "time.time":
                flag(node, "time.time()", fn.name)
            elif _is_trace_call(node):
                flag(node, f"{name}(...)", fn.name)
        if isinstance(node, (ast.If, ast.IfExp)):
            g = guarded or _is_trace_guard(node.test)
            # the test expression itself still runs unconditionally — a
            # call there is not protected by its own branch
            scan(node.test, fn, guarded)
            body = node.body if isinstance(node, ast.If) else [node.body]
            orelse = (node.orelse if isinstance(node, ast.If)
                      else [node.orelse])
            for child in body + orelse:
                scan(child, fn, g)
            return
        for child in ast.iter_child_nodes(node):
            scan(child, fn, guarded)

    for fn in _hot_functions(mod):
        for child in ast.iter_child_nodes(fn):
            scan(child, fn, False)
    return findings


# MST107: the wall clock spellings that must never feed a deadline, and the
# identifier fragments that mark an expression as deadline/timeout math
WALL_CLOCK_CALLS = {"time.time", "_time.time"}
# the monotonic spellings that bypass an INJECTED clock: only flagged
# inside classes that carry one (see _clocked_class_ranges) — a raw
# monotonic read there makes virtual-time tests pass while the shipped
# binary runs on a different clock
MONOTONIC_CALLS = {"time.monotonic", "_time.monotonic"}
DEADLINE_HINTS = (
    "deadline", "timeout", "expires", "expiry", "expire", "until",
    "budget", "retry_after", "ttft", "lease",
)


def _clocked_class_ranges(tree: ast.AST) -> list[tuple[int, int]]:
    """Line ranges of ClassDefs that reference an injectable clock
    attribute (``self.clock`` / ``self._clock``): inside these, deadline
    arithmetic must read the injected source, never ``time.monotonic()``
    directly."""
    ranges = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        for n in ast.walk(node):
            if (isinstance(n, ast.Attribute)
                    and n.attr in ("clock", "_clock")
                    and isinstance(n.value, ast.Name)
                    and n.value.id == "self"):
                ranges.append((node.lineno, node.end_lineno or node.lineno))
                break
    return ranges


def _check_wall_clock_deadlines(mod: ModuleInfo) -> list[Finding]:
    # context = the smallest statement (or branch condition) around the
    # call; if any identifier in it smells like a deadline, the wall clock
    # is feeding timeout arithmetic
    contexts: list[ast.AST] = []
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign,
                             ast.Return, ast.Expr, ast.Assert, ast.Raise)):
            contexts.append(node)
        elif isinstance(node, (ast.While, ast.If)):
            contexts.append(node.test)
    clocked = _clocked_class_ranges(mod.tree)

    def in_clocked_class(line: int) -> bool:
        return any(lo <= line <= hi for lo, hi in clocked)

    findings = []
    seen: set[tuple[int, int]] = set()
    for ctx in contexts:
        wall_calls, mono_calls = [], []
        for n in ast.walk(ctx):
            if not isinstance(n, ast.Call):
                continue
            name = dotted_name(n.func)
            if name in WALL_CLOCK_CALLS:
                wall_calls.append(n)
            elif name in MONOTONIC_CALLS and in_clocked_class(n.lineno):
                mono_calls.append(n)
        if not wall_calls and not mono_calls:
            continue
        idents: set[str] = set()
        for n in ast.walk(ctx):
            if isinstance(n, ast.Name):
                idents.add(n.id.lower())
            elif isinstance(n, ast.Attribute):
                idents.add(n.attr.lower())
        idents -= {"time", "_time"}  # the call itself is not evidence
        if not any(h in ident for ident in idents for h in DEADLINE_HINTS):
            continue
        for call, msg in (
            [(c, "time.time() feeding deadline/timeout arithmetic — the "
                 "wall clock steps/slews under NTP, so the deadline can "
                 "fire early or never; use time.monotonic()")
             for c in wall_calls]
            + [(c, "raw time.monotonic() feeding deadline arithmetic in a "
                   "class that carries an injectable clock — it bypasses "
                   "the injected time source, so virtual-clock tests and "
                   "the fleet simulator diverge from the shipped binary; "
                   "read self.clock()/self._clock() instead")
               for c in mono_calls]
        ):
            key = (call.lineno, call.col_offset)
            if key in seen:
                continue
            seen.add(key)
            findings.append(Finding(
                "MST107", mod.display_path, call.lineno, call.col_offset,
                msg, context=qualname_for_line(mod.tree, call.lineno)))
    return findings


def check_module(mod: ModuleInfo) -> list[Finding]:
    table = _collect_functions(mod.tree)
    traced = _traced_closure(_traced_roots(mod.tree, table), table)
    findings = _check_host_effects(mod, traced)
    findings += _check_hot_syncs(mod)
    findings += _check_spec_policy_syncs(mod)
    findings += _check_double_harvest(mod)
    findings += _check_sync_spill(mod)
    findings += _check_block_migration(mod)
    findings += _check_control_plane_in_tick(mod)
    findings += _check_prefix_federation_in_tick(mod)
    findings += _check_latent_reconstruct_in_tick(mod)
    findings += _check_sync_import(mod)
    findings += _check_store_import(mod)
    findings += _check_hot_trace_overhead(mod)
    findings += _check_spawn_weight_upload(mod)
    findings += _check_recompile_hazards(mod)
    findings += _check_dense_dequant(mod, table)
    findings += _check_wall_clock_deadlines(mod)
    return findings
