"""Central resource registry: every refcounted handle the stack hands out.

The serving layer runs on acquire/release pairs — ``WeightStore`` leases,
``PrefixStore`` COW leases, breaker probe tickets, slot/page allocations,
``KVSpillTier`` blocks, fault-site arms, tracing binds. The same bug class
(release missing on ONE exit path) kept escaping to review: the PR-3 probe
ticket not returned on ``ValueError``/``QueueFullError`` exits, leases that
must release "exactly once through drain/close/fault paths", demote-on-
last-release ordering. This registry is the single source of truth both
checkers read:

- the **static** MST40x verifier (:mod:`.resource_lifecycle`) uses the
  ``static`` specs to recognize acquire/release calls in the AST and run
  its path-sensitive must-release analysis;
- the **runtime** leak ledger (:mod:`.runtime` ``instrument_resources()``)
  tracks the ``RUNTIME_KINDS`` below as live-handle sets under a real
  composed workload and asserts zero live handles at teardown.

Adding a new handle type means adding a spec here — both checkers pick it
up without touching their engines.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class ResourceSpec:
    """One handle type's acquire/release vocabulary.

    ``acquire`` / ``release`` name the *bare* (last-dotted-component) call
    names. ``receiver_hints`` narrows acquire matching: the dotted receiver
    of the call must contain one of the substrings (``store.acquire`` is a
    lease; ``self._lock.acquire`` is not). ``receiver_blocklist`` rejects
    receivers outright (lock objects). ``handle_pos`` selects which element
    of a tuple-unpacked acquire result is the handle (``i, probe =
    self._pick(...)`` → position 1, the probe ticket). ``release_as_arg``
    marks release calls that take the handle as an argument
    (``self._done(i, probe)``) rather than as the receiver
    (``lease.release()``). ``cm`` marks acquires that are safe as ``with``
    context expressions (auto-released by ``__exit__``).
    """

    kind: str                       # "weights.lease"
    module: str                     # owning module (docs + registry table)
    acquire: tuple = ()
    release: tuple = ()
    receiver_hints: tuple = ()      # substrings; () = any receiver
    receiver_blocklist: tuple = ("lock", "mutex", "cond", "sem")
    handle_pos: Optional[int] = None
    release_as_arg: bool = False
    cm: bool = False                # acquire usable as a `with` context
    static: bool = True             # tracked by the MST40x verifier
    escape_attrs: tuple = ()        # doc-only: where handles legally live
    notes: str = ""


# --------------------------------------------------------------- registry
REGISTRY: tuple = (
    ResourceSpec(
        kind="weights.lease",
        module="weights.py",
        acquire=("acquire",),
        release=("release",),
        receiver_hints=("store", "weight"),
        escape_attrs=("engine._weight_lease",),
        notes="refcounted device-resident packed param tree; released "
        "exactly once via engine close()/drain/fault paths (PR 11)",
    ),
    ResourceSpec(
        kind="prefix.lease",
        module="prefix_store.py",
        acquire=("register",),
        release=("release",),
        receiver_hints=("store", "prefix"),
        escape_attrs=("req._please",),
        notes="COW claim on shared prefix KV pages; LAST release demotes "
        "the entry to the host tier (PR 12 ordering)",
    ),
    ResourceSpec(
        kind="replica.probe",
        module="replicas.py",
        acquire=("_pick",),
        release=("_done",),
        handle_pos=1,
        release_as_arg=True,
        notes="half-open breaker probe ticket; must come back on EVERY "
        "exit path or the replica can never be probed again (PR 3)",
    ),
    ResourceSpec(
        kind="faults.arm",
        module="testing/faults.py",
        acquire=("arm",),
        release=("disarm",),
        static=False,  # disarm is site-keyed, not handle-keyed
        notes="armed fault site; a test that forgets disarm() poisons "
        "every later test in the process",
    ),
    ResourceSpec(
        kind="tracing.bind",
        module="tracing.py",
        acquire=("bind",),
        release=(),
        receiver_hints=("tracing",),
        cm=True,
        notes="TLS trace binding; context-manager only — a dangling bind "
        "attributes spans to the wrong request",
    ),
    ResourceSpec(
        kind="tier.block",
        module="kv_transfer.py",
        acquire=("put",),
        release=("take", "drop", "clear"),
        static=False,  # put/take are tier-side ownership moves, not
        # caller-held handles; the runtime ledger tracks residency
        notes="host-DRAM spill-tier residency; close()/clear() must empty "
        "the tier or exported KV outlives every consumer",
    ),
    ResourceSpec(
        kind="scheduler.slot",
        module="scheduler.py",
        acquire=(),
        release=(),
        static=False,  # slots move through self._slots[] — attribute
        # state the runtime ledger tracks at its 3 fill / 6 clear sites
        notes="continuous-batcher slot occupancy; freed through _finish/"
        "_preempt/_suspend/_fail_all/close",
    ),
    ResourceSpec(
        kind="scheduler.page",
        module="page_pool.py",
        acquire=("take",),
        release=("unref", "release", "reset"),
        static=False,  # a page is held by COUNT (slots mapping it + an
        # index/store entry), given back by whoever holds it last and
        # named by slot as often as by page: no one handle for MST40x to
        # follow. take() pops in one place (MST302 covers it) and the
        # ledger balances it against unref's returns
        notes="KV pool page; PagePool counts slot claims + index/store "
        "entry claims; every take must return via the free list",
    ),
)

# kinds the runtime ledger tracks (everything; static-only specs none)
RUNTIME_KINDS: tuple = tuple(s.kind for s in REGISTRY)

# specs the static verifier drives its dataflow from
STATIC_SPECS: tuple = tuple(s for s in REGISTRY if s.static and s.acquire)


# --------------------------------------------- static-analysis vocabulary
# Calls treated as non-raising when deciding whether a live handle can
# leak on an exception edge (MST401). Counters, logging and cheap builtins
# dominate acquire→escape windows in the real tree; treating them as
# raising would drown the signal in "if this counter bump raised" paths.
NONRAISING_PREFIXES = (
    "count_", "note_", "_note_", "log", "debug", "info", "warning", "error",
    "exception", "append", "extend", "add", "discard", "touch",
    "move_to_end",
)
NONRAISING_NAMES = frozenset({
    "len", "int", "float", "str", "bool", "list", "tuple", "set", "dict",
    "min", "max", "sum", "sorted", "range", "enumerate", "zip", "id",
    "isinstance", "getattr", "hasattr", "repr", "format", "print",
    "perf_counter", "monotonic", "time", "get", "items", "keys", "values",
    "current", "point", "inject",
})


def is_nonraising(bare_name: str) -> bool:
    """Heuristic: ``bare_name`` (last dotted component) never raises in
    practice, so a live handle crossing it is not an MST401 leak path."""
    return (bare_name in NONRAISING_NAMES
            or bare_name.startswith(NONRAISING_PREFIXES))


def match_acquire(bare_name: str, receiver: Optional[str]) -> Optional[ResourceSpec]:
    """The spec whose acquire vocabulary matches a call, or None.

    ``receiver`` is the dotted receiver ("store", "self._lock") or None
    for bare-name calls.
    """
    recv = (receiver or "").lower()
    for spec in STATIC_SPECS:
        if bare_name not in spec.acquire:
            continue
        if any(b in recv for b in spec.receiver_blocklist):
            continue
        if spec.receiver_hints and not any(h in recv for h in spec.receiver_hints):
            continue
        return spec
    return None


def match_release(bare_name: str) -> Optional[ResourceSpec]:
    """The spec whose release vocabulary matches ``bare_name``, or None."""
    for spec in STATIC_SPECS:
        if bare_name in spec.release:
            return spec
    return None


# ------------------------------------------------------- registry table
def registry_table() -> list:
    """Rows for the README resource-registry table and ``--format json``
    consumers: (kind, module, acquire, release, static, notes)."""
    return [
        {
            "kind": s.kind,
            "module": s.module,
            "acquire": list(s.acquire),
            "release": list(s.release),
            "static": s.static,
            "notes": s.notes,
        }
        for s in REGISTRY
    ]
