"""Scheduler: of the positions the window's harvested decode blocks had
computed for streams that were still wanted, the share that reached one,
percent: window delta of ``mst_decode_tokens_emitted_total`` over emitted +
dropped (``mst_decode_tokens_dropped_total``, reasons ``slot_finished`` and
``abandoned_block``). What is missing from 100 was computed past a slot's
last token (the rest of its block and its whole lookahead block) or in a
block that was thrown away. Positions dropped because the consumer left
(``cancelled``) are the client's doing and are left out: a closed-loop cell
cuts its 16 streams when the window ends. Emitted and dropped are both
counted at the harvest, so a window's edge cannot split a block between them
(positions computed are counted at dispatch, a block earlier: over a window
that ratio moves by a block's 128 positions either way)."""
from benchmarks import tick_counters


def read(ctx):
    tick_counters.print_account(ctx)
    emitted = tick_counters.total(ctx, "mst_decode_tokens_emitted_total")
    dropped = tick_counters.delta(ctx, "mst_decode_tokens_dropped_total")
    if emitted is None or dropped is None:
        return None
    lost = sum(v for reason, v in dropped.items() if reason != "cancelled")
    return 100.0 * emitted / (emitted + lost) if emitted + lost > 0 else None
