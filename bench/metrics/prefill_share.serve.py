"""prefill_share.serve: of the slot-ticks in which a slot of the
``ContinuousBatcher`` was occupied, the share that fed a prompt token, in
%, over the whole window.  Counted by the harness from the batcher's slots
at each call of the serve step.  Moves ``ttft_p95_ms``."""


def read(ctx):
    occupied = ctx.counters.get("occupied_slot_ticks")
    if not occupied:
        return None
    return 100.0 * ctx.counters["prefill_slot_ticks"] / occupied
