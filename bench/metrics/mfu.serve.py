"""mfu.serve: the whole decode tick's share of its roofline on this chip,
in %, over the traced ticks.  A tick's least time is the larger of the
model FLOPs of the tokens it feeds over the peak FLOP/s and of the bytes it
must read (every weight, and the live KV rows of the occupied slots) over
the HBM bandwidth (``bench.peaks.decode_tick_least_s``); the share is their
sum over the device's busy time in the trace.  Moves ``tpot_p95_ms``."""


def read(ctx):
    red = ctx.reduced
    if red is None or not ctx.counters.get("traced_ticks") \
            or red.busy_s <= 0:
        return None
    return 100.0 * ctx.counters["traced_least_s"] / red.busy_s
