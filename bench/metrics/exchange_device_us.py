"""exchange_device_us: device time per exchange, in us, of the XLA
collective ops and of the ops under the factorized schedule's
``a2a_round[*]`` scopes, over the exchanges of the traced window, mean over
the chips.  Moves ``exchange_us``."""

from bench.reduce import is_exchange_op


def read(ctx):
    red, n = ctx.reduced, ctx.counters.get("exchanges_traced")
    if red is None or not n:
        return None
    t = red.time_where(is_exchange_op)
    return t / n * 1e6 if t > 0 else None
