"""exchange_ici_share: the exchange's least time over its measured device
time, in %.  The least time is the bytes one chip sends in a plain
all-to-all of the same blocks, (p - 1) blocks, over the chip's published
ICI bandwidth: the same work whatever backend runs, so a schedule that
forwards blocks through another chip pays for it here.  Moves
``exchange_us``."""

from bench.peaks import exchange_least_s
from bench.reduce import is_exchange_op


def read(ctx):
    red, n = ctx.reduced, ctx.counters.get("exchanges_traced")
    if red is None or not n:
        return None
    t = red.time_where(is_exchange_op) / n
    if t <= 0:
        return None
    least = exchange_least_s(ctx.counters["p"], ctx.counters["block_bytes"],
                             ctx.peaks)
    return 100.0 * least / t
