"""exchange_pack_share: the device time of the non-collective ops under
the program's ``a2a[<backend>]`` scopes (local packing and unpacking), in
% of that time plus the time of the collectives, over the traced window.
The paper's exchange is zero-copy: it reorders nothing locally.  Traced
ops are joined to the compiled program by instruction name
(``bench/scopes.py``).  None where the program carries no exchange scopes.
Moves ``exchange_us``."""

from bench import scopes


def read(ctx):
    if ctx.reduced is None:
        return None
    table = scopes.ScopeTable(scopes.exchange_text(ctx.cell))
    scopes.log_top(ctx.reduced.events, table, "exchange program")
    return scopes.exchange_pack_share(ctx.reduced.events, table)
