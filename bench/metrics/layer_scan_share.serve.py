"""layer_scan_share.serve: of the device time of the decode step's ops in
the traced window, the share, in %, whose ``op_name`` scope path holds no
model-layer scope (``embed``, ``norm``, ``mixer.*``, ``ffn``, ``moe``,
``lm_head``): the layer scan's own slicing, re-layout and write-back of the
stacked cache, and whatever else runs outside the layers.  Traced ops are
joined to the compiled step by instruction name (``bench/scopes.py``);
async copies in flight are no device work and are left out.  None where
the program carries no layer scopes.  Moves ``tpot_p95_ms``."""

from bench import scopes


def read(ctx):
    if ctx.reduced is None:
        return None
    table = scopes.ScopeTable(scopes.serve_step_text(ctx.cell))
    scopes.log_top(ctx.reduced.events, table, "decode step")
    return scopes.layer_scan_share(ctx.reduced.events, table)
