"""Device time by name scope.

The program's ``jax.named_scope``s (model layers, ``a2a[<backend>]``) reach
the compiled HLO's ``op_name`` metadata, but a profiler trace's op events
carry only the instruction's name.  Joining the two by instruction name
gives each traced op its scope path.

The compiled text is that of the program the window ran, rebuilt from the
cell after the window and taken from ``jit(...).lower(...).compile()
.as_text()``: the persistent compile cache serves it, so a traced run
compiles nothing anew where the window's program was cached, and an
untraced run never gets here.
"""

from __future__ import annotations

import re

from bench import harness
from bench.reduce import COLLECTIVE_OPS

UNSCOPED = "unscoped"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
# a scope is one component of the path, possibly inside a transform's
# parentheses: ".../while/body/mixer.attn/dot_general"
LAYER_SCOPE = re.compile(
    r"(?:^|[/(])(?:embed|norm|ffn|moe|lm_head|mixer\.[\w-]+)(?=[/)]|$)")
EXCHANGE_SCOPE = re.compile(r"(?:^|[/(])a2a\[[^\]/]*\](?=[/)]|$)")
# an async op's start: in a trace it spans from issue to done on the
# "Async XLA Ops" line, over the ops that run meanwhile, so it is no work
# of the device's own (a 5 KiB prefetch in flight across the whole layer
# scan reads 30 ms a tick)
ASYNC_START = re.compile(r"-start(?:\.\d+)?$")


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` scope path, for every instruction
    of the compiled module text; :data:`UNSCOPED` for one without
    metadata."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else UNSCOPED
    return out


class ScopeTable:
    """The scope paths of one compiled module, looked up by the names a
    trace gives its ops."""

    def __init__(self, hlo_text: str):
        self.paths = op_scopes(hlo_text)
        # a TPU trace may spell an instruction's hyphens as underscores
        # ("all_to_all.3" for "all-to-all.3")
        self._loose = {k.replace("_", "-"): v for k, v in self.paths.items()}

    def scope(self, name: str) -> str | None:
        """The op's scope path, or None when it is no instruction of this
        module."""
        path = self.paths.get(name)
        if path is None:
            path = self._loose.get(name.replace("_", "-"))
        return path

    def carries(self, pattern: re.Pattern) -> bool:
        """Whether any instruction's scope path matches ``pattern``: a
        program without the scope gives no reading."""
        return any(pattern.search(p) for p in self.paths.values())


def joined(events, table: ScopeTable) -> list[tuple[str, str, float]]:
    """``(name, scope path, seconds)`` for each traced op (``(name, text,
    seconds)`` as ``bench.reduce.Reduced.events`` holds them) that is an
    instruction of the table's module and not an async op's start."""
    out = []
    for name, _, sec in events:
        path = table.scope(name)
        if path is not None and not ASYNC_START.search(
                name.replace("_", "-")):
            out.append((name, path, sec))
    return out


def is_collective(name: str) -> bool:
    name = name.replace("_", "-")
    return any(op in name for op in COLLECTIVE_OPS)


def layer_scan_share(events, table: ScopeTable) -> float | None:
    """Of the device time of the module's ops, the share in % whose scope
    path holds no model-layer scope; None where the program carries no
    layer scopes or none of its ops ran."""
    if not table.carries(LAYER_SCOPE):
        return None
    ops = joined(events, table)
    total = sum(s for _, _, s in ops)
    if total <= 0:
        return None
    outside = sum(s for _, p, s in ops if not LAYER_SCOPE.search(p))
    return 100.0 * outside / total


def exchange_pack_share(events, table: ScopeTable) -> float | None:
    """Device time of the non-collective ops under ``a2a[*]`` scopes, in %
    of that time plus the collectives' time; None where the program
    carries no exchange scopes or none of those ops ran."""
    if not table.carries(EXCHANGE_SCOPE):
        return None
    pack = coll = 0.0
    for name, path, sec in joined(events, table):
        if is_collective(name):
            coll += sec
        elif EXCHANGE_SCOPE.search(path):
            pack += sec
    if pack + coll <= 0:
        return None
    return 100.0 * pack / (pack + coll)


def log_top(events, table: ScopeTable, what: str, top: int = 12):
    """Log the join's coverage and the longest ops with their scopes."""
    ops = joined(events, table)
    total = sum(s for _, _, s in events)
    mine = sum(s for _, _, s in ops)
    harness.log(f"scopes: {what}: {len(table.paths)} instructions; "
                f"{mine:.6f} of {total:.6f} s of traced op time joined")
    by_op: dict[tuple[str, str], float] = {}
    for name, path, sec in ops:
        by_op[name, path] = by_op.get((name, path), 0.0) + sec
    for (name, path), sec in sorted(by_op.items(), key=lambda kv: -kv[1])[
            :top]:
        harness.log(f"scopes:   {sec:.6f} s  {name}  {path}")


# ---------------------------------------------------------------------------
# the compiled text of the program a cell's window ran
# ---------------------------------------------------------------------------

def serve_step_text(cell) -> str:
    """The serving cell's jitted decode step, as ``bench.drivers.serve``
    builds and calls it, lowered at the window's shapes."""
    harness.use_program()
    import jax
    import jax.numpy as jnp
    from bench.drivers.serve import program
    from repro.models import make_serve_step
    from repro.parallel.sharding import ShardingRules

    c = cell.config
    ref = harness.load_reference(c)
    prog, model = program(c)
    max_batch, max_seq = c["serve"]["max_batch"], c["serve"]["max_seq"]
    params = jax.eval_shape(lambda k: ref.make_weights(c, k, prog.pdtype),
                            jax.random.key(0))
    caches = jax.eval_shape(lambda: model.init_caches(max_batch, max_seq))
    toks = jax.ShapeDtypeStruct((max_batch, 1), jnp.int32)
    step = jax.jit(make_serve_step(model, None, ShardingRules()))
    return step.lower(params, caches, toks, None).compile().as_text()


def exchange_text(cell) -> str:
    """The exchange cell's timed program, as ``bench.drivers.exchange``
    builds it, lowered at the window's shape and sharding."""
    harness.use_program()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench.drivers.exchange import exchange_program, geometry
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(jax.devices()[:cell.chips])
    prog, axes, p, block, plan = geometry(cell, mesh)
    spec = P(tuple(reversed(axes)))
    call = exchange_program(plan, mesh, spec, p,
                            int(cell.mix["round_trips_per_call"]))
    x = jax.ShapeDtypeStruct((p, p) + block, prog.cdtype,
                             sharding=NamedSharding(mesh, spec))
    return call.lower(x).compile().as_text()
