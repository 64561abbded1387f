"""Structural zero-copy verification (paper §4) on compiled HLO.

Claims checked:
* natural variant ("TPU-native datatype" formulation): NO local
  data-movement ops at all — zero transpose/copy/gather in the whole
  compiled module, and nothing between the component collectives.  This is
  the paper's "formally zero-copy" property, realized structurally.
* paper variant (literal column-major composite construction): same
  collective schedule and byte volume; XLA is *allowed* to keep relayout
  ops (it does on the CPU backend — the MPI-datatype transliteration is
  strictly weaker than the natural axis form; recorded as a finding in
  EXPERIMENTS.md).
* both variants emit exactly d component collectives with identical
  collective bytes.

With ``--backend overlap|pipelined --operand flat|blocks`` it checks the
compute-free exchange of those backends instead (``pipelined`` resolves
to ``factorized``): ``forward`` and ``reverse`` on a flat ``(p, L)`` or a
``(p, E, C, d)`` operand, over a 2x2 and a 2x3 torus, each compile
exactly d all-to-alls per chunk that ``describe()["forward_chunks"]``
reports, that count is 1, and no op under the plan's ``a2a[<backend>]``
scope slices or concatenates: no chunks.
"""

import argparse
import math
import re
import sys

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.cache import cart_create
from repro.core.comm import torus_comm
from repro.core.hlo_inspect import parse_hlo


def compile_report(dims, names, variant, block=64):
    p = math.prod(dims)
    mesh = cart_create(p, dims, names)
    spec = P(tuple(reversed(names)))
    plan = torus_comm(mesh, names, variant=variant).all_to_all(
        (block,), jnp.float32, backend="factorized")

    def loc(xl):
        return plan.forward(xl[0])[None]

    f = jax.jit(jax.shard_map(loc, mesh=mesh, in_specs=spec, out_specs=spec))
    x = jax.ShapeDtypeStruct((p, p, block), jnp.float32)
    compiled = f.lower(x).compile()
    return parse_hlo(compiled.as_text())


def movement_count(rep):
    return sum(rep.op_counts.get(k, 0)
               for k in ("transpose", "copy", "gather"))


# compute-free operands: flat (p, L) and the MoE layer's (p, E, C, d)
OPERANDS = {"flat": (96,), "blocks": (2, 3, 8)}
PACKING_KINDS = ("concatenate", "slice", "dynamic-slice")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def is_packing(line, scope):
    """An op of the exchange's own, not of a collective's lowering: the
    CPU backend expands each all-to-all into slices and concatenates
    named ``.../all_to_all``."""
    return any(scope in path and not path.endswith("/all_to_all")
               for path in _OP_NAME.findall(line))


def compute_free_text(plan, mesh, block, direction):
    """Compiled module of one compute-free ``forward`` or ``reverse``."""
    spec = P(tuple(reversed(plan.axis_names)))
    run = getattr(plan, direction)
    f = jax.jit(jax.shard_map(lambda xl: run(xl[0])[None], mesh=mesh,
                              in_specs=spec, out_specs=spec))
    x = jax.ShapeDtypeStruct((plan.p, plan.p) + block, jnp.float32)
    return f.lower(x).compile().as_text()


def check_compute_free(backend, operand):
    block = OPERANDS[operand]
    for dims, names in [((2, 2), ("i", "j")), ((2, 3), ("i", "j"))]:
        d = len(dims)
        mesh = cart_create(math.prod(dims), dims, names)
        plan = torus_comm(mesh, names).all_to_all(
            block, jnp.float32, backend=backend, n_chunks=2)
        chunks = plan.describe()["forward_chunks"]
        assert chunks == 1, chunks
        scope = f"a2a[{plan.backend}]"
        for direction in ("forward", "reverse"):
            text = compute_free_text(plan, mesh, block, direction)
            rep = parse_hlo(text)
            n_a2a = sum(o.kind == "all-to-all" for o in rep.ops)
            assert n_a2a == d * chunks, (
                f"{backend} {direction} {dims}: {n_a2a} all-to-alls, "
                f"expected {d} per chunk x {chunks} chunk")
            packing = [o.line for o in rep.ops
                       if o.kind in PACKING_KINDS and is_packing(o.line,
                                                                 scope)]
            assert not packing, (
                f"{backend} {direction} {dims} packs under {scope}: "
                f"{packing}")
            assert any(scope in path for path in _OP_NAME.findall(text))
        print(f"OK compute-free {backend} {operand} {block} dims={dims}: "
              f"{d} all-to-alls per direction, no chunk packing under "
              f"{scope}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=("overlap", "pipelined"))
    ap.add_argument("--operand", choices=tuple(OPERANDS), default="flat")
    args = ap.parse_args()
    if args.backend:
        check_compute_free(args.backend, args.operand)
        return 0

    for dims, names in [((2, 3, 2), ("i", "j", "k")), ((3, 4), ("i", "j"))]:
        d = len(dims)
        nat = compile_report(dims, names, "natural")
        pap = compile_report(dims, names, "paper")

        # Natural variant: formally zero-copy, structurally verified.
        n_mv = movement_count(nat)
        assert n_mv == 0, (
            f"natural variant not zero-copy: "
            f"{[o.line for o in nat.ops if o.kind in ('transpose','copy','gather')]}")
        assert not nat.movement_ops_between_collectives()
        assert len(nat.collective_ops()) == d, (
            f"expected {d} component collectives, got "
            f"{len(nat.collective_ops())}")

        # Paper variant: same schedule/volume; report its residual relayouts.
        assert len(pap.collective_ops()) == d
        assert nat.collective_bytes() == pap.collective_bytes() > 0
        p_mv = movement_count(pap)
        assert n_mv <= p_mv, "natural variant should never move more data"
        print(f"OK dims={dims}: {d} collectives, zero-copy verified "
              f"(natural movement-ops=0, paper-literal={p_mv}), "
              f"coll_bytes={nat.collective_bytes():.0f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
