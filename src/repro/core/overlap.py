"""Overlap engine: double-buffered round/compute software pipelining.

The paper's algorithm is a sequence of d per-dimension collectives glued by
double buffering; its §5 conclusion is that the win comes from tuning the
schedule to the machine.  This module is that tuning knob taken one step
further: a chunked, software-pipelined scheduler that interleaves the
dimension-wise *rounds* of independent payload chunks with a per-chunk
*compute stage*, so the rounds of one chunk can hide behind consumer
compute of another (MoE expert FFN, Ulysses attention).

Chunks exist for the compute stage alone, so the engine requires one.
Without one, ``A2APlan`` runs the d rounds on the whole ``(p, *block)``
buffer in one torus view (``factorized._factorized_impl``): chunking
would only add the local slicing, re-tiling and concatenation that the
zero-copy exchange avoids, and hide nothing: on the TPU v5e the
all-to-alls are synchronous ops (no ``-start``/``-done`` pair) that run
one after another, so the rounds of different chunks never overlap.

Per chunk the stage list is::

    [round k0, ..., round k_{d-1}]  + [compute]  (+ [rev k'0, ..., rev k'_{d-1}])

and the engine emits stage ``s`` of chunk ``c`` at pipeline step ``t = c +
s``, deepest stage first within a step, i.e. the program order

    chunk c-2 reverse-round k' ; chunk c-1 compute ; chunk c round k ; ...

Chunk ``c``'s stages depend only on chunk ``c``'s earlier stages, so every
step's ops are mutually independent: adjacent in program order, they are
what XLA's latency-hiding scheduler may overlap where the collectives are
asynchronous.  Correctness is independent of scheduling — the
interleaving only reorders independent ops (property- and parity-tested
against ``factorized`` and ``direct``).

Cost model: see ``tuning.predict_overlapped`` — it assumes the rounds of
different chunks run concurrently on different dimension links, dividing
the bandwidth term by ~min(d, n_chunks), while the latency term stretches
by the pipeline fill ``(d + n - 1)/d``; ``tuning.choose_chunks`` picks the
argmin.  ``backend="tuned"`` still picks ``overlap`` by that price for a
compute-free exchange, which then runs unchunked.
"""

from __future__ import annotations

import math
from typing import Callable, Sequence

import jax.numpy as jnp
import numpy as np
from jax import lax

from .factorized import (
    _as_tuple,
    _axis_sizes,
    _skip_trivial,
    _warn_deprecated,
)


# ---------------------------------------------------------------------------
# Generic software-pipeline scheduler
# ---------------------------------------------------------------------------

def pipeline_order(n_chunks: int, n_stages: int):
    """Emission order of the software pipeline: yields ``(chunk, stage)``.

    Stage ``s`` of chunk ``c`` runs at step ``t = c + s``; within a step the
    deepest stage (oldest chunk) is emitted first, so a 2-chunk, 5-stage
    program (2 fwd rounds, compute, 2 rev rounds) reads

        c0.r0 | c0.r1 c1.r0 | c0.comp c1.r1 | c0.rev0 c1.comp | ...

    — chunk 1's forward round and chunk 0's reverse round sit *between* the
    two compute stages, which is the structure ``hlo_inspect
    .interleave_report`` verifies on the lowered program.
    """
    for t in range(n_chunks + n_stages - 1):
        for c in range(n_chunks):
            s = t - c
            if 0 <= s < n_stages:
                yield c, s


def run_pipelined(states: Sequence, stages: Sequence[Callable]):
    """Run every chunk state through every stage in pipelined program order.

    ``stages[s]`` is called as ``stages[s](state, chunk_index)`` and returns
    the new state.  Pure program-order transformation: the result is
    identical to running each chunk's stages back to back.
    """
    states = list(states)
    for c, s in pipeline_order(len(states), len(stages)):
        states[c] = stages[s](states[c], c)
    return states


# ---------------------------------------------------------------------------
# Per-round stage construction (the torus round schedule)
# ---------------------------------------------------------------------------

def _round_stages(names, sizes, variant, order):
    """One closure per round, operating on the d-dim block *view*
    (axes ``[dim d-1, ..., dim 0, *block]``, dim 0 fastest)."""
    d = len(sizes)
    pos = lambda m: d - 1 - m

    def natural(k):
        def stage(view, _c):
            return lax.all_to_all(view, names[k], split_axis=pos(k),
                                  concat_axis=pos(k), tiled=False)
        return stage

    def paper(k):
        def stage(view, _c):
            nb = view.ndim - d
            perm = ([pos(k)]
                    + [pos(m) for m in range(k + 1, d)]
                    + [pos(m) for m in range(k - 1, -1, -1)]
                    + [d + i for i in range(nb)])
            inv = tuple(int(i) for i in np.argsort(perm))
            out = view.transpose(perm)
            out = lax.all_to_all(out, names[k], split_axis=0, concat_axis=0,
                                 tiled=False)
            return out.transpose(inv)
        return stage

    if variant == "natural":
        return [natural(k) for k in order]
    if variant == "paper":
        return [paper(k) for k in order]
    raise ValueError(f"unknown variant {variant!r}")


def _check_order(order, d):
    order = tuple(order) if order is not None else tuple(range(d))
    if sorted(order) != list(range(d)):
        raise ValueError(f"round_order {order} is not a permutation of 0..{d-1}")
    return order


def _split_chunks(x, axis, n_chunks):
    """Split ``x`` along ``axis`` into the largest feasible number of equal
    chunks <= ``n_chunks`` (shrink until the axis size divides)."""
    size = x.shape[axis]
    n = max(1, min(n_chunks, size))
    while size % n:
        n -= 1
    step = size // n
    idx = [slice(None)] * x.ndim
    out = []
    for c in range(n):
        idx[axis] = slice(c * step, (c + 1) * step)
        out.append(x[tuple(idx)])
    return out


# ---------------------------------------------------------------------------
# The overlapped all-to-all
# ---------------------------------------------------------------------------

def _overlapped_impl(x, axis_names, compute_fn: Callable, *,
                     n_chunks: int = 2, variant: str = "natural",
                     round_order=None, reverse: bool = False,
                     reverse_round_order=None,
                     chunk_axis: int | None = None):
    """Chunked, software-pipelined factorized all-to-all with a per-chunk
    compute stage and an optional reverse (combine) all-to-all.

    Chunks exist to interleave rounds with the compute stage; without one
    the caller runs the unchunked rounds (``A2APlan.forward`` /
    ``.reverse``, ``factorized._factorized_impl``).

    Args:
      x: local ``(p, *block)`` array, ``p`` = product of the named axis
        sizes; block ``i`` is destined for torus rank ``i``.
      axis_names: torus dimensions, fastest digit first.
      n_chunks: target chunk count (shrunk to a divisor of the chunked
        extent; 1 disables pipelining but still runs fwd/compute/reverse).
      variant: per-round formulation, "natural" (zero-copy) or "paper".
      round_order: forward round permutation (default ``range(d)``).
      compute_fn: ``f(chunk, chunk_index) -> chunk`` applied to each
        chunk *after* its forward rounds; must preserve the chunk's
        shape.  Called on the ``(p, *chunk_block)`` layout.
      reverse: append a second (combine-direction) all-to-all after the
        compute stage — the MoE dispatch/combine shape.
      reverse_round_order: round permutation for the reverse all-to-all
        (default: forward order reversed, so the pipeline drains the
        dimension links in the opposite order it filled them).
      chunk_axis: which axis of ``x`` (>= 1) to chunk.  Default: the
        trailing payload is flattened and chunked.

    Returns ``(p, *block)`` with the same semantics as composing
    ``factorized_all_to_all`` (+ ``compute_fn`` + ``factorized_all_to_all``)
    on the whole payload — bit-exact, since chunks never interact.
    """
    axis_names = _as_tuple(axis_names)
    dims = _axis_sizes(axis_names)
    p = math.prod(dims)
    if x.shape[0] != p:
        raise ValueError(f"leading dim {x.shape[0]} != prod(dims)={p} ({dims})")
    names, sizes = _skip_trivial(axis_names, dims)
    d = len(sizes)
    order = _check_order(round_order, d)
    rev_order = (tuple(reversed(order)) if reverse_round_order is None
                 else _check_order(reverse_round_order, d))

    # ---- chunking ----
    if chunk_axis is None:
        payload = math.prod(x.shape[1:]) if x.ndim > 1 else 1
        flat = x.reshape(p, payload)
        chunks = _split_chunks(flat, 1, n_chunks if payload else 1)
    else:
        if not 1 <= chunk_axis < x.ndim:
            raise ValueError(f"chunk_axis {chunk_axis} out of range for "
                             f"rank-{x.ndim} operand")
        chunks = _split_chunks(x, chunk_axis, n_chunks)

    # ---- per-chunk stage list ----
    view_prefix = tuple(reversed(sizes))

    def to_view(chunk):
        return chunk.reshape(view_prefix + chunk.shape[1:])

    def to_blocks(view):
        return view.reshape((p,) + view.shape[d:])

    def compute_stage(view, c):
        return to_view(compute_fn(to_blocks(view), c))

    stages = list(_round_stages(names, sizes, variant, order))
    stages.append(compute_stage)
    if reverse:
        stages.extend(_round_stages(names, sizes, variant, rev_order))

    views = run_pipelined([to_view(c) for c in chunks], stages)
    outs = [to_blocks(v) for v in views]
    if chunk_axis is None:
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        return out.reshape((p,) + x.shape[1:])
    return outs[0] if len(outs) == 1 else \
        jnp.concatenate(outs, axis=chunk_axis)


# ---------------------------------------------------------------------------
# Deprecated free-function shims (see core.factorized for the policy): each
# builds-or-fetches an A2APlan and delegates, staying bit-exact with plan
# execution.  Internal call sites must use plans directly.
# ---------------------------------------------------------------------------


def overlapped_all_to_all(x, axis_names, *, n_chunks: int = 2,
                          variant: str = "natural", round_order=None,
                          compute_fn: Callable | None = None,
                          reverse: bool = False, reverse_round_order=None,
                          chunk_axis: int | None = None):
    """Deprecated: use ``plan_all_to_all(..., backend="overlap")
    .overlap``."""
    _warn_deprecated("overlapped_all_to_all", "plan.overlap")
    from .plan import plan_all_to_all
    names = _as_tuple(axis_names)
    plan = plan_all_to_all(_axis_sizes(names), names, x.shape[1:], x.dtype,
                           backend="overlap", variant=variant,
                           round_order=round_order,
                           reverse_round_order=reverse_round_order,
                           n_chunks=max(1, n_chunks))
    return plan.overlap(x, compute_fn, reverse=reverse,
                        chunk_axis=chunk_axis)


def overlapped_all_to_all_tiled(x, axis_names, split_axis, concat_axis, *,
                                n_chunks: int = 2, variant: str = "natural",
                                round_order=None):
    """Deprecated: use ``plan_all_to_all(..., backend="overlap").tiled``."""
    _warn_deprecated("overlapped_all_to_all_tiled", "plan.tiled")
    from .plan import plan_all_to_all
    names = _as_tuple(axis_names)
    plan = plan_all_to_all(_axis_sizes(names), names, None, x.dtype,
                           backend="overlap", variant=variant,
                           round_order=round_order,
                           n_chunks=max(1, n_chunks))
    return plan.tiled(x, split_axis, concat_axis)


def pipelined_all_to_all(x, axis_names, *, n_chunks: int = 2,
                         variant: str = "natural", round_order=None):
    """Deprecated: use ``plan_all_to_all(..., backend="factorized")
    .forward``.  With no compute stage there is nothing to chunk for, so
    ``n_chunks`` is ignored and the whole payload runs the d rounds."""
    _warn_deprecated("pipelined_all_to_all", "plan.forward")
    from .plan import plan_all_to_all
    names = _as_tuple(axis_names)
    plan = plan_all_to_all(_axis_sizes(names), names, x.shape[1:], x.dtype,
                           backend="factorized", variant=variant,
                           round_order=round_order)
    return plan.forward(x)
