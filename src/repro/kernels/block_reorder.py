"""Block-reorder Pallas kernel — the MPI *derived datatype* on TPU.

The paper's round-k datatype describes, per peer ``j``, the strided block
traversal ``positions[t] + j*extent``.  On TPU the natural home for that
descriptor is ``BlockSpec.index_map``: the DMA engine performs the strided
HBM->VMEM block gather *during the copy it must do anyway* — an index map
is a derived datatype.

Offsets of the round-k traversal are runs of ``sigma(k)`` consecutive
blocks at bases ``sum_{m>k} i_m * sigma(m)`` (see ``core.simulator``), so
in units of sigma(k)-sized *tiles* the gather is exact:

    in-tile index  (j, u) -> j + f(u),   f(u) = sum_m i_m(u)*sigma(m)/sigma(k)
    out-tile index (j, u) -> j * (p / (D_k * sigma_k)) + u

with ``i_m(u)`` the mixed-radix digits of ``u`` over ``(D[k+1]...D[d-1])``
(column-major: ``i_{d-1}`` fastest).  Both maps are closed-form functions
of the grid indices — no materialized index arrays, no gather op.

This kernel is the *explicit-copy baseline*: an MPI library without
derived-datatype support would pack composite messages exactly like this
before every component all-to-all.  The zero-copy path
(``core.factorized``, natural variant) never runs it; benchmarks compare
the two to quantify what zero-copy saves.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.simulator import strides


def _digits_to_tile(u, uppers_dims, uppers_strides_tiles):
    """f(u): mixed-radix decompose u (column-major, last dim fastest) and
    re-linearize with the round's tile strides."""
    tile = 0
    # u enumerates itertools.product(*dims) with the LAST dim fastest.
    for dim, stride in zip(reversed(uppers_dims),
                           reversed(uppers_strides_tiles)):
        tile = tile + (u % dim) * stride
        u = u // dim
    return tile


def _pack_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _tile_copy(x, sigma_k, grid, in_map, out_map, interpret):
    """Copy ``(p, B)`` rows tile by tile, one sigma_k-row tile per step.

    ``x`` is viewed as ``(p // sigma_k, sigma_k, B)`` so that the last two
    dimensions of each ``(1, sigma_k, B)`` block are the array's own: the
    TPU's (8, 128) tiling rule then holds for any sigma_k, sigma_0 = 1
    included."""
    p, B = x.shape
    tiles = x.reshape(p // sigma_k, sigma_k, B)
    out = pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, sigma_k, B), in_map)],
        out_specs=pl.BlockSpec((1, sigma_k, B), out_map),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, x.dtype),
        interpret=interpret,
    )(tiles)
    return out.reshape(p, B)


@functools.partial(jax.jit, static_argnames=("dims", "k", "interpret"))
def datatype_pack(x, *, dims: tuple[int, ...], k: int,
                  interpret: bool = False):
    """Pack round-k composite messages contiguously (explicit-copy path).

    x: ``(p, B)`` block buffer.  Returns ``(p, B)`` where rows
    ``[j*p/D_k : (j+1)*p/D_k]`` are peer j's composite message in datatype
    order.  Equivalent to ``ref.ref_block_reorder`` with the round-k
    positions.
    """
    p = x.shape[0]
    d = len(dims)
    if math.prod(dims) != p:
        raise ValueError(f"prod(dims)={math.prod(dims)} != p={p}")
    sig = strides(dims)
    sigma_k = sig[k]
    Dk = dims[k]
    uppers = list(range(k + 1, d))
    uppers_dims = tuple(dims[m] for m in uppers)
    # Strides of the upper digits, in units of sigma_k-row tiles; the digit
    # m contributes sigma(m)/sigma(k) tiles.
    uppers_strides = tuple(sig[m] // sigma_k for m in uppers)
    n_upper = math.prod(uppers_dims) if uppers_dims else 1
    tiles_per_peer = p // (Dk * sigma_k)
    assert tiles_per_peer == n_upper

    grid = (Dk, n_upper)

    def in_map(j, u):
        base = _digits_to_tile(u, uppers_dims, uppers_strides)
        return (base + j, 0, 0)   # one sigma_k-row tile, full width

    def out_map(j, u):
        return (j * tiles_per_peer + u, 0, 0)

    return _tile_copy(x, sigma_k, grid, in_map, out_map, interpret)


@functools.partial(jax.jit, static_argnames=("dims", "k", "interpret"))
def datatype_unpack(y, *, dims: tuple[int, ...], k: int,
                    interpret: bool = False):
    """Inverse of ``datatype_pack``: scatter contiguous composite messages
    back into datatype positions (the receive-side explicit copy)."""
    p = y.shape[0]
    d = len(dims)
    sig = strides(dims)
    sigma_k = sig[k]
    Dk = dims[k]
    uppers = list(range(k + 1, d))
    uppers_dims = tuple(dims[m] for m in uppers)
    uppers_strides = tuple(sig[m] // sigma_k for m in uppers)
    n_upper = math.prod(uppers_dims) if uppers_dims else 1
    tiles_per_peer = p // (Dk * sigma_k)

    grid = (Dk, n_upper)

    def in_map(j, u):
        return (j * tiles_per_peer + u, 0, 0)

    def out_map(j, u):
        base = _digits_to_tile(u, uppers_dims, uppers_strides)
        return (base + j, 0, 0)

    return _tile_copy(y, sigma_k, grid, in_map, out_map, interpret)
